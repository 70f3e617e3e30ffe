"""The comparison that decides ``correct``: at the paper cell's own size on
the CPU, the program passes its limits, the control (the reference in
three-pass bfloat16) fails them, and a run with a fault planted under the
timed path comes out not correct.  Also: the command refuses to run
without a TPU, and the benchmark's files are whole."""
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache.compilation_cache import \
    reset_cache

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import faults, harness  # noqa: E402

PAPER = "h2fed_mlp.paper_csr10"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(autouse=True)
def cpu_for_chip(monkeypatch, tmp_path):
    """The CPU stands in for the chip; the rest of a run is as on it,
    with a persistent compilation cache of its own, as the command keeps
    one (the suite turns the cache off)."""
    monkeypatch.setattr(harness, "check_device",
                        lambda chips: harness.describe_device())
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    kept = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    harness.use_compile_cache()
    reset_cache()
    yield
    for k, v in kept.items():
        jax.config.update(k, v)
    reset_cache()


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(PAPER)


@pytest.fixture(scope="module")
def rounds(cell):
    data, res = harness.prepare(cell, 11)
    ev = harness.Eval(data.x_test, data.y_test)
    ev.reset(harness.COMPARED)
    harness.timed_call(res, data.params, ev, harness.COMPARED,
                       cell.config["matmul_precision"])
    ref = harness.reference_rounds(cell, data, res)
    return data, res, ev.captured, ref


def _numbers(data, got, ref):
    return harness.numbers(got, ref, jax.device_get(data.params),
                           data.x_test, data.y_test)


def test_the_program_passes_its_limits(cell, rounds):
    data, _, prog, ref = rounds
    got = _numbers(data, prog, ref)
    assert set(got) == set(cell.limits) - {"readings"}
    for k, v in got.items():
        assert v <= cell.limits[k], (k, v, cell.limits[k])


def test_the_control_fails_its_limits(cell, rounds):
    data, res, _, ref = rounds
    ctrl = harness.reference_rounds(cell, data, res, mode="bf16x3")
    got = _numbers(data, ctrl, ref)
    assert any(v > cell.limits[k] for k, v in got.items()), got


def _run(cell, seed):
    return harness.run(cell, seed, 1.0, False,
                       process_start=time.perf_counter())


def test_a_sound_run_is_correct(cell):
    r = _run(cell, 12)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] >= harness.MIN_ROUNDS
    assert list(r)[-1] == "checks"
    assert {"round_ms", "round_p95_ms", "setup_s"} <= set(r["metrics"])
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_planted_fault_is_not_correct(cell, fault):
    with faults.FAULTS[fault]():
        r = _run(cell, 13)
    assert r["correct"] is False, r["checks"]


def test_a_compile_in_the_window_ends_the_run(cell, monkeypatch):
    timed = harness.timed_call

    def compiling(res, params, ev, rounds, precision):
        if ev.capture:     # only the window keeps rounds for the check
            fresh = float(time.time_ns() % 1_000_003)
            jax.jit(lambda x: x * fresh)(jnp.ones(3)).block_until_ready()
        return timed(res, params, ev, rounds, precision)

    monkeypatch.setattr(harness, "timed_call", compiling)
    with pytest.raises(SystemExit, match="compiles in the measured window"):
        _run(cell, 14)


@pytest.mark.parametrize("change", [
    {"rsu_sharded": True}, {"engine": "sharded"}, {"engine": "async"},
    {"partition": "scenario_one"}, {"eval_every": None}])
def test_a_mix_the_harness_does_not_drive_is_refused(cell, change):
    mix = {k: v for k, v in {**cell.traffic, **change}.items()
           if v is not None}
    with pytest.raises(SystemExit, match="traffic 'mix'"):
        harness.check_traffic("mix", mix)
    assert harness.check_traffic("mix", dict(cell.traffic)) == cell.traffic


def _command(cwd, env_extra=None):
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        bench["command"] + ["--workload", PAPER, "--seed", "1",
                            "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    out = _command(harness.ROOT)
    assert out.returncode != 0
    assert "correct" not in out.stdout


def test_the_benchmark_alone_exits_nonzero(tmp_path):
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(harness.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0
    assert "correct" not in out.stdout


def test_benchmark_json_names_whole_files():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"])
        assert (harness.CHIP / "metrics" / f"{m['name']}.py").exists()
    for c in configs.values():
        conf = json.loads((harness.ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        cell = harness.load_cell(w["name"], bench)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        assert {"loss", "acc", "update1", "change3"} <= set(cell.limits)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])
