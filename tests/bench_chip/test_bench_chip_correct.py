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
import numpy as np
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
    ev = harness.Eval(cell.model.evaluate, data.x_test, data.y_test)
    ev.reset(harness.COMPARED)
    harness.timed_call(cell, res, data.params, ev, harness.COMPARED)
    ref = harness.reference_rounds(cell, data, res)
    return data, res, ev.captured, ref


def _numbers(cell, data, got, ref):
    return harness.numbers(cell, got, ref, jax.device_get(data.params),
                           data.x_test, data.y_test)


def test_the_program_passes_its_limits(cell, rounds):
    data, _, prog, ref = rounds
    got = _numbers(cell, data, prog, ref)
    compared = set(cell.limits) - {"readings"}
    skipped = set(cell.limits["readings"].get("not_compared", {}))
    assert compared and compared | skipped == set(got) == set(
        harness.NUMBERS)
    for k in compared:
        assert got[k] <= cell.limits[k], (k, got[k], cell.limits[k])


def test_the_control_fails_its_limits(cell, rounds):
    data, res, _, ref = rounds
    ctrl = harness.reference_rounds(cell, data, res, mode="bf16x3")
    got = _numbers(cell, data, ctrl, ref)
    assert any(v > cell.limits[k] for k, v in got.items()
               if k in cell.limits), got


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


def test_a_gap_in_a_few_elements_leaves_the_median_gap_unmoved():
    rng = np.random.default_rng(0)
    base = {"w": rng.normal(size=(784, 40)), "b": rng.normal(size=40)}
    ref = {k: v + rng.normal(size=v.shape) * 1e-2 for k, v in base.items()}
    # one sample's term in one hidden unit: one column and one bias
    flip = {"w": ref["w"].copy(), "b": ref["b"].copy()}
    flip["w"][:, 19] += 1e-2
    flip["b"][19] += 1e-2
    assert harness.median_gap(flip, ref, base) == 0.0
    assert harness.leaf_gap(flip, ref, base) > 1e-3
    # a lower precision moves every element
    low = {k: v * (1 + 1e-4) for k, v in ref.items()}
    assert harness.median_gap(low, ref, base) > 1e-3
    assert harness.median_gap(base, ref, base) == 1.0


def test_a_leaf_the_reference_mostly_leaves_unmoved_has_a_median_gap():
    # rows no batch touched: the reference moves 10 of 100 rows, exactly
    rng = np.random.default_rng(1)
    base = {"emb": rng.normal(size=(100, 8)), "b": rng.normal(size=8)}
    ref = {k: v.copy() for k, v in base.items()}
    ref["emb"][:10] += rng.normal(size=(10, 8)) * 1e-2
    ref["b"] += rng.normal(size=8) * 1e-2
    assert harness.median_gap(ref, ref, base) == 0.0
    low = {k: base[k] + (ref[k] - base[k]) * (1 + 1e-4) for k in base}
    assert harness.median_gap(low, ref, base) == pytest.approx(1e-4)
    assert harness.median_gap(base, ref, base) == 1.0
    # a gap in rows the reference left alone is the norm numbers' to see
    stray = {k: v.copy() for k, v in ref.items()}
    stray["emb"][50:] += 1e-2
    assert harness.median_gap(stray, ref, base) == 0.0
    assert harness.leaf_gap(stray, ref, base) > 1.0


def _renamed(limits, old, new):
    return {(new if k == old else k): v for k, v in limits.items()}


LIMIT_EDITS = {
    "compares_nothing": lambda l: {"readings": {"not_compared": {
        k: {} for k in harness.NUMBERS}}},
    "misspelt": lambda l: _renamed(l, "update1_median", "update1_med"),
    "unaccounted_for": lambda l: {k: v for k, v in l.items()
                                  if k != "change3"},
    "compared_and_not": lambda l: {**l, "readings": {
        **l["readings"], "not_compared": {"loss": {}}}},
    "a_string": lambda l: {**l, "acc": "0.01"},
    "nan": lambda l: {**l, "acc": float("nan")},
    "negative": lambda l: {**l, "acc": -1.0},
}


@pytest.mark.parametrize("edit", sorted(LIMIT_EDITS))
def test_limits_that_compare_less_than_they_seem_are_refused(cell, edit):
    """Every number is compared or named as not compared, each limit is a
    number of at least 0, and a file that compares nothing, misspells a
    number or leaves one unaccounted for ends the run."""
    assert harness.check_limits(PAPER, cell.limits) == cell.limits
    with pytest.raises(SystemExit, match=f"limits of '{PAPER}'"):
        harness.check_limits(PAPER, LIMIT_EDITS[edit](cell.limits))


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_planted_fault_is_not_correct(cell, fault):
    with faults.FAULTS[fault]():
        r = _run(cell, 13)
    assert r["correct"] is False, r["checks"]


def test_a_compile_in_the_window_ends_the_run(cell, monkeypatch):
    timed = harness.timed_call

    def compiling(cell, res, params, ev, rounds):
        if ev.capture:     # only the window keeps rounds for the check
            fresh = float(time.time_ns() % 1_000_003)
            jax.jit(lambda x: x * fresh)(jnp.ones(3)).block_until_ready()
        return timed(cell, res, params, ev, rounds)

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
        harness.check_traffic("mix", mix, cell.model)
    assert harness.check_traffic("mix", dict(cell.traffic),
                                 cell.model) == cell.traffic


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
        assert harness.compared(cell.limits)
    paper = harness.load_cell(PAPER, bench)
    assert harness.compared(paper.limits) == list(harness.NUMBERS)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])
