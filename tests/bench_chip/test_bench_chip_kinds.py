"""A model kind becomes a cell by files and entries alone.

``data/toy_kind`` holds a kind the harness was not written for
(``softreg``: softmax regression on Gaussian blobs, with data keys and
work counts of its own) with its config, mix and limits.  Each test copies
the benchmark's files beside it, adds the config and cell to a copy of
``BENCHMARK.json``, and points the harness's paths there; the harness's
code is the repository's.  On the CPU the toy cell runs whole, passes its
comparison on the program's own ``run_scenario`` trained with the toy's
loss, reads ``step_mfu`` and ``agg_kernels_roofline`` from the toy's
counts, and its control and planted faults fail its limits.  Also the
refusals: a config naming no kind or a kind with no file, a module that
lacks a name, a mix with a key neither the harness nor the kind reads, a
partition the kind does not build, and data of another fleet shape than
the mix states."""
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache.compilation_cache import \
    reset_cache

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import counts, faults, harness  # noqa: E402
from benchmarks.chip import trace_reduce  # noqa: E402

HERE = Path(__file__).resolve().parent
TOY = HERE / "data" / "toy_kind"
RECORDED = HERE / "data" / "paper_csr10.xplane.pb"
CELL = "softreg.blobs"


@pytest.fixture(autouse=True)
def cpu_for_chip(monkeypatch, tmp_path):
    """The CPU stands in for the chip, with a persistent compilation cache
    of its own, as the command keeps one (the suite turns the cache
    off)."""
    monkeypatch.setattr(harness, "check_device",
                        lambda chips: harness.describe_device())
    cache = tmp_path / "jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    kept = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    harness.use_compile_cache()
    reset_cache()
    yield
    for k, v in kept.items():
        jax.config.update(k, v)
    reset_cache()


@pytest.fixture
def chip(tmp_path, monkeypatch):
    """A copy of the benchmark's files with the toy kind's added, and the
    harness pointed at it; returns the copy's ``benchmarks/chip``."""
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(harness.CHIP, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("models", "configs", "traffic", "limits"):
        for f in (TOY / kind).iterdir():
            shutil.copy(f, chip / kind / f.name)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "softreg", "source": "softmax regression",
        "file": "benchmarks/chip/configs/softreg.json", "reduced": [],
        "why": "a kind the harness was not written for"})
    bench["workloads"].append({
        "name": CELL, "config": "softreg", "traffic": "blobs", "chips": 1,
        "why": "8 agents under 2 RSUs, 16 samples each"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "CHIP", chip)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    return chip


def _run(cell, seed, trace=False):
    return harness.run(cell, seed, 1.0, trace,
                       process_start=time.perf_counter())


def test_a_new_kind_runs_as_a_cell_on_its_own_loss(chip, monkeypatch):
    from repro.fedsim import sweep
    run_scenario, losses = sweep.run_scenario, []

    def spy(res, params, **kw):
        losses.append(kw.get("loss_fn"))
        return run_scenario(res, params, **kw)

    monkeypatch.setattr(sweep, "run_scenario", spy)
    cell = harness.load_cell(CELL)
    assert cell.model.__name__.endswith("softreg")
    r = _run(cell, 21)
    assert r["correct"] is True and r["failed"] == 0, r["checks"]
    assert set(r["checks"]) == set(cell.limits) - {"readings"}
    assert {"round_ms", "round_p95_ms", "setup_s"} <= set(r["metrics"])
    toy_loss = cell.model.program_loss(cell.config)
    assert len(losses) >= 2 and all(f is toy_loss for f in losses)


def test_a_new_kind_reads_step_mfu_and_agg_kernels_roofline(chip,
                                                            monkeypatch):
    # the CPU writes no device trace: a trace recorded on a v5e stands in
    # for the reduction of this run's own
    recorded = trace_reduce.reduce_file(str(RECORDED))
    monkeypatch.setattr(trace_reduce, "reduce_file", lambda path: recorded)
    monkeypatch.setattr(harness, "check_device", lambda chips: {
        **harness.describe_device(), "kind": "TPU v5 lite"})
    read_metrics, seen = harness.read_metrics, []

    def keep(entries, ctx):
        seen.append(ctx)
        return read_metrics(entries, ctx)

    monkeypatch.setattr(harness, "read_metrics", keep)
    cell = harness.load_cell(CELL)
    r = _run(cell, 22, trace=True)
    assert r["correct"] is True, r["checks"]
    ctx, m = seen[0], r["metrics"]
    d, t = ctx.draws, cell.traffic
    peak_flops, peak_bytes = 197e12, 819e9
    # softmax regression: forward and weight gradient, 2 x 2 x 16 x 4
    flops = float(d["live_steps"].sum()) * t["batch"] * 4 * 16 * 4
    assert m["step_mfu"]["value"] == pytest.approx(
        flops / recorded["window_s"] / peak_flops * 100.0, rel=1e-12)
    n = 16 * 4 + 4
    least = sum(max(2 * c * n / peak_flops, (c + h) * n * 4 / peak_bytes)
                for c, h in zip(d["connected"].ravel(),
                                d["rsus_hit"].ravel()))
    least += sum(max(2 * k * n / peak_flops, (k * n * 4 + n * 4) / peak_bytes)
                 for k in d["cloud_rsus"].ravel() if k)
    assert m["agg_kernels_roofline"]["value"] == pytest.approx(
        least / counts.agg_kernel_seconds(recorded) * 100.0, rel=1e-12)


def test_the_control_of_a_new_kind_fails_its_limits(chip):
    cell = harness.load_cell(CELL)
    data, res = harness.prepare(cell, 23)
    ref = harness.reference_rounds(cell, data, res)
    ctrl = harness.reference_rounds(cell, data, res, mode="bf16x3")
    got = harness.numbers(cell, ctrl, ref, jax.device_get(data.params),
                          data.x_test, data.y_test)
    assert any(v > cell.limits[k] for k, v in got.items()
               if k in cell.limits), got


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_planted_fault_in_a_new_kind_is_not_correct(chip, fault):
    cell = harness.load_cell(CELL)
    with faults.FAULTS[fault]():
        r = _run(cell, 24)
    assert r["correct"] is False, r["checks"]


def _edit_config(chip, **change):
    path = chip / "configs" / "softreg.json"
    conf = {k: v for k, v in {**json.loads(path.read_text()),
                              **change}.items() if v is not None}
    path.write_text(json.dumps(conf))


def test_a_config_whose_kind_has_no_file_is_refused(chip):
    _edit_config(chip, model="nosuch")
    with pytest.raises(SystemExit, match="no file models/nosuch.py"):
        harness.load_cell(CELL)


def test_a_config_that_names_no_kind_is_refused(chip):
    _edit_config(chip, model=None)
    with pytest.raises(SystemExit, match="names no model kind"):
        harness.load_cell(CELL)


@pytest.mark.parametrize("name", harness.MODEL_NAMES)
def test_a_kind_that_lacks_a_name_is_refused(chip, name):
    src = (chip / "models" / "softreg.py").read_text()
    (chip / "models" / "partial.py").write_text(f"{src}\ndel {name}\n")
    _edit_config(chip, model="partial")
    with pytest.raises(SystemExit, match=rf"lacks \['{name}'\]"):
        harness.load_cell(CELL)


@pytest.mark.parametrize("change", [
    {"noise": 0.8}, {"oem_pool": 2640}, {"spread": None},
    {"labels_per_agent": None}, {"n_agents": None},
    {"partition": "scenario_one"}, {"partition": "dirichlet"},
    {"engine": "async"}])
def test_a_mix_the_kind_does_not_drive_is_refused(chip, change):
    model = harness.load_model("softreg")
    mix = json.loads((chip / "traffic" / "blobs.json").read_text())
    assert harness.check_traffic("mix", dict(mix), model) == mix
    bad = {k: v for k, v in {**mix, **change}.items() if v is not None}
    with pytest.raises(SystemExit, match="traffic 'mix'"):
        harness.check_traffic("mix", bad, model)


def test_the_paper_mix_is_refused_for_another_kind(chip):
    paper = json.loads((chip / "traffic" / "paper_csr10.json").read_text())
    with pytest.raises(SystemExit, match=r"does not drive \['excluded"):
        harness.check_traffic("paper_csr10", paper,
                              harness.load_model("softreg"))


def test_data_of_another_fleet_shape_is_refused(chip):
    path = chip / "traffic" / "blobs.json"
    mix = json.loads(path.read_text())
    path.write_text(json.dumps({**mix, "samples_per_agent": 12}))
    cell = harness.load_cell(CELL)
    with pytest.raises(SystemExit, match="the mix states"):
        harness.prepare(cell, 25)
