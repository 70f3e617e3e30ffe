"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the metrics.

Everything that belongs to one cell is found by name: the entry in
``BENCHMARK.json``, ``configs/<config>.json`` (sizes, dtype, precision,
and ``"model"``, the model kind), ``models/<kind>.py`` (what depends on
the model: its data recipe, the loss the program trains with, the plain
reference model and the work counts; ``MODEL_NAMES``),
``traffic/<mix>.json`` (fleet, data recipe, heterogeneity),
``limits/<cell>.json`` (the limit of each number compared) and
``metrics/<metric>.py`` (one reader per metric).  Adding a cell, a
configuration, a model kind, a mix or a metric adds files and entries;
nothing here changes, as long as the mix keeps to what this harness
drives: the flat engine, with exactly the keys of ``ALGORITHM_KEYS`` and
of the model kind's ``DATA_KEYS``, on a partition the kind builds.  A mix
that asks for anything else is refused, since its knobs would reach
neither the program nor the reference.

The window drives ``repro.fedsim.run_scenario`` with the flat engine, the
entry users call, from the pretrained model, sized from the warm-up to
fill the requested seconds.  The benchmark's ``eval_fn`` stamps the host
clock after each round's test accuracy is on the host, and keeps the cloud
model of the first ``COMPARED`` rounds for the comparison.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
CHIP = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax                                          # noqa: E402
import numpy as np                                  # noqa: E402

from benchmarks.chip import datagen, reference      # noqa: E402
from benchmarks.chip import trace_reduce            # noqa: E402

COMPARED = 3          # global rounds compared with the reference
MIN_ROUNDS = COMPARED + 1
WARM_ROUNDS = 5       # rounds of the first warm-up call
WARM_SECONDS = 0.5    # and about the seconds of rounds of the next
WINDOW_SPAN = "bench.run_scenario"
TRACED_SPAN = trace_reduce.WINDOW_SPAN
EVAL_SPAN = "bench.eval"
TRACE_AFTER = 1       # the traced rounds start after the call's first
TRACE_SECONDS = 2.0   # and last about this long
TRACE_DIR = ROOT / "results" / "bench_chip_trace"
# the keys of a traffic mix that the harness itself drives, whatever the
# model: the fleet, the H2-Fed hyper-parameters, heterogeneity and the run;
# a mix holds all of them and its model kind's DATA_KEYS, and nothing else
ALGORITHM_KEYS = frozenset((
    "n_agents", "n_rsus", "samples_per_agent", "batch", "lar",
    "local_epochs", "lr", "mu1", "mu2", "csr", "scd", "fsr", "partition",
    "data_seed", "engine", "eval_every"))
ENGINE = "flat"              # the engine the reference follows
# what a model kind's module gives (models/<kind>.py)
MODEL_NAMES = ("DATA_KEYS", "PARTITIONS", "make", "spec_fields",
               "program_loss", "loss", "evaluate", "n_params",
               "flops_per_sample")
# the numbers of the program against the reference (``numbers``); a cell's
# limits file gives each a limit or names it under readings.not_compared
NUMBERS = ("loss", "acc", "change3", "update1_median", "change3_median")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    model: ModuleType        # models/<config["model"]>.py
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_bench() -> Dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str, bench: Optional[Dict] = None) -> Cell:
    bench = bench or load_bench()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    conf = [c for c in bench["configs"] if c["name"] == w["config"]][0]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    config = json.loads((ROOT / conf["file"]).read_text())
    if "model" not in config:
        raise SystemExit(f"config {conf['name']!r} names no model kind "
                         f"(its \"model\" key)")
    model = load_model(config["model"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config, model=model,
        traffic=check_traffic(w["traffic"], json.loads(
            (CHIP / "traffic" / f"{w['traffic']}.json").read_text()), model),
        limits=check_limits(name, json.loads(
            (CHIP / "limits" / f"{name}.json").read_text())),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def _load_file(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_model(kind: str) -> ModuleType:
    """The module of model kind ``kind``, ``models/<kind>.py``; the run
    exits where there is none or it lacks one of ``MODEL_NAMES``."""
    path = CHIP / "models" / f"{kind}.py"
    if not path.is_file():
        raise SystemExit(f"model kind {kind!r}: no file models/{kind}.py")
    mod = _load_file(path, f"bench_chip_model_{kind}")
    missing = [n for n in MODEL_NAMES if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"model kind {kind!r}: models/{kind}.py lacks "
                         f"{missing}")
    return mod


def check_traffic(mix: str, traffic: Dict, model: ModuleType) -> Dict:
    """``traffic`` if this harness and the model kind drive all of it; else
    the run exits: a key neither would read, a missing key, an engine the
    reference does not follow, or a partition the kind does not build."""
    keys = ALGORITHM_KEYS | frozenset(model.DATA_KEYS)
    unknown = sorted(set(traffic) - keys)
    missing = sorted(keys - set(traffic))
    if unknown or missing:
        raise SystemExit(f"traffic {mix!r}: keys the harness does not drive "
                         f"{unknown}, missing {missing}")
    if (traffic["engine"] != ENGINE
            or traffic["partition"] not in model.PARTITIONS):
        raise SystemExit(f"traffic {mix!r}: the harness drives engine "
                         f"{ENGINE!r} on partitions {list(model.PARTITIONS)}"
                         f", not {traffic['engine']!r} on "
                         f"{traffic['partition']!r}")
    return traffic


def compared(limits: Dict) -> List[str]:
    """The numbers a limits file compares, in the order of ``NUMBERS``."""
    return [k for k in NUMBERS if k in limits]


def check_limits(cell: str, limits: Dict) -> Dict:
    """``limits`` if it gives at least one of ``NUMBERS`` a limit, a finite
    number of at least 0, and names each of the others under
    ``readings.not_compared``; else the run exits: a misspelt key, a number
    both compared and named as not compared, or one left unaccounted for
    would compare less than it seems to."""
    keys = set(limits) - {"readings"}
    skipped = set(limits.get("readings", {}).get("not_compared", {}))
    unknown = sorted((keys | skipped) - set(NUMBERS))
    unaccounted = sorted(set(NUMBERS) - keys - skipped)
    bad = sorted(k for k in keys if isinstance(limits[k], bool)
                 or not isinstance(limits[k], (int, float))
                 or not 0 <= limits[k] < math.inf)
    if not keys or unknown or unaccounted or bad or keys & skipped:
        raise SystemExit(
            f"limits of {cell!r}: compare at least one of {list(NUMBERS)} "
            f"and name each other under readings.not_compared; compared "
            f"{sorted(keys)}, not compared {sorted(skipped)}, unknown "
            f"{unknown}, unaccounted for {unaccounted}, no limit {bad}")
    return limits


def sim_seed(seed: int) -> int:
    """The 31-bit realization seed a run's ``--seed`` stands for."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0]) >> 1


def check_device(chips: int) -> Dict:
    """The accelerator this run uses.  Exits non-zero where JAX finds no
    TPU or fewer chips than the cell asks for, or where the kernels would
    take the XLA route instead of the compiled Pallas one."""
    if os.environ.get("REPRO_INTERPRET"):
        raise SystemExit("REPRO_INTERPRET is set: the benchmark measures "
                         "the compiled Pallas kernels")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX finds {devices[0].platform}")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devices)}")
    from repro.kernels import ops
    if ops.interpret_mode():
        raise SystemExit("kernels.ops would take the XLA route")
    return describe_device()


def use_compile_cache() -> None:
    """Keep every program in JAX's persistent cache, however quick its
    compile, so that a second run in the checkout compiles nothing (the
    program turns this on only at its first ``run_scenario`` call)."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def describe_device() -> Dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


class CompileCounter:
    """Backend compiles seen by JAX's monitoring events; a compile request
    that the persistent cache answers is not a compile."""

    def __init__(self):
        self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def compiles(self) -> int:
        return self.requests - self.hits

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


class Eval:
    """The ``eval_fn`` handed to ``run_scenario``: test accuracy of the
    cloud model by the model kind's ``evaluate``, a host-clock stamp once
    it is on the host, a host copy of the first ``capture`` cloud models,
    and, where asked, the profiler started after round ``trace[0]`` and
    stopped after round ``trace[1]``, with the host span ``TRACED_SPAN``
    over the traced rounds."""

    def __init__(self, evaluate: Callable, x_test, y_test):
        self.evaluate, self.x, self.y = evaluate, x_test, y_test
        self.reset(0)

    def reset(self, capture: int, trace=None):
        self.capture, self.captured, self.stamps = capture, [], []
        self.trace, self.span = trace, None

    def __call__(self, params):
        with jax.profiler.TraceAnnotation(EVAL_SPAN):
            _, acc = self.evaluate(params, self.x, self.y)
            acc = float(acc)
            self.stamps.append(time.perf_counter())
            if len(self.captured) < self.capture:
                self.captured.append(jax.device_get(params))
        if self.trace:
            first, last, directory = self.trace
            if len(self.stamps) == first:
                jax.profiler.start_trace(directory)
                self.span = jax.profiler.TraceAnnotation(TRACED_SPAN)
                self.span.__enter__()
            elif len(self.stamps) == last:
                self.span.__exit__(None, None, None)
                jax.profiler.stop_trace()
        return acc


def scenario(cell: Cell, data: datagen.CellData, seed: int):
    """The ``ResolvedScenario`` of the cell: its spec, with the model
    kind's fields, and the benchmark's data in the program's
    ``FederatedData``."""
    from repro.core.h2fed import H2FedParams
    from repro.core.heterogeneity import HeterogeneityModel
    from repro.core.scenario import ResolvedScenario, ScenarioSpec
    from repro.data.partition import FederatedData
    t, c = cell.traffic, cell.config
    spec = ScenarioSpec(
        n_agents=t["n_agents"], n_rsus=t["n_rsus"], batch=t["batch"],
        partition=t["partition"],
        hp=H2FedParams(mu1=t["mu1"], mu2=t["mu2"], lar=t["lar"],
                       local_epochs=t["local_epochs"], lr=t["lr"]),
        het=HeterogeneityModel(csr=t["csr"], scd=t["scd"], fsr=t["fsr"],
                               lar=t["lar"]),
        engine=t["engine"], fleet_dtype=c["fleet_dtype"],
        eval_every=t["eval_every"], rounds=MIN_ROUNDS,
        seed=int(t["data_seed"]), sim_seed=sim_seed(seed),
        **cell.model.spec_fields(c, t)).validate()
    fed = FederatedData(x=data.x, y=data.y, n_per_agent=data.n_per_agent,
                        rsu_assign=data.rsu_assign)
    return ResolvedScenario(spec=spec, train=None, test=None,
                            pretrain_pool=None, fed_pool=None, fed=fed)


def program_seed(res) -> int:
    """The integer the program keys its draws with: a scenario's data seed
    times 1000 plus its realization seed."""
    return res.spec.seed * 1000 + res.spec.sim_seed


def timed_call(cell: Cell, res, params, ev: Eval, rounds: int):
    """One ``run_scenario`` call of ``rounds`` rounds on the model kind's
    ``program_loss``, under the configuration's matmul precision; returns
    (start, end, history)."""
    from repro.fedsim import sweep
    res = dataclasses.replace(res, spec=res.spec.replace(rounds=rounds))
    loss_fn = cell.model.program_loss(cell.config)
    with jax.default_matmul_precision(cell.config["matmul_precision"]):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            final, hist = sweep.run_scenario(res, params, loss_fn=loss_fn,
                                             eval_fn=ev)
            jax.block_until_ready(final)
        t1 = time.perf_counter()
    del final
    return t0, t1, hist


def _leaf_norms(tree: Dict, base: Dict) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(tree[k], np.float64)
                                    - np.asarray(base[k], np.float64)))
            for k in base}


def _kept(dr: Dict[str, float]):
    """The median leaf's norm, and the leaves the reference moves by at
    least a thousandth of it."""
    med = statistics.median(dr.values())
    return med, [k for k in dr if dr[k] >= 1e-3 * med]


def leaf_gap(prog: Dict, ref: Dict, base: Dict) -> float:
    """Worst leaf's gap between the program's and the reference's norm of
    the change from ``base``, over the larger of the reference's norm of
    that leaf and of the median leaf.  Leaves the reference moves by under
    a thousandth of the median leaf are left out."""
    dp, dr = _leaf_norms(prog, base), _leaf_norms(ref, base)
    med, kept = _kept(dr)
    return max(abs(dp[k] - dr[k]) / max(dr[k], med) for k in kept)


def median_gap(prog: Dict, ref: Dict, base: Dict) -> float:
    """Worst leaf's median element of the gap between the program's and
    the reference's change from ``base``, over the median element of the
    reference's change of that leaf, both taken over the elements that the
    reference moves; leaves as in ``leaf_gap``.  An element the reference
    leaves exactly where it was, as a vocabulary row no batch touched or an
    expert nothing was routed to, stays out, so a leaf that is mostly such
    elements still has a median to divide by.  A gap confined to a few
    elements, as one sample's term that one side's ReLU gate lets through
    and the other's does not, leaves it unmoved; a lower precision or a
    wrong step moves every element."""
    _, kept = _kept(_leaf_norms(ref, base))
    out = 0.0
    for k in kept:
        b = np.asarray(base[k], np.float64)
        dr = np.asarray(ref[k], np.float64) - b
        moved = dr != 0
        dp = np.asarray(prog[k], np.float64)[moved] - b[moved]
        dr = dr[moved]
        out = max(out, float(np.median(np.abs(dp - dr))
                             / np.median(np.abs(dr))))
    return out


def numbers(cell: Cell, prog: List[Dict], ref: List[Dict], base: Dict,
            x_test, y_test) -> Dict[str, float]:
    """The numbers of the program against the reference, ``NUMBERS``: per
    round, the test loss (relative gap) and the test accuracy (absolute
    gap) of the cloud model, by the model kind's ``evaluate``; the change
    over all compared rounds by the worst leaf's norm; the round-1 update
    and that change by the median element.  Each is compared, or named as
    not compared in the cell's limits file (``check_limits``)."""
    evaluate = cell.model.evaluate
    ev_p = [tuple(map(float, evaluate(p, x_test, y_test))) for p in prog]
    ev_r = [tuple(map(float, evaluate(r, x_test, y_test))) for r in ref]
    return {
        "loss": max(abs(p[0] - r[0]) / r[0] for p, r in zip(ev_p, ev_r)),
        "acc": max(abs(p[1] - r[1]) for p, r in zip(ev_p, ev_r)),
        "change3": leaf_gap(prog[-1], ref[-1], base),
        "update1_median": median_gap(prog[0], ref[0], base),
        "change3_median": median_gap(prog[-1], ref[-1], base),
    }


def reference_rounds(cell: Cell, data: datagen.CellData, res,
                     mode: str = "fp32") -> List[Dict]:
    return reference.simulate(
        data.params, data.x, data.y, data.n_per_agent, data.rsu_assign,
        cell.traffic, program_seed(res), COMPARED, loss=cell.model.loss,
        mode=mode)


def load_metric(name: str) -> Callable:
    return _load_file(CHIP / "metrics" / f"{name}.py",
                      f"bench_chip_metric_{name.replace('.', '_')}").read


def read_metrics(entries: List[Dict], ctx) -> Dict:
    out = {}
    for m in entries:
        v = load_metric(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def prepare(cell: Cell, seed: int):
    """The cell's data, starting model and scenario for ``seed``, from the
    model kind's ``make``; a fleet of another shape than the mix states
    ends the run."""
    t = cell.traffic
    data = cell.model.make(t, cell.config, sim_seed(seed))
    jax.block_until_ready((data.x, data.params))
    fleet = tuple(data.x.shape[:2])
    if fleet != (t["n_agents"], t["samples_per_agent"]):
        raise SystemExit(f"{cell.name}: the data holds {fleet} agents x "
                         f"samples, the mix states "
                         f"{(t['n_agents'], t['samples_per_agent'])}")
    return data, scenario(cell, data, seed)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        process_start: float) -> Dict:
    """One run of ``cell``; returns the result object."""
    device = check_device(cell.chips)
    compiles = CompileCounter()
    try:
        return _run(cell, seed, seconds, trace, process_start, device,
                    compiles)
    finally:
        compiles.close()


def _run(cell, seed, seconds, trace, process_start, device, compiles):
    t0 = time.perf_counter()
    data, res = prepare(cell, seed)
    data_setup_s = time.perf_counter() - t0
    log(f"data: {cell.traffic['n_agents']} agents x "
        f"{data.x.shape[1]} samples, pretrained acc {data.pre_acc:.4f} "
        f"after {data.pre_epochs} epochs, {data_setup_s:.3f} s")

    t1 = time.perf_counter()
    ev = Eval(cell.model.evaluate, data.x_test, data.y_test)
    round_s = overhead = None
    # the first call loads (or compiles) the round; the window is sized
    # from the later half of the next, since the rounds just after a
    # program's first load can run at twice the steady time
    n = WARM_ROUNDS
    for call in range(3):
        c0 = compiles.compiles
        a, _, _ = timed_call(cell, res, data.params, ev, n)
        d = np.diff([a] + ev.stamps)
        round_s = float(np.median(d[len(d) // 2:]))
        overhead = float(d[0] - round_s)
        ev.reset(0)
        if call and compiles.compiles == c0:
            break
        n = max(WARM_ROUNDS, int(WARM_SECONDS / round_s))
    warmup_s = time.perf_counter() - t1
    rounds = max(MIN_ROUNDS, int((seconds - overhead) / round_s))
    log(f"warm-up: {warmup_s:.3f} s, {compiles.compiles} backend compiles; "
        f"round {round_s * 1e3:.3f} ms, call overhead {overhead:.3f} s; "
        f"window of {rounds} rounds")

    traced = None
    trace_dir = TRACE_DIR / cell.name
    if trace:
        n = max(3, min(int(TRACE_SECONDS / round_s), rounds - TRACE_AFTER))
        traced = (TRACE_AFTER, TRACE_AFTER + n)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ev.reset(COMPARED, traced and (*traced, str(trace_dir)))
    c0 = compiles.compiles
    setup_s = time.perf_counter() - process_start
    start, end, hist = timed_call(cell, res, data.params, ev, rounds)
    window_compiles = compiles.compiles - c0
    log(f"window: {rounds} rounds in {end - start:.6f} s, "
        f"{window_compiles} backend compiles in the window")
    if window_compiles:
        raise SystemExit(f"{window_compiles} backend compiles in the "
                         f"measured window: it has to compile nothing")
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    gc.collect()

    accs = [float(a) for a in hist["acc"]]
    stamps = [s - start for s in ev.stamps]
    per_round = list(np.diff([0.0] + stamps))
    failed = sum(1 for a in accs if not math.isfinite(a))
    slow = np.argsort(per_round[1:])[::-1][:5] + 1
    med = float(np.median(per_round[1:]))
    log(f"window: first round {per_round[0]:.6f} s (with the call's "
        f"set-up), median {med * 1e3:.4f} ms, slowest "
        + ", ".join(f"#{i} {per_round[i] * 1e3:.3f} ms" for i in slow)
        + f"; {sum(d - med for d in per_round[1:] if d > 2 * med):.6f} s "
        f"over the median in rounds above twice it")

    t2 = time.perf_counter()
    ref = reference_rounds(cell, data, res)
    got = numbers(cell, ev.captured, ref, jax.device_get(data.params),
                  data.x_test, data.y_test)
    log(f"reference: {COMPARED} rounds in {time.perf_counter() - t2:.3f} s")
    checks = {k: {"value": got[k], "limit": cell.limits[k]}
              for k in compared(cell.limits)}
    correct = failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())

    ctx = SimpleNamespace(
        cell=cell, seed=seed, device=device, trace=None, draws=None,
        setup={"setup_s": setup_s, "data_setup_s": data_setup_s,
               "warmup_s": warmup_s},
        window={"seconds": end - start, "rounds": rounds,
                "per_round_s": per_round, "stamps_s": stamps, "acc": accs,
                "compiles": window_compiles, "round_s": round_s})
    result = {"correct": correct, "attempted": rounds, "failed": failed}
    if trace:
        summary = trace_reduce.reduce_file(trace_reduce.find_xplane(
            str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        t = cell.traffic
        draws = reference.realized(
            program_seed(res), t, t["n_agents"], traced[1],
            max(int(data.x.shape[1]) // t["batch"], 1), data.rsu_assign,
            t["n_rsus"])
        ctx.trace = summary
        ctx.traced_rounds = traced[1] - traced[0]
        ctx.draws = {k: v[traced[0]:traced[1]] for k, v in draws.items()}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        log(f"trace: rounds {traced[0] + 1}-{traced[1]}, "
            f"{summary['window_s']:.6f} s, busy {summary['busy_s']:.6f} s")
        result["metrics"] = read_metrics(cell.per_layer, ctx)
        result["breakdown"] = trace_reduce.breakdown(summary)
    else:
        result["metrics"] = read_metrics(cell.end_to_end, ctx)
    result["device"] = device
    result["checks"] = checks
    return result
