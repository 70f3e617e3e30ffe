"""Trace a few seconds of one cell's rounds and split them by what the
program was doing (``scopes``); beside it, what the traced call's
re-trace, lowering and cache load cost by the program's compile counters,
and what one host span costs.

    python -m benchmarks.chip.scope_probe --workload <cell> --seed <n> \\
        [--seconds 2] [--keep <file> --keep-rounds 6]

Set-up and warm-up are the harness's.  Then one ``run_scenario`` call
whose rounds after the first are traced for about ``--seconds``; with
``--keep``, a second call traces ``--keep-rounds`` rounds and keeps that
trace at ``<file>``.  The last line of standard output is one JSON object:
per traced round, device time by scope and idle time by host span (ms),
and the call's compile counters beside its overhead (s).  A program
without the scopes or counters reads as all ``(unscoped)`` and ``None``.
Without a TPU it exits non-zero, as ``run`` does.
"""
import time

PROCESS_START = time.perf_counter()

import argparse                                   # noqa: E402
import json                                       # noqa: E402
import os                                         # noqa: E402
import shutil                                     # noqa: E402
import statistics                                 # noqa: E402
import sys                                        # noqa: E402
from pathlib import Path                          # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
COUNTERS = ("trace", "lower", "compile", "cache_load")
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def _counters():
    from repro.core import program_cache
    s = program_cache.stats()
    return {k: s.get(k) for n in COUNTERS for k in (f"{n}_s", f"{n}_events")}


def _delta(before, after):
    return {k: None if before[k] is None else after[k] - before[k]
            for k in before}


def traced_call(harness, cell, res, data, ev, rounds, trace_dir):
    """One call of ``rounds`` + 2 rounds, rounds 2 to ``rounds`` + 1
    traced; returns its per-round seconds, its compile counters and the
    wall time of its traces counted once (nested traces overlap)."""
    import jax
    import numpy as np
    ends = []

    def on_trace(event, duration, **_):
        if event == TRACE_EVENT:
            now = time.perf_counter()
            ends.append((now - duration, now))

    first = harness.TRACE_AFTER
    shutil.rmtree(trace_dir, ignore_errors=True)
    ev.reset(0, (first, first + rounds, str(trace_dir)))
    before = _counters()
    jax.monitoring.register_event_duration_secs_listener(on_trace)
    try:
        start, _, _ = harness.timed_call(cell, res, data.params, ev,
                                         rounds + 2)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_trace)
    from benchmarks.chip import trace_reduce
    per_round = list(np.diff([start] + ev.stamps))
    traced = per_round[first:first + rounds]
    return {
        "per_round_s": per_round,
        "traced_median_ms": statistics.median(traced) * 1e3,
        "call_overhead_s": per_round[0] - statistics.median(per_round[1:]),
        "counters": _delta(before, _counters()),
        "trace_wall_s": sum(e - s for s, e in trace_reduce.union(ends)),
        "trace_events": len(ends),
    }


def per_round_ms(split, rounds):
    ms = {k: v / rounds * 1e3 for k, v in split.items()}
    return dict(sorted(ms.items(), key=lambda kv: -kv[1]))


def span_cost_ns(trace_dir, n=200_000):
    """Nanoseconds of one ``TraceAnnotation`` with a round argument, with
    the profiler off and on, and of the empty loop around it."""
    import jax

    def loop(k, annotate=True):
        t = time.perf_counter()
        for r in range(k):
            if annotate:
                with jax.profiler.TraceAnnotation("h2fed.round", round=r):
                    pass
        return (time.perf_counter() - t) / k * 1e9

    out = {"empty_loop": loop(n, annotate=False), "inactive": loop(n)}
    jax.profiler.start_trace(str(trace_dir))
    out["active"] = loop(n // 10)
    jax.profiler.stop_trace()
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--keep", default="")
    ap.add_argument("--keep-rounds", type=int, default=6)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.makedirs(ROOT / ".jax_cache", exist_ok=True)
    sys.path.insert(0, str(ROOT))

    import numpy as np
    from benchmarks.chip import harness, scopes, trace_reduce
    harness.use_compile_cache()
    cell = harness.load_cell(args.workload)
    device = harness.check_device(cell.chips)
    data, res = harness.prepare(cell, args.seed)
    ev = harness.Eval(cell.model.evaluate, data.x_test, data.y_test)
    n = harness.WARM_ROUNDS
    for _ in range(2):
        a, _, _ = harness.timed_call(cell, res, data.params, ev, n)
        d = np.diff([a] + ev.stamps)
        round_s = float(np.median(d[len(d) // 2:]))
        ev.reset(0)
        n = max(harness.WARM_ROUNDS, int(harness.WARM_SECONDS / round_s))
    setup_s = time.perf_counter() - PROCESS_START
    trace_dir = harness.TRACE_DIR / "scope_probe"

    rounds = max(3, int(args.seconds / round_s))
    call = traced_call(harness, cell, res, data, ev, rounds, trace_dir)
    path = trace_reduce.find_xplane(str(trace_dir))
    summary = trace_reduce.reduce_file(path)
    split = scopes.reduce_file(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    out = {
        "device": device, "seed": args.seed, "setup_s": setup_s,
        "traced_rounds": rounds, "window_s": split["window_s"],
        "busy_s": split["busy_s"], "idle_s": split["idle_s"],
        "summary_busy_s": summary["busy_s"],
        "device_ms": per_round_ms(split["device_s"], rounds),
        "relayout_ms": per_round_ms(split["relayout_s"], rounds),
        "idle_ms": per_round_ms(split["idle_by_span_s"], rounds),
        "spans": split["spans"],
        "agg_kernel_ms": sum(v for k, v in summary["ops"].items()
                             if k.startswith("_fused_agg_blend")
                             ) / rounds * 1e3,
        "call": {k: v for k, v in call.items() if k != "per_round_s"},
    }
    if args.keep:
        kept = traced_call(harness, cell, res, data, ev, args.keep_rounds,
                           trace_dir)
        path = trace_reduce.find_xplane(str(trace_dir))
        Path(args.keep).parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(path, args.keep)
        shutil.rmtree(trace_dir, ignore_errors=True)
        out["kept"] = {"rounds": args.keep_rounds, "bytes":
                       os.path.getsize(args.keep),
                       "traced_median_ms": kept["traced_median_ms"]}
    out["span_ns"] = span_cost_ns(trace_dir)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
