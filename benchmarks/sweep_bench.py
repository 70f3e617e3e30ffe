"""Sweep-vs-sequential benchmark (DESIGN.md §7) — the PR-5 speed story,
extended with the PR-8 compile-time story (DESIGN.md §10).

Runs the SAME 4-point CSR grid two ways:

  sequential — one ``run_scenario`` per cell, the old experiment-layer
               shape: S jit traces, S compiles, S× dispatch;
  sweep      — ``fedsim.sweep``: the grid stacked on a leading sweep axis
               and vmapped, ONE jit trace for all cells.

Records total wall (compile included — the number a figure grid actually
pays), steady-state per-round latency (compile excluded), and the jit
trace count into the BENCH json flow (the ``--summary`` record asserts
the sweep is ≥1.3× faster wall-clock in CI).

Two PR-8 cells ride in the same record:

  mixed_cadence — a lar × local_epochs × cloud_every async grid that the
                  widened static_key keeps in ONE group: walls, actual
                  trace count (``core.program_cache`` counters; CI pins 1)
                  and equivalence vs sequential;
  cold_warm     — the same small grid run in two fresh CPU child
                  processes sharing one persistent compilation cache at a
                  fixed, wiped path (``COLD_WARM_DIR``): the first pays
                  XLA compilation and populates it, the second loads
                  from disk — ``cold_vs_warm_wall`` is the ratio CI
                  asserts ≥ 2×.

Standalone:
  PYTHONPATH=src python -m benchmarks.sweep_bench [--rounds 3] [--agents 16]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import textwrap
import time
from pathlib import Path
from typing import List

CSRS = (1.0, 0.5, 0.2, 0.1)
CADENCES = ((2, 1, 0), (3, 2, 2), (1, 2, 3))   # (lar, local_epochs, ce)
# the cold/warm cell's own compilation cache, inside the git-ignored
# default cache directory and wiped before the cold run
COLD_WARM_DIR = (Path(__file__).resolve().parents[1] / ".jax_cache"
                 / "cold_warm")


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--agents", type=int, default=16)
    ap.add_argument("--rsus", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--lar", type=int, default=2)
    ap.add_argument("--n-train", type=int, default=2000)
    ap.add_argument("--out", default=os.environ.get("REPRO_RESULTS",
                                                    "results") + "/bench")
    return ap.parse_args()


def _grid(args) -> List:
    from repro.core.h2fed import H2FedParams
    from repro.core.scenario import ScenarioSpec
    base = ScenarioSpec(
        n_agents=args.agents, n_rsus=args.rsus, batch=16,
        n_train=args.n_train, n_test=200,
        hp=H2FedParams(mu1=0.01, mu2=0.005, lar=args.lar, local_epochs=1,
                       lr=0.1),
        rounds=args.rounds)
    return [base.replace(het=dataclasses.replace(base.het, csr=c))
            for c in CSRS]


def _mixed_grid(args) -> List:
    """lar × local_epochs × cloud_every all varying in ONE async group —
    pre-PR-8 this grid was 3 groups (3 traces, 3 compiles)."""
    from repro.core.h2fed import H2FedParams
    from repro.core.heterogeneity import HeterogeneityModel
    from repro.core.scenario import ScenarioSpec
    base = ScenarioSpec(
        n_agents=args.agents, n_rsus=args.rsus, batch=16,
        n_train=args.n_train, n_test=200, engine="async",
        het=HeterogeneityModel(csr=0.8, scd=1, max_delay=2, delay_p=0.4),
        staleness_decay=0.6, buffer_keep=0.25,
        hp=H2FedParams(mu1=0.01, mu2=0.005, lar=2, local_epochs=1, lr=0.1),
        rounds=args.rounds)
    return [base.replace(
        hp=dataclasses.replace(base.hp, lar=l, local_epochs=e),
        cloud_every=ce) for (l, e, ce) in CADENCES]


def run_cell(args) -> dict:
    import jax
    import numpy as np

    from repro.configs.mnist_mlp import CONFIG as MLP_CFG
    from repro.core import program_cache
    from repro.fedsim import sweep
    from repro.models import mlp

    specs = _grid(args)
    params = mlp.init_params(MLP_CFG, jax.random.key(0))
    resolved = [s.resolve() for s in specs]          # shared data, uncounted
    program_cache.clear()                            # honest trace counts

    # -- total wall: what a figure grid pays, compile included ------------
    t0 = time.perf_counter()
    seq_hists = [sweep.run_scenario(r, params)[1] for r in resolved]
    wall_seq = time.perf_counter() - t0

    t0 = time.perf_counter()
    sweep_hists = sweep.run_sweep(resolved, params)
    wall_sweep = time.perf_counter() - t0
    sweep_traces = program_cache.trace_count("sweep_round")

    for a, b in zip(seq_hists, sweep_hists):         # same math, fp32 tol
        np.testing.assert_allclose(a["acc"], b["acc"], atol=5e-5)

    # -- steady-state per-round latency (compile excluded) ----------------
    from repro.core import flatten
    from repro.fedsim.simulator import (init_flat_state,
                                        make_flat_global_round)
    fspec = flatten.spec_of(params)
    seq_rounds = []
    for r in resolved:
        fn = make_flat_global_round(r.cfg, r.hp, r.het, r.fed, fspec)
        st = init_flat_state(r.cfg, fspec, params,
                             jax.random.key(r.cfg.seed))
        st = fn(fn(st))                              # compile x2 + warmup
        jax.block_until_ready(st)
        t0 = time.perf_counter()
        for _ in range(args.rounds):
            st = fn(st)
        jax.block_until_ready(st)
        seq_rounds.append((time.perf_counter() - t0) / args.rounds)
    round_seq = float(np.sum(seq_rounds))            # all S cells, 1 round

    prog = sweep.build_sweep(resolved, params)
    st = prog.round_fn(prog.round_fn(prog.state, prog.data, prog.dyn),
                       prog.data, prog.dyn)
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    for _ in range(args.rounds):
        st = prog.round_fn(st, prog.data, prog.dyn)
    jax.block_until_ready(st)
    round_sweep = (time.perf_counter() - t0) / args.rounds

    return {
        "bench": "sweep_round",
        "n_scenarios": len(specs),
        "csrs": list(CSRS),
        "n_agents": args.agents,
        "n_rsus": args.rsus,
        "lar": args.lar,
        "n_rounds": args.rounds,
        "wall_s": {"sequential": wall_seq, "sweep": wall_sweep},
        "round_s": {"sequential": round_seq, "sweep": round_sweep},
        "sweep_vs_sequential_wall": wall_seq / max(wall_sweep, 1e-12),
        "sweep_vs_sequential_round": round_seq / max(round_sweep, 1e-12),
        "sweep_trace_count": sweep_traces,
    }


def run_mixed(args) -> dict:
    """The mixed-cadence cell: one group, one trace, sequential-equal."""
    import jax
    import numpy as np

    from repro.configs.mnist_mlp import CONFIG as MLP_CFG
    from repro.core import program_cache
    from repro.fedsim import sweep
    from repro.models import mlp

    specs = _mixed_grid(args)
    params = mlp.init_params(MLP_CFG, jax.random.key(0))
    resolved = [s.resolve() for s in specs]

    t0 = time.perf_counter()
    seq = [sweep.run_scenario(r, params)[1] for r in resolved]
    wall_seq = time.perf_counter() - t0

    program_cache.clear()
    t0 = time.perf_counter()
    hists = sweep.run_scenarios(specs, params)
    wall_sweep = time.perf_counter() - t0
    traces = program_cache.trace_count("sweep_round")

    diff = max(float(np.max(np.abs(a["acc"] - b["acc"])))
               for a, b in zip(seq, hists))
    assert diff <= 5e-5, f"mixed-cadence sweep diverged: {diff}"
    return {
        "cadences": [list(c) for c in CADENCES],
        "wall_s": {"sequential": wall_seq, "sweep": wall_sweep},
        "mixed_cadence_vs_sequential_wall":
            wall_seq / max(wall_sweep, 1e-12),
        "trace_count": traces,
        "max_abs_acc_diff": diff,
    }


_COLD_WARM_CHILD = textwrap.dedent("""
    import dataclasses, json, sys, time
    import jax
    from repro.configs.mnist_mlp import CONFIG as MLP_CFG
    from repro.core.h2fed import H2FedParams
    from repro.core.heterogeneity import HeterogeneityModel
    from repro.core.scenario import ScenarioSpec
    from repro.fedsim import sweep
    from repro.models import mlp

    # the async mixed-cadence grid: the compile-heaviest one-trace program
    # (tick scan + staleness buffers), so the measured wall is dominated by
    # exactly the compilation the persistent cache elides
    agents, rounds = int(sys.argv[1]), int(sys.argv[2])
    base = ScenarioSpec(
        n_agents=agents, n_rsus=4, batch=16, n_train=400, n_test=100,
        engine="async",
        het=HeterogeneityModel(csr=0.8, scd=1, max_delay=2, delay_p=0.4),
        staleness_decay=0.6, buffer_keep=0.25,
        hp=H2FedParams(mu1=0.01, mu2=0.005, lar=2, local_epochs=1, lr=0.1),
        rounds=rounds)
    specs = [base.replace(
        hp=dataclasses.replace(base.hp, lar=l, local_epochs=e),
        cloud_every=ce) for (l, e, ce) in ((2, 1, 0), (3, 2, 2), (1, 2, 3))]
    params = mlp.init_params(MLP_CFG, jax.random.key(0))
    [s.resolve() for s in specs]              # data generation, uncounted
    t0 = time.perf_counter()
    hists = sweep.run_scenarios(specs, params)
    print(json.dumps({"wall": time.perf_counter() - t0,
                      "acc": float(hists[0]["acc"][-1])}))
""")


def run_cold_warm(args) -> dict:
    """Persistent-compilation-cache story: the same sweep in two fresh CPU
    child processes sharing one ``JAX_COMPILATION_CACHE_DIR``.  The first
    (cold) pays XLA compilation and writes the disk cache; the second
    (warm) re-traces but loads the compiled executables.  The directory is
    wiped first so the cold run is genuinely cold even under CI's restored
    cache volume."""
    from benchmarks.common import run_cpu_child
    shutil.rmtree(COLD_WARM_DIR, ignore_errors=True)
    env = {"JAX_COMPILATION_CACHE_DIR": str(COLD_WARM_DIR)}
    walls, accs = [], []
    for _ in ("cold", "warm"):
        out = run_cpu_child(     # 1 round: the wall IS compile time
            ["-c", _COLD_WARM_CHILD, str(args.agents), "1"], env=env)
        rec = json.loads(out.strip().splitlines()[-1])
        walls.append(rec["wall"])
        accs.append(rec["acc"])
    entries = sum(1 for _ in COLD_WARM_DIR.iterdir())
    assert accs[0] == accs[1], "cached program changed the math"
    return {
        "cold_s": walls[0],
        "warm_s": walls[1],
        "cold_vs_warm_wall": walls[0] / max(walls[1], 1e-12),
        "cache_entries": entries,
    }


def _csv_rows(rec: dict) -> List[str]:
    from benchmarks.common import csv_row
    s = rec["n_scenarios"]
    rows = [
        csv_row("sweep_round/sequential_wall", rec["wall_s"]["sequential"]
                * 1e6, f"S{s} csr grid, {rec['n_rounds']} rounds"),
        csv_row("sweep_round/sweep_wall", rec["wall_s"]["sweep"] * 1e6,
                f"speedup={rec['sweep_vs_sequential_wall']:.2f}x"),
        csv_row("sweep_round/sequential_round", rec["round_s"]["sequential"]
                * 1e6, "steady-state, all cells"),
        csv_row("sweep_round/sweep_round", rec["round_s"]["sweep"] * 1e6,
                f"speedup={rec['sweep_vs_sequential_round']:.2f}x"),
    ]
    mc, cw = rec.get("mixed_cadence"), rec.get("cold_warm")
    if mc:
        rows += [
            csv_row("sweep_round/mixed_cadence_wall",
                    mc["wall_s"]["sweep"] * 1e6,
                    f"traces={mc['trace_count']} "
                    f"speedup={mc['mixed_cadence_vs_sequential_wall']:.2f}x"),
        ]
    if cw:
        rows += [
            csv_row("sweep_round/cold_wall", cw["cold_s"] * 1e6,
                    "fresh process, empty compilation cache"),
            csv_row("sweep_round/warm_wall", cw["warm_s"] * 1e6,
                    f"cold/warm={cw['cold_vs_warm_wall']:.2f}x"),
        ]
    return rows


def _record(args) -> dict:
    # the child-process cell first: off the CPU it refuses before any work
    cold_warm = run_cold_warm(args)
    rec = run_cell(args)
    rec["mixed_cadence"] = run_mixed(args)
    rec["cold_warm"] = cold_warm
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / "sweep_round.json"
    path.write_text(json.dumps(rec, indent=1))
    print(f"[json] {path}", file=sys.stderr)
    return rec


def run() -> List[str]:
    """Harness entry (benchmarks.run --only sweep): defaults only — the
    harness owns argv."""
    args = argparse.Namespace(
        agents=16, rsus=4, rounds=3, lar=2, n_train=2000,
        out=os.environ.get("REPRO_RESULTS", "results") + "/bench")
    return _csv_rows(_record(args))


def main():
    for row in _csv_rows(_record(_parse_args())):
        print(row)


if __name__ == "__main__":
    main()
