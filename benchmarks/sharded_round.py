"""Round-latency benchmark for the simulation engines (DESIGN.md §3–4).

Measures one compiled global round of the SAME federated workload under:

  tree     — per-leaf jax.tree.map aggregation (the reference engine)
  flat     — flat-buffer engine: Pallas aggregation matmuls on (A, N)
  sharded  — flat engine with the agent axis shard_map'd over the mesh

and records tree-vs-flat and 1-vs-N-host-device latency into the BENCH json
flow (one record per device count under results/bench/).  Because the device
count must be fixed before jax initializes, the multi-device cells run as
subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=N — the
same mechanism launch/dryrun.py uses.

Standalone:
  PYTHONPATH=src python -m benchmarks.sharded_round --devices 8 \
      [--agents 16 --rsus 4 --rounds 2 --out results/bench]

Via the harness (the 1- and 8-device cells as CPU child processes; on an
accelerator the harness refuses them, since its process holds the chip):
  PYTHONPATH=src python -m benchmarks.run --only sharded
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import List

DEFAULT_DEVICES = (1, 8)


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (0 = use what's there)")
    ap.add_argument("--agents", type=int, default=40)
    ap.add_argument("--rsus", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=2, help="timed rounds")
    ap.add_argument("--lar", type=int, default=2)
    ap.add_argument("--n-train", type=int, default=2000)
    ap.add_argument("--out", default=os.environ.get("REPRO_RESULTS",
                                                    "results") + "/bench")
    return ap.parse_args()


def _time_rounds(round_fn, state, n: int) -> float:
    """Mean per-round wall seconds, compile excluded.  Two warmup rounds:
    the first output's device layout differs from the host-built initial
    state, so round 2 triggers a second compile for the steady-state
    signature."""
    import jax
    state = round_fn(round_fn(state))            # compile x2 + warmup
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(n):
        state = round_fn(state)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / n


def run_cell(args) -> dict:
    """Benchmark all three engines at the current device count."""
    import jax

    from repro.configs.mnist_mlp import CONFIG as MLP_CFG
    from repro.core import flatten
    from repro.core.baselines import h2fed
    from repro.core.heterogeneity import HeterogeneityModel
    from repro.data.partition import scenario_two
    from repro.data.synthetic import mnist_class_task
    from repro.fedsim import sharded
    from repro.fedsim.simulator import (SimConfig, init_flat_state,
                                        init_state, make_flat_global_round,
                                        make_global_round)
    from repro.models import mlp

    n_dev = len(jax.devices())
    train, _ = mnist_class_task(n_train=args.n_train, n_test=100, seed=0)
    fed = scenario_two(train, n_agents=args.agents, n_rsus=args.rsus, seed=0)
    cfg = SimConfig(n_agents=args.agents, n_rsus=args.rsus, batch=16, seed=0)
    hp = h2fed(mu1=0.01, mu2=0.005, lar=args.lar, lr=0.1)
    het = HeterogeneityModel(csr=0.8, lar=hp.lar)
    params = mlp.init_params(MLP_CFG, jax.random.key(0))
    spec = flatten.spec_of(params)

    # fresh key per engine: the flat/sharded round jits donate their input
    # state, so a shared key buffer would be consumed by the first engine
    def key():
        return jax.random.key(cfg.seed)

    timings = {}
    # tree reference
    tree_round = make_global_round(cfg, hp, het, fed, engine="tree")
    timings["tree"] = _time_rounds(tree_round,
                                   init_state(cfg, params, key()),
                                   args.rounds)
    # flat Pallas engine
    flat_round = make_flat_global_round(cfg, hp, het, fed, spec)
    timings["flat"] = _time_rounds(
        flat_round, init_flat_state(cfg, spec, params, key()), args.rounds)
    # sharded flat engine over the fleet mesh
    mesh = sharded.make_fleet_mesh()
    sh_round = sharded.make_sharded_global_round(cfg, hp, het, fed, spec,
                                                 mesh)
    with mesh:
        timings["sharded"] = _time_rounds(
            sh_round, init_flat_state(cfg, spec, params, key()),
            args.rounds)

    # compute-vs-collective split: the compute leg is the sharded round's
    # PER-DEVICE workload (A/n_dev agents, same R and N) run through the
    # single-device flat engine — no collectives, same training scan and
    # (R, N) blend.  What the sharded round spends beyond that is its
    # collective + shard_map overhead.  Single-device engines are all
    # compute by construction.
    import dataclasses
    time_split = {e: {"compute_s": timings[e], "collective_s": 0.0}
                  for e in ("tree", "flat")}
    compute_s = timings["sharded"]
    if n_dev > 1:
        a_loc = max(args.agents // n_dev, 1)
        cfg_loc = dataclasses.replace(cfg, n_agents=a_loc)
        fed_loc = dataclasses.replace(
            fed, x=fed.x[:a_loc], y=fed.y[:a_loc],
            n_per_agent=fed.n_per_agent[:a_loc],
            rsu_assign=fed.rsu_assign[:a_loc])
        loc_round = make_flat_global_round(cfg_loc, hp, het, fed_loc, spec)
        compute_s = _time_rounds(
            loc_round, init_flat_state(cfg_loc, spec, params, key()),
            args.rounds)
    coll_s = max(timings["sharded"] - compute_s, 0.0)
    time_split["sharded"] = {
        "compute_s": compute_s, "collective_s": coll_s,
        "collective_frac": coll_s / max(timings["sharded"], 1e-12)}

    return {
        "bench": "sharded_round",
        "n_devices": n_dev,
        "mesh": dict(mesh.shape),
        "n_agents": args.agents,
        "n_rsus": args.rsus,
        "lar": args.lar,
        "n_params": spec.n,
        "round_s": timings,
        "time_split": time_split,
        "flat_vs_tree": timings["tree"] / max(timings["flat"], 1e-12),
        "sharded_vs_flat": timings["flat"] / max(timings["sharded"], 1e-12),
    }


def _csv_rows(rec: dict) -> List[str]:
    from benchmarks.common import csv_row
    d = rec["n_devices"]
    rows = [csv_row(f"sharded_round/{eng}/d{d}", s * 1e6,
                    f"A{rec['n_agents']}xR{rec['n_rsus']}")
            for eng, s in rec["round_s"].items()]
    rows.append(csv_row(f"sharded_round/flat_vs_tree/d{d}",
                        rec["round_s"]["flat"] * 1e6,
                        f"speedup={rec['flat_vs_tree']:.2f}x"))
    sh = rec["time_split"]["sharded"]
    rows.append(csv_row(f"sharded_round/collective_s/d{d}",
                        sh["collective_s"] * 1e6,
                        f"frac={sh.get('collective_frac', 0.0):.2f}"))
    return rows


def run() -> List[str]:
    """Harness entry (benchmarks.run): one CPU child process per device
    count, so each cell gets a fresh jax with the forced device count."""
    from benchmarks.common import run_cpu_child
    rows: List[str] = []
    for n_dev in DEFAULT_DEVICES:
        out = run_cpu_child(["-m", "benchmarks.sharded_round", "--devices",
                             str(n_dev)], devices=n_dev, timeout=1200)
        rows.extend(ln for ln in out.splitlines()
                    if ln.startswith("sharded_round/"))
    return rows


def main():
    args = _parse_args()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    rec = run_cell(args)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"sharded_round__d{rec['n_devices']}.json"
    path.write_text(json.dumps(rec, indent=1))
    for row in _csv_rows(rec):
        print(row)
    print(f"[json] {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
