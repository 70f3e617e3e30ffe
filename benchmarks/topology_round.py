"""Replicated vs RSU-sharded round latency at large R (DESIGN.md §4).

The RSU-sharded mode exists for exactly one reason: with a large RSU axis
the replicated engine makes every device hold and psum the full (R, N)
buffer, while the topology-first layout keeps each pod's (R_local, N) block
local and pays cross-pod traffic only at the cloud layer.  This benchmark
records one compiled global round of the SAME large-R federated workload
under both modes into the BENCH json flow:

  replicated   — (R, N) buffer on every device, RSU psum over all agent axes
  rsu_sharded  — (R/pods, N) block per pod, within-pod psum only

Because the device count must be fixed before jax initializes, the cell runs
as a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=N (the
launch/dryrun mechanism), on the 2 x N/2 ('pod','data') fleet mesh.

Standalone:
  PYTHONPATH=src python -m benchmarks.topology_round --devices 8 \
      [--agents 64 --rsus 32 --rounds 2 --out results/bench]

Via the harness (the 8-device cell as a CPU child process; on an
accelerator the harness refuses it, since its process holds the chip):
  PYTHONPATH=src python -m benchmarks.run --only topology
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import List

HARNESS_DEVICES = 8


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (0 = use what's there)")
    ap.add_argument("--agents", type=int, default=64)
    ap.add_argument("--rsus", type=int, default=32,
                    help="large R: the regime the RSU-sharded mode targets")
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=2, help="timed rounds")
    ap.add_argument("--lar", type=int, default=2)
    ap.add_argument("--n-train", type=int, default=2000)
    ap.add_argument("--out", default=os.environ.get("REPRO_RESULTS",
                                                    "results") + "/bench")
    return ap.parse_args()


def _time_rounds(round_fn, state, n: int) -> float:
    """Mean per-round wall seconds, compile + relayout warmup excluded.
    The round jits donate their input state, so every call rebinds."""
    import jax
    state = round_fn(round_fn(state))            # compile x2 + warmup
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(n):
        state = round_fn(state)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / n


def run_cell(args) -> dict:
    import jax

    from repro.configs.mnist_mlp import CONFIG as MLP_CFG
    from repro.core import flatten
    from repro.core.baselines import h2fed
    from repro.core.heterogeneity import HeterogeneityModel
    from repro.data.partition import scenario_two
    from repro.data.synthetic import mnist_class_task
    from repro.fedsim.sharded import (make_fleet_mesh,
                                      make_sharded_global_round,
                                      resolve_topology)
    from repro.fedsim.simulator import SimConfig, init_flat_state
    from repro.models import mlp

    n_dev = len(jax.devices())
    train, _ = mnist_class_task(n_train=args.n_train, n_test=100, seed=0)
    fed = scenario_two(train, n_agents=args.agents, n_rsus=args.rsus,
                       seed=0)
    cfg = SimConfig(n_agents=args.agents, n_rsus=args.rsus, batch=16,
                    seed=0)
    hp = h2fed(mu1=0.01, mu2=0.005, lar=args.lar, lr=0.1)
    het = HeterogeneityModel(csr=0.8, lar=hp.lar)
    params = mlp.init_params(MLP_CFG, jax.random.key(0))
    spec = flatten.spec_of(params)
    mesh = make_fleet_mesh(n_dev, n_pods=args.pods if n_dev > 1 else 1)

    def key():
        return jax.random.key(cfg.seed)

    from repro.fedsim.simulator import make_flat_global_round
    from repro.launch.hlo_analysis import round_cost

    timings, costs = {}, {}
    with mesh:
        for mode, rsu_sharded in (("replicated", False),
                                  ("rsu_sharded", True)):
            topo = resolve_topology(cfg, fed, mesh,
                                    rsu_sharded=rsu_sharded)
            rf = make_sharded_global_round(cfg, hp, het, fed, spec, topo)

            def state():
                s = init_flat_state(cfg, spec, params, key())
                if topo.rsu_sharded:
                    s = s._replace(
                        agent_flat=topo.permute_agents(s.agent_flat))
                return s

            if topo.rsu_sharded:
                rsu_per_pod = topo.rsu_per_pod      # as actually executed
            timings[mode] = _time_rounds(rf, state(), args.rounds)
            costs[mode] = round_cost(rf, state(), latency_s=timings[mode])

    # fused vs un-fused one-pass round (DESIGN.md §3) on this cell's flat
    # engine — the A/B the CI bench-smoke asserts on (the fused program
    # must not be slower; off-TPU both lower to the same XLA ops, so this
    # guards against regressions rather than measuring a kernel win).
    # Host-CPU wall time drifts by tens of percent over a cell, so the
    # variants are timed in INTERLEAVED batches and each takes its best
    # batch — per-variant drift cancels instead of biasing whichever ran
    # second.
    ab = {}
    for mode, fused in (("flat_fused", True), ("flat_unfused", False)):
        rf = make_flat_global_round(cfg, hp, het, fed, spec, fused=fused)
        state = init_flat_state(cfg, spec, params, key())
        state = rf(rf(state))                    # compile + warmup
        ab[mode] = {"rf": rf, "state": state, "best": float("inf")}
    batch = max(args.rounds, 4)
    for _ in range(5):
        for mode in ab:
            v = ab[mode]
            jax.block_until_ready(v["state"])
            t0 = time.perf_counter()
            for _ in range(batch):
                v["state"] = v["rf"](v["state"])
            jax.block_until_ready(v["state"])
            v["best"] = min(v["best"],
                            (time.perf_counter() - t0) / batch)
    for mode, fused in (("flat_fused", True), ("flat_unfused", False)):
        timings[mode] = ab[mode]["best"]
        costs[mode] = round_cost(
            ab[mode]["rf"], init_flat_state(cfg, spec, params, key()),
            latency_s=timings[mode])

    return {
        "bench": "topology_round",
        "n_devices": n_dev,
        "mesh": dict(mesh.shape),
        "n_agents": args.agents,
        "n_rsus": args.rsus,
        "rsu_per_pod": rsu_per_pod,
        "lar": args.lar,
        "n_params": spec.n,
        "round_s": timings,
        "bytes_per_round": {m: c["bytes"] for m, c in costs.items()},
        "collective_bytes_per_round":
            {m: c["collective_bytes"] for m, c in costs.items()},
        "hbm_gbps": {m: c["hbm_gbps"] for m, c in costs.items()},
        "rsu_sharded_vs_replicated":
            timings["replicated"] / max(timings["rsu_sharded"], 1e-12),
        "flat_fused_vs_unfused":
            timings["flat_unfused"] / max(timings["flat_fused"], 1e-12),
    }


def _csv_rows(rec: dict) -> List[str]:
    from benchmarks.common import csv_row
    d = rec["n_devices"]
    rows = [csv_row(f"topology_round/{mode}/d{d}", s * 1e6,
                    f"A{rec['n_agents']}xR{rec['n_rsus']}")
            for mode, s in rec["round_s"].items()]
    rows += [csv_row(f"topology_round/bytes/{mode}/d{d}", b / 1e6,
                     f"MB/round gbps={rec['hbm_gbps'][mode]:.2f}")
             for mode, b in rec["bytes_per_round"].items()]
    rows.append(csv_row(
        f"topology_round/rsu_sharded_vs_replicated/d{d}",
        rec["round_s"]["rsu_sharded"] * 1e6,
        f"speedup={rec['rsu_sharded_vs_replicated']:.2f}x"
        f"@R{rec['n_rsus']}"))
    rows.append(csv_row(
        f"topology_round/flat_fused_vs_unfused/d{d}",
        rec["round_s"]["flat_fused"] * 1e6,
        f"speedup={rec['flat_fused_vs_unfused']:.2f}x"))
    return rows


def run() -> List[str]:
    """Harness entry (benchmarks.run --only topology): the multi-device
    cell in a CPU child process, so it gets a fresh jax with the forced
    device count."""
    from benchmarks.common import run_cpu_child
    out = run_cpu_child(["-m", "benchmarks.topology_round", "--devices",
                         str(HARNESS_DEVICES)], devices=HARNESS_DEVICES)
    return [ln for ln in out.splitlines()
            if ln.startswith("topology_round/")]


def main():
    args = _parse_args()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    rec = run_cell(args)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"topology_round__d{rec['n_devices']}.json"
    path.write_text(json.dumps(rec, indent=1))
    for row in _csv_rows(rec):
        print(row)
    print(f"[json] {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
