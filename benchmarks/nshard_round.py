"""N-sharding benchmark: per-device fleet bytes + cross-pod collective
bytes of the model-sharded engine (DESIGN.md §12, BENCH_PR10).

Two claims, both measured from compiled artifacts (never estimated):

  fleet bytes — ``hlo_analysis.memory_footprint`` OUTPUT bytes of the
      compiled round program are the per-device persistent fleet state:
      the round's output IS the next round's FlatSimState (agent rows +
      (R, N) staleness buffer + cloud master).  At ``model_shards=2`` the
      (R, N) staleness buffer and the fp32 cloud master live half-N per
      device, so fleet bytes must shrink ≥1.8x vs the model-replicated
      round on the SAME 8 devices (CI asserts from BENCH_PR10.json).

  cross-pod bytes — ``hlo_analysis.collective_axis_bytes`` attributes
      every collective in the round HLO to the mesh axes its replica
      groups span.  Bytes spanning ``pod`` ride the cross-pod DCI links;
      the N-sharded round's cloud layer reduces 1/shards-sized slices, so
      its pod-axis bytes must not exceed the replicated baseline's (the
      round-opening reference all-gather spans only the ``model`` axis —
      intra-pod ICI by construction).

Plus the big-N cell: a ~1e7-parameter MLP (hidden 12000) streamed through
``run_scenario`` with TWO-AXIS chunking (agent chunks x N-tiles), pinning
that the device working set is bounded by (chunk x N) + (R x tile), not
(A x N) + (R x N).

Standalone:
  PYTHONPATH=src python -m benchmarks.nshard_round --devices 8
Via the harness (the cell as an 8-device CPU child process; on an
accelerator the harness refuses it, since its process holds the chip):
  PYTHONPATH=src python -m benchmarks.run --only nshard
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import List

BIG_HIDDEN = 12000       # 784-12000-10 MLP -> N = 9.55e6 (~1e7) params


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--agents", type=int, default=8)
    ap.add_argument("--rsus", type=int, default=128)
    ap.add_argument("--rounds", type=int, default=2, help="timed rounds")
    ap.add_argument("--n-train", type=int, default=80)
    ap.add_argument("--big-hidden", type=int, default=BIG_HIDDEN)
    ap.add_argument("--out", default=os.environ.get("REPRO_RESULTS",
                                                    "results") + "/bench")
    return ap.parse_args()


def _sharded_cell(args, model_shards: int) -> dict:
    """Compile + time one sharded round at the given model_shards on the
    current device count; read fleet bytes and per-axis collective bytes
    off the compiled artifact."""
    import jax

    from benchmarks.sharded_round import _time_rounds
    from repro.configs.mnist_mlp import CONFIG as MLP_CFG
    from repro.core import flatten
    from repro.core.baselines import h2fed
    from repro.core.heterogeneity import HeterogeneityModel
    from repro.data.partition import scenario_two
    from repro.data.synthetic import mnist_class_task
    from repro.fedsim import sharded
    from repro.fedsim.simulator import SimConfig, init_flat_state
    from repro.launch import hlo_analysis
    from repro.models import mlp

    import numpy as np
    train, _ = mnist_class_task(n_train=args.n_train, n_test=100, seed=0)
    fed = scenario_two(train, n_agents=args.agents, n_rsus=args.rsus,
                       seed=0)
    # spread the small cohort's RSUs evenly across the id range so the
    # pod blocks are balanced (rsu_sharded needs equal agents per pod;
    # scenario_two's round-robin parks A<R cohorts all in pod 0)
    fed = dataclasses.replace(
        fed, rsu_assign=np.arange(args.agents, dtype=np.int32)
        * (args.rsus // args.agents))
    cfg = SimConfig(n_agents=args.agents, n_rsus=args.rsus, batch=8, seed=0)
    hp = h2fed(mu1=0.01, mu2=0.005, lar=2, lr=0.1)
    het = HeterogeneityModel(csr=0.8, lar=hp.lar)
    params = mlp.init_params(MLP_CFG, jax.random.key(0))
    spec = flatten.spec_of(params)

    mesh = sharded.make_fleet_mesh(n_model_shards=model_shards)
    # rsu_sharded on BOTH sides: the cloud layer is the round's one
    # explicit cross-pod collective, so pod-axis attribution compares the
    # same contract (DESIGN.md §4) at model_shards 1 vs S
    topo = sharded.resolve_topology(cfg, fed, mesh, rsu_sharded=True)
    round_fn = sharded.make_sharded_global_round(cfg, hp, het, fed, spec,
                                                 topo)
    state = init_flat_state(cfg, spec, params, jax.random.key(cfg.seed))
    state = sharded.pad_model_axis(state, topo, spec.n)
    with mesh:
        lowered = round_fn.lower(state)
        mem = hlo_analysis.memory_footprint(round_fn, state)
        axes = list(zip(mesh.axis_names, mesh.devices.shape))
        coll = hlo_analysis.collective_axis_bytes(
            lowered.compile().as_text(), axes)
        if topo.rsu_sharded:
            state = state._replace(
                agent_flat=topo.permute_agents(state.agent_flat))
        round_s = _time_rounds(round_fn, state, args.rounds)
    return {
        "model_shards": model_shards,
        "mesh": dict(mesh.shape),
        "n_params": spec.n,
        "n_params_padded": topo.model_pad(spec.n),
        "round_s": round_s,
        "fleet_bytes_per_device": mem["output_bytes"],
        "collective_bytes_per_axis": coll["per_axis"],
        "n_collectives": len(coll["entries"]),
    }


def _bign_cell(args) -> dict:
    """~1e7-param model through run_scenario under two-axis streaming;
    the device working set is pinned off the compiled chunk programs."""
    import jax
    import jax.numpy as jnp

    from repro.core.scenario import ScenarioSpec
    from repro.fedsim import run_scenario
    from repro.launch import hlo_analysis

    spec = ScenarioSpec(
        n_agents=8, n_rsus=4, batch=8, n_train=160, n_test=100, rounds=1,
        fleet_store="host", chunk_agents=4, chunk_params=1 << 20,
        fleet_dtype="bf16", hidden_dims=(args.big_hidden,))
    t0 = time.perf_counter()
    state, history = run_scenario(spec)
    wall = time.perf_counter() - t0

    # re-build the round to lower its chunk programs (run_scenario keeps
    # them internal); abstract args only — nothing big is allocated
    from repro.core import flatten
    from repro.fedsim import streaming
    from repro.models import mlp
    from repro.configs.mnist_mlp import CONFIG
    res = spec.resolve()
    cfg_model = dataclasses.replace(CONFIG, hidden_dims=spec.hidden_dims)
    params = mlp.init_params(cfg_model, jax.random.key(spec.seed))
    fspec = flatten.spec_of(
        params, storage_dtype=flatten.resolve_storage_dtype("bf16"))
    round_fn = streaming.make_streamed_twoaxis_round(
        res.cfg, spec.hp, spec.het, res.fed, fspec,
        chunk_agents=spec.chunk_agents, chunk_params=spec.chunk_params)
    plan, tiles = round_fn.plan, round_fn.tiles
    sds = jax.ShapeDtypeStruct
    import numpy as np
    x_np, y_np = np.asarray(res.fed.x), np.asarray(res.fed.y)
    samples = x_np.shape[1]
    train_mem = hlo_analysis.memory_footprint(
        round_fn.chunk_train,
        sds((plan.chunk, tiles.n_padded), fspec.storage_dtype),
        sds((tiles.n_padded,), jnp.float32),
        sds((plan.chunk, samples) + x_np.shape[2:], x_np.dtype),
        sds((plan.chunk, samples), y_np.dtype),
        sds((plan.chunk,), jnp.int32),
        sds((plan.chunk,), jnp.float32))
    agg_mem = hlo_analysis.memory_footprint(
        round_fn.tile_agg,
        sds((plan.chunk, tiles.tile), fspec.storage_dtype),
        sds((plan.chunk,), jnp.float32),
        sds((plan.chunk,), jnp.int32))
    n = fspec.n
    return {
        "n_params": n,
        "hidden": args.big_hidden,
        "chunk_agents": plan.chunk,
        "chunk_params": tiles.tile,
        "n_tiles": tiles.n_tiles,
        "round_wall_s": wall,
        "final_acc": float(history["acc"][-1]),
        "host_fleet_bytes": float(state.store.nbytes),
        "train_working_set_bytes": train_mem["total_bytes"],
        "agg_working_set_bytes": agg_mem["total_bytes"],
        # the bound the two-axis design promises: training is O(chunk*N)
        # (full-N per agent chunk — the gradient couples all params, so
        # this leg CAN'T tile on N), aggregation O(R*tile); the honest
        # comparator for the agg side is the f32 (R, N_pad) numerator a
        # one-axis streamed round materializes on device
        "rsu_numerator_bytes": spec.n_rsus * tiles.n_padded * 4.0,
        "fleet_full_bytes": float(state.store.nbytes)
        + spec.n_rsus * tiles.n_padded * 2 + tiles.n_padded * 4,
    }


def run_cell(args) -> dict:
    import jax
    n_dev = len(jax.devices())
    base = _sharded_cell(args, model_shards=1)
    nsh = _sharded_cell(args, model_shards=2)
    big = _bign_cell(args)
    fleet_ratio = (base["fleet_bytes_per_device"]
                   / max(nsh["fleet_bytes_per_device"], 1.0))
    pod_base = base["collective_bytes_per_axis"].get("pod", 0.0)
    pod_nsh = nsh["collective_bytes_per_axis"].get("pod", 0.0)
    return {
        "bench": "nshard_round",
        "n_devices": n_dev,
        "n_agents": args.agents,
        "n_rsus": args.rsus,
        "replicated": base,
        "nsharded": nsh,
        "big_n": big,
        "fleet_bytes_ratio": fleet_ratio,
        "crosspod_bytes": {"replicated": pod_base, "nsharded": pod_nsh},
        "crosspod_ratio": pod_nsh / max(pod_base, 1.0),
        "round_s": {"replicated": base["round_s"],
                    "nsharded": nsh["round_s"]},
    }


def _csv_rows(rec: dict) -> List[str]:
    from benchmarks.common import csv_row
    d = rec["n_devices"]
    rows = [csv_row(f"nshard_round/{k}/d{d}", v["round_s"] * 1e6,
                    f"fleet_bytes={v['fleet_bytes_per_device']:.0f}")
            for k, v in (("replicated", rec["replicated"]),
                         ("nsharded", rec["nsharded"]))]
    rows.append(csv_row(f"nshard_round/fleet_ratio/d{d}",
                        rec["nsharded"]["round_s"] * 1e6,
                        f"shrink={rec['fleet_bytes_ratio']:.2f}x"))
    rows.append(csv_row("nshard_round/big_n",
                        rec["big_n"]["round_wall_s"] * 1e6,
                        f"N={rec['big_n']['n_params']}"))
    return rows


def run() -> List[str]:
    """Harness entry: one 8-device CPU child process (the device count
    must be fixed before jax initializes, as in benchmarks/sharded_round)."""
    from benchmarks.common import run_cpu_child
    out = run_cpu_child(["-m", "benchmarks.nshard_round", "--devices", "8"],
                        devices=8)
    return [ln for ln in out.splitlines()
            if ln.startswith("nshard_round/")]


def main():
    args = _parse_args()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    rec = run_cell(args)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / "nshard_round.json"
    path.write_text(json.dumps(rec, indent=1))
    for row in _csv_rows(rec):
        print(row)
    print(f"[json] {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
