"""Production training launcher: H²-Fed hierarchical rounds on a device mesh.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        [--mesh 2,4,1] [--devices 8] [--reduced] [--rounds 8] \
        [--lar 4] [--epochs 1] [--csr 0.8] [--quantize-cloud] \
        [--adaptive-mu] [--ckpt-dir results/ckpt] [--seq 128 --batch 4]

Runs the paper's Algorithms 1–3 as one compiled SPMD program per global
round (launch/h2fed_round.py) over synthetic Non-IID LM shards, with
checkpointing and optional adaptive-mu orchestration (core/orchestrator).
The mesh defaults to the visible devices (2 pods when there are 4 or
more, an even count).  ``--devices N`` is for CPU dry runs only: it forces
N host devices before JAX starts.

``--scenario-json spec.json`` instead runs a declarative experiment
scenario (core/scenario.ScenarioSpec, DESIGN.md §7) through the fedsim
engines — any paper-figure cell, engine / partition / heterogeneity chosen
by the spec.
"""
import argparse
import os


def _decay_arg(s: str):
    """float, or comma list -> tuple of per-pod/RSU decay rates."""
    vals = tuple(float(x) for x in s.split(","))
    return vals[0] if len(vals) == 1 else vals


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="reduced config (full configs need a real pod)")
    ap.add_argument("--full-config", dest="reduced", action="store_false")
    ap.add_argument("--devices", type=int, default=0,
                    help="force this many host devices (CPU dry runs; "
                         "0 = the visible devices)")
    ap.add_argument("--mesh", default="",
                    help="pod,data,model mesh shape (default: the visible "
                         "devices as 2 x n/2 x 1 for an even n >= 4, else "
                         "1 x n x 1)")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--lar", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--mu1", type=float, default=0.001)
    ap.add_argument("--mu2", type=float, default=0.005)
    ap.add_argument("--csr", type=float, default=0.8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--quantize-cloud", action="store_true")
    ap.add_argument("--flat-agg", action="store_true",
                    help="flat-buffer aggregation: one fused collective per "
                         "hierarchy layer instead of per-leaf reductions")
    ap.add_argument("--async-rounds", type=int, default=0, metavar="D",
                    help="semi-async rounds with a staleness-bounded "
                         "in-flight buffer: agents deliver up to D local "
                         "ticks late with staleness-decayed weight "
                         "(implies --flat-agg; 0 = synchronous)")
    ap.add_argument("--staleness-decay", type=_decay_arg, default=0.5,
                    metavar="D[,D...]",
                    help="per-tick exponential decay of late deliveries; a "
                         "comma list gives one rate per pod/RSU (per-RSU "
                         "adaptive staleness, DESIGN.md §6)")
    ap.add_argument("--buffer-keep", type=float, default=0.0,
                    help="RSU cohort mass retained across ticks [0, 1]")
    ap.add_argument("--fleet-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="fleet-buffer / aggregation-reduction dtype "
                         "(DESIGN.md §3 dtype policy): bfloat16 halves "
                         "ICI/DCI collective bytes (requires --flat-agg)")
    ap.add_argument("--adaptive-mu", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scenario-json", default="", metavar="PATH",
                    help="run a declarative ScenarioSpec (core/scenario, "
                         "DESIGN.md §7) through the fedsim engines instead "
                         "of the LM arch path — any paper-figure cell from "
                         "the CLI")
    ap.add_argument("--scenario-pretrain", action="store_true",
                    help="with --scenario-json: run the spec's OEM "
                         "pretrain stage first (the biased '68%' model) "
                         "instead of a fresh init")
    ap.add_argument("--fleet-store", default="", choices=("", "device",
                                                          "host"),
                    help="with --scenario-json: override the spec's fleet "
                         "row storage (DESIGN.md §8) — 'host' streams the "
                         "(A, N) fleet from host memory in cohort chunks")
    ap.add_argument("--chunk-agents", type=int, default=-1, metavar="C",
                    help="with --scenario-json: override the spec's "
                         "streamed chunk size (agents per device chunk; "
                         "0 = auto)")
    return ap.parse_args()


def _run_scenario_json(args):
    """Run one declarative scenario end to end (engine chosen by the spec:
    flat / tree / sharded / async; sharded uses the visible devices)."""
    from pathlib import Path

    import jax

    from repro.configs.mnist_mlp import CONFIG as MLP_CFG
    from repro.core.scenario import ScenarioSpec
    from repro.fedsim.sweep import run_scenario
    from repro.models import mlp

    spec = ScenarioSpec.from_json(Path(args.scenario_json).read_text())
    if args.fleet_store:
        spec = spec.replace(fleet_store=args.fleet_store)
    if args.chunk_agents >= 0:
        spec = spec.replace(chunk_agents=args.chunk_agents)
    spec.validate()
    res = spec.resolve()
    print(f"[scenario] {args.scenario_json}  cache_key={spec.cache_key}")
    print(f"[scenario] engine={spec.engine} partition={spec.partition} "
          f"A={spec.n_agents} R={spec.n_rsus} rounds={spec.rounds} "
          f"fleet_store={spec.fleet_store} chunk_agents={spec.chunk_agents}")
    params = mlp.init_params(MLP_CFG, jax.random.key(spec.seed))
    if args.scenario_pretrain:
        from repro.fedsim.pretrain import pretrain_to_target
        params, pre_acc = pretrain_to_target(
            params, res.pretrain_pool, res.test.x, res.test.y,
            target_acc=spec.pretrain_target, seed=spec.seed)
        print(f"[pretrain] biased OEM model: test acc {pre_acc:.3f}")
    _, hist = run_scenario(res, params)
    for r, a in zip(hist["round"], hist["acc"]):
        print(f"[round {r:3d}] acc {a:.4f}")
    print("[done]")


def main():
    args = _parse_args()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    if args.scenario_json:
        return _run_scenario_json(args)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.checkpoint import ckpt
    from repro.configs.registry import get_config, get_reduced_config
    from repro.core import orchestrator as orch
    from repro.core.h2fed import H2FedParams
    from repro.core.topology import HierarchyTopology
    from repro.data.synthetic import lm_token_task
    from repro.launch import sharding as shard
    from repro.launch.h2fed_round import comm_model, make_h2fed_round
    from repro.models import model as M

    from repro.launch.mesh import make_mesh

    if args.mesh:
        mesh_shape = tuple(int(x) for x in args.mesh.split(","))
    else:
        n = len(jax.devices())
        mesh_shape = (2, n // 2, 1) if n >= 4 and n % 2 == 0 else (1, n, 1)
    mesh = make_mesh(mesh_shape, ("pod", "data", "model"))
    topo = HierarchyTopology.from_mesh(mesh)
    A = topo.n_agents
    if args.async_rounds and not args.flat_agg:
        print("[async] --async-rounds implies --flat-agg (raveled pending "
              "buffer); enabling it")
        args.flat_agg = True
    if args.fleet_dtype != "float32" and not args.flat_agg:
        print("[dtype] --fleet-dtype implies --flat-agg (storage-dtype "
              "reduction on the raveled buffer); enabling it")
        args.flat_agg = True
    cfg = (get_reduced_config if args.reduced else get_config)(args.arch)
    if cfg.encoder.kind != "none":
        raise SystemExit("text-only archs for the LM training launcher")

    base_hp = H2FedParams(mu1=args.mu1, mu2=args.mu2, lar=args.lar,
                          local_epochs=args.epochs, lr=args.lr)
    params = M.init_params(cfg, jax.random.key(args.seed))
    n_par = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    cm = comm_model(cfg, base_hp, mesh, quantize_cloud=args.quantize_cloud)
    print(f"[mesh] {dict(mesh.shape)}  agents={A}")
    print(f"[model] {args.arch}{' (reduced)' if args.reduced else ''}: "
          f"{n_par/1e6:.1f}M params")
    print(f"[comm] ici={cm['ici_s']*1e3:.1f}ms dci={cm['dci_s']*1e3:.1f}ms "
          f"per-round (analytical)")

    # Non-IID agent shards: per-agent Markov streams
    streams = [lm_token_task(vocab=min(cfg.vocab_size, 512),
                             n_tokens=args.lar * args.batch * (args.seq + 1)
                             * 4, seed=100 + a) for a in range(A)]
    rng = np.random.default_rng(args.seed)

    mu_state, mu_cfg = orch.init_state(), orch.AdaptiveMuConfig()
    hp = base_hp
    round_fns = {}

    with mesh:
        cloud = jax.device_put(
            params, jax.tree.map(lambda _: shard.replicated(mesh), params))
        ev = {"tokens": jnp.asarray(streams[0][:args.batch * args.seq]
                                    .reshape(args.batch, args.seq)),
              "labels": jnp.asarray(streams[0][1:args.batch * args.seq + 1]
                                    .reshape(args.batch, args.seq))}
        print(f"[init] eval loss {float(M.loss_fn(cfg, cloud, ev)[0]):.4f}")

        for r in range(args.rounds):
            if args.adaptive_mu:
                hp, badness = orch.schedule(mu_state, mu_cfg, base_hp)
            key = (hp.mu1, hp.mu2)
            if key not in round_fns:
                fn = make_h2fed_round(cfg, hp, mesh,
                                      quantize_cloud=args.quantize_cloud,
                                      flat_agg=args.flat_agg,
                                      async_rounds=args.async_rounds,
                                      staleness_decay=args.staleness_decay,
                                      buffer_keep=args.buffer_keep,
                                      fleet_dtype=args.fleet_dtype)
                mask_sh = NamedSharding(mesh, topo.stacked_spec())
                in_sh = (
                    shard.param_shardings_model_only(
                        jax.eval_shape(lambda: params), mesh),
                    {"tokens": NamedSharding(mesh, topo.stacked_spec()),
                     "labels": NamedSharding(mesh, topo.stacked_spec())},
                    mask_sh,
                    NamedSharding(mesh, topo.agent_spec))
                if args.async_rounds:
                    in_sh = in_sh + (mask_sh,)
                round_fns[key] = jax.jit(fn, in_shardings=in_sh)

            n = args.batch * (args.seq + 1)
            toks = np.zeros((args.lar, A, args.batch, args.seq), np.int32)
            labs = np.zeros_like(toks)
            for a in range(A):
                off = (r * args.lar * n) % max(len(streams[a])
                                               - n * args.lar, 1)
                for l in range(args.lar):
                    seg = np.resize(streams[a][off + l * n:
                                               off + (l + 1) * n], n)
                    seg = seg.reshape(args.batch, args.seq + 1)
                    toks[l, a], labs[l, a] = seg[:, :-1], seg[:, 1:]
            mask = (rng.random((args.lar, A)) < args.csr).astype(np.float32)
            n_data = np.full((A,), float(args.batch * args.seq), np.float32)

            round_args = [cloud, {"tokens": jnp.asarray(toks),
                                  "labels": jnp.asarray(labs)},
                          jnp.asarray(mask), jnp.asarray(n_data)]
            if args.async_rounds:
                delays = rng.integers(0, args.async_rounds + 1,
                                      (args.lar, A)).astype(np.int32)
                round_args.append(jnp.asarray(delays))
            cloud, metrics = round_fns[key](*round_args)
            observed = float(mask.mean())
            mu_state = orch.observe_csr(mu_state, mu_cfg, observed, 1.0)
            loss = float(M.loss_fn(cfg, cloud, ev)[0])
            print(f"[round {r+1:3d}] loss {loss:.4f} csr_obs {observed:.2f} "
                  f"mu=({hp.mu1:.4f},{hp.mu2:.4f}) "
                  f"mass {float(metrics['surviving_mass']):.0f}")
            if args.ckpt_dir and (r + 1) % args.ckpt_every == 0:
                path = ckpt.save(args.ckpt_dir, r + 1, cloud)
                print(f"[ckpt] {path}")

    print("[done]")


if __name__ == "__main__":
    main()
