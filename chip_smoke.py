"""Bring-up smoke run of the H²-Fed paper cell on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded engine only

One chip runs the paper's Sec. VI cell end to end through the normal
entry points (``ScenarioSpec`` -> ``resolve()`` -> ``pretrain_to_target``
-> ``fedsim.run_scenario``) at the paper's full width: the 784-40-10 MLP
(N = 31,810), 100 agents under 10 RSUs, the scenario-II partition with
labels 7, 8, 9 excluded from the pretrain pool, and 90% of the fleet
disconnected (CSR 0.1).  The biased pretrained model (~0.68) is trained
from the seed in the same run.  Phases, all in this one process:

  flat-f32   the flat engine on the compiled Pallas aggregation kernels
             (checked in the compiled round program), then again on the
             XLA route on the same chip; the two cloud masters must agree
             to fp32 tolerance and accuracy must beat the pretrained model;
  async      the semi-async engine, finite and above the pretrained model;
  flat-bf16  the flat engine with a bf16 fleet, likewise.

``--chips 4`` runs only the sharded engine on a four-chip mesh (RSU-
sharded on a 2x2 pod/data mesh, and two model shards) against the flat
engine on one device of the same process.

The script needs a TPU: with no accelerator, or with ``REPRO_INTERPRET``
set, it exits non-zero before any phase.  Any failed check propagates as
a non-zero exit.  Timing lines are smoke timings, not benchmark numbers.
The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core.h2fed import H2FedParams                    # noqa: E402
from repro.core.heterogeneity import HeterogeneityModel     # noqa: E402
from repro.core.scenario import ScenarioSpec                # noqa: E402

ROUNDS = 5
# fp32 agreement between two routes of the same cell: the tolerance
# tests/test_sharded.py pins for the cloud and RSU rows of the sharded
# engine against the flat engine, and for the accuracy history
CLOUD_TOL = dict(rtol=1e-4, atol=1e-4)
ACC_TOL = 2e-3


def paper_spec(**overrides) -> ScenarioSpec:
    """The paper's Sec. VI cell at ``REPRO_BENCH_FULL`` scale
    (benchmarks/common.py) under the 90%-disconnect case."""
    lar = 5
    kw = dict(n_agents=100, n_rsus=10, batch=32, n_train=22_000,
              n_test=4_000, noise=0.8, excluded_labels=(7, 8, 9),
              pretrain_frac=0.12, pretrain_target=0.68,
              partition="scenario_two",
              hp=H2FedParams(mu1=0.01, mu2=0.005, lar=lar, lr=0.05),
              het=HeterogeneityModel(csr=0.1, scd=1, lar=lar),
              rounds=ROUNDS, seed=0)
    kw.update(overrides)
    return ScenarioSpec(**kw).validate()


def device_check(n_chips: int) -> dict:
    """The TPU this run must use, as JAX reports it.  Exits non-zero when
    JAX finds no TPU or fewer than ``n_chips``, or when the kernels would
    not take the compiled Pallas route."""
    if os.environ.get("REPRO_INTERPRET"):
        raise SystemExit("chip_smoke: REPRO_INTERPRET is set; the smoke "
                         "run checks the compiled Pallas kernels")
    import jax

    from repro.kernels import ops
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{d.platform!r} ({d.device_kind})")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, JAX found "
                         f"{len(devices)}")
    if ops.interpret_mode():
        raise SystemExit("chip_smoke: the kernels would take the XLA route")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def pretrained(res):
    """The biased OEM model, trained from the spec's seed in this run."""
    import jax

    from repro.configs.mnist_mlp import CONFIG
    from repro.fedsim.pretrain import pretrain_to_target
    from repro.models import mlp
    s = res.spec
    params = mlp.init_params(CONFIG, jax.random.key(s.seed))
    return pretrain_to_target(params, res.pretrain_pool, res.test.x,
                              res.test.y, target_acc=s.pretrain_target,
                              max_epochs=40, seed=s.seed)


def _timed_eval(res, stamps):
    """Test-set accuracy that records when each round's result was ready:
    the engine evaluates after every round, and the stamp is taken after
    ``block_until_ready`` of an accuracy that depends on that round."""
    import jax
    import jax.numpy as jnp

    from repro.models import mlp
    x, y = jnp.asarray(res.test.x), jnp.asarray(res.test.y)
    acc = jax.jit(lambda p: mlp.accuracy(p, x, y))

    def eval_fn(params):
        a = acc(params).block_until_ready()
        stamps.append(time.perf_counter())
        return a
    return eval_fn


def run_cell(res, params, pre_acc: float, label: str):
    """One ``run_scenario`` call with finite/above-pretrained checks and
    its smoke timings; returns (state, history)."""
    import numpy as np

    from repro.fedsim import run_scenario
    stamps = [time.perf_counter()]
    state, hist = run_scenario(res, params,
                               eval_fn=_timed_eval(res, stamps))
    acc = np.asarray(hist["acc"])
    assert np.isfinite(acc).all(), f"{label}: non-finite accuracy {acc}"
    assert acc[-1] > pre_acc, \
        f"{label}: accuracy {acc[-1]:.4f} not above pretrained {pre_acc:.4f}"
    steady = np.diff(stamps[1:])
    log(f"{label}: acc {pre_acc:.4f} -> {acc[-1]:.4f}; set-up + first "
        f"round {stamps[1] - stamps[0]:.3f} s, steady round "
        f"{np.median(steady) if steady.size else float('nan'):.4f} s "
        f"(smoke timings, not benchmark numbers)")
    return state, hist


def assert_pallas_route(res, params) -> None:
    """The kernels take the compiled Pallas route, and the compiled flat
    round of this cell calls them for both aggregation layers."""
    import jax

    from repro.core import flatten
    from repro.fedsim import simulator
    from repro.kernels import ops
    assert not ops.interpret_mode(), "the kernels take the XLA route"
    s = res.spec
    fspec = flatten.spec_of(params)
    round_fn = simulator.make_flat_global_round(res.cfg, s.hp, s.het,
                                                res.fed, fspec)
    state = jax.eval_shape(lambda: simulator.init_flat_state(
        res.cfg, fspec, params, jax.random.key(0)))
    n = round_fn.lower(state).compile().as_text().count("tpu_custom_call")
    assert n >= 2, f"{n} Pallas calls in the compiled flat round"
    log(f"flat-f32: the compiled round holds {n} Pallas kernel calls")


def _cloud(state):
    import numpy as np

    from repro.core import flatten
    return np.asarray(flatten.spec_of(state.cloud_params).ravel(
        state.cloud_params), np.float32)


def phase_flat(res, params, pre_acc: float) -> float:
    """Flat engine, fp32: the Pallas route against the XLA route on the
    same device.  Both runs pin matmuls to fp32: at the TPU's default
    precision the local training's fp32 matmuls take one bf16 pass, and a
    one-ulp difference in an aggregate then flips bf16 roundings in the
    next local round.  Returns the max |Δ| of the two cloud masters."""
    import jax
    import numpy as np

    from repro.kernels import ops
    with jax.default_matmul_precision("highest"):
        st_k, h_k = run_cell(res, params, pre_acc, "flat-f32 pallas")
        assert_pallas_route(res, params)
        ops.set_interpret(True)
        try:
            st_x, h_x = run_cell(res, params, pre_acc, "flat-f32 xla")
        finally:
            ops.set_interpret(None)
    cloud_k, cloud_x = _cloud(st_k), _cloud(st_x)
    diff = float(np.max(np.abs(cloud_k - cloud_x)))
    log(f"flat-f32: pallas vs xla cloud master max |diff| {diff:.3e}")
    np.testing.assert_allclose(cloud_k, cloud_x, **CLOUD_TOL)
    np.testing.assert_allclose(h_k["acc"], h_x["acc"], atol=ACC_TOL)
    return diff


def phase_async(res, params, pre_acc: float) -> None:
    """Semi-async engine: stragglers deliver up to one tick late."""
    s = res.spec
    spec = s.replace(engine="async",
                     het=dataclasses.replace(s.het, max_delay=1,
                                             delay_p=0.3))
    run_cell(spec.resolve(), params, pre_acc, "async")


def phase_bf16(res, params, pre_acc: float) -> None:
    """Flat engine with the fleet buffers stored in bf16."""
    spec = res.spec.replace(fleet_dtype="bfloat16")
    run_cell(spec.resolve(), params, pre_acc, "flat-bf16")


def phase_sharded(res, params, n_devices: int = 4) -> float:
    """The sharded engine on ``n_devices`` chips against the flat engine
    on one of them: RSU-sharded on a 2x2 (pod, data) mesh, and the
    parameter axis over two model shards.  Returns the max |Δ| of the
    cloud masters."""
    import jax
    import numpy as np

    from repro.fedsim import run_scenario
    s = res.spec
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        ref, h_ref = run_scenario(res, params)
        log(f"flat reference: acc {h_ref['acc'][-1]:.4f} in "
            f"{time.perf_counter() - t0:.3f} s (smoke timing)")
        ref_cloud = _cloud(ref)
        n = ref_cloud.shape[0]
        for label, kw in (("rsu_sharded", dict(rsu_sharded=True)),
                          ("model_shards=2", dict(model_shards=2))):
            spec = s.replace(engine="sharded", **kw)
            t0 = time.perf_counter()
            st, hist = run_scenario(spec.resolve(), params)
            wall = time.perf_counter() - t0
            split = []
            for name in ("agent_flat", "rsu_flat", "cloud_flat"):
                buf = getattr(st, name)
                devs = {sh.device for sh in buf.addressable_shards}
                assert len(devs) == n_devices, \
                    f"{label}: {name} spans {len(devs)} devices"
                if buf.sharding.shard_shape(buf.shape) != buf.shape:
                    split.append(f"{name} {buf.sharding.spec}")
            assert split, f"{label}: no fleet buffer is sharded"
            cloud = np.asarray(st.cloud_flat, np.float32)[:n]
            diff = float(np.max(np.abs(cloud - ref_cloud)))
            worst = max(worst, diff)
            mesh = dict(st.cloud_flat.sharding.mesh.shape)
            log(f"sharded {label}: mesh {mesh}, split {', '.join(split)}; "
                f"acc {hist['acc'][-1]:.4f}, "
                f"cloud max |diff| vs flat {diff:.3e}, {wall:.3f} s "
                f"(smoke timing)")
            np.testing.assert_allclose(cloud, ref_cloud, **CLOUD_TOL)
            np.testing.assert_allclose(hist["acc"], h_ref["acc"],
                                       atol=ACC_TOL)
    return worst


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded engine on four chips")
    args = ap.parse_args(argv)
    device = device_check(args.chips)
    log(f"device {device['kind']} x{device['count']}")

    t0 = time.perf_counter()
    res = paper_spec().resolve()
    params, pre_acc = pretrained(res)
    log(f"data + pretrain: acc {pre_acc:.4f} in "
        f"{time.perf_counter() - t0:.3f} s")
    if args.chips == 4:
        phase_sharded(res, params)
    else:
        phase_flat(res, params, pre_acc)
        phase_async(res, params, pre_acc)
        phase_bf16(res, params, pre_acc)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
