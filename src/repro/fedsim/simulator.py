"""Paper-faithful H²-Fed simulator (Algorithms 1–3), fully vectorized.

One compiled ``global_round``:

  1. RSUs download the cloud model (Alg. 2 line 2): w_k ← w.
  2. LAR local rounds (lax.scan).  Per local round:
       a. connectivity draw (CSR/SCD) + FSR epoch draw  (Sec. III),
       b. every agent trains from its RSU model w_k for its completed
          epochs with the dual-proximal objective (Alg. 1, Eq. 6) —
          vmap over agents, scan over minibatch steps,
       c. CSR-masked, data-volume-weighted per-RSU aggregation
          (Alg. 2 line 8); RSUs with an empty cohort keep their model.
  3. Cloud aggregation over RSUs weighted by surviving data mass
     (Alg. 3 line 6); if nothing survived the cloud model is kept.

Two engines share this program structure (DESIGN.md §3):

  engine="flat" (default, the production hot path) — the fleet lives in
  contiguous fp32 buffers: agents (A, N), RSUs (R, N), cloud (N,)
  (core/flatten).  Both aggregation layers are single Pallas matmul calls
  (kernels/masked_hier_agg via kernels/ops); local training unravels each
  agent's row once and runs the dual-proximal update on the model's leaves.
  fedsim/sharded.py partitions the same buffers' agent axis over a device
  mesh.

  engine="tree" (the reference) — per-leaf jax.tree.map aggregation
  (core/aggregation).  Property tests assert both engines agree to fp32
  tolerance (tests/test_flatten.py).

  engine="async" (fedsim/async_engine, DESIGN.md §6) — drops the global
  round barrier: agents deliver with drawn arrival latencies, RSU buffers
  absorb stragglers with staleness-decayed weights, and the cloud
  aggregates at its own cadence.  With zero latencies and decay disabled it
  reproduces engine="flat" to fp32 tolerance (tests/test_async.py).

Baseline equivalences (paper Sec. V) hold *exactly* by construction:
LAR=1 makes the RSU layer a pass-through (w_k == w at training time), so
mu=0 is FedAvg and mu1>0 is FedProx on the flat topology; mu=0 with LAR>1
is HierFAVG.  Property tests assert this numerically.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as faults_mod
from repro.core import flatten
from repro.core.aggregation import (blend_on_mass, broadcast_to_agents,
                                    gather_rsu_for_agents, masked_weighted_mean,
                                    rsu_aggregate, screen_updates)
from repro.core.h2fed import H2FedParams
from repro.core.heterogeneity import (ConnState, HeterogeneityModel,
                                      init_conn_state, step_connectivity)
from repro.data.partition import FederatedData
from repro.data.pipeline import agent_minibatch
from repro.kernels import ops
from repro.models import mlp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class SimConfig:
    n_agents: int = 100
    n_rsus: int = 10
    batch: int = 32
    seed: int = 0
    eval_every: int = 1     # global rounds between test-set evaluations


class SimState(NamedTuple):
    """Pytree-view state (the eval/checkpoint boundary representation)."""
    agent_params: PyTree    # stacked (A, ...) — w_{i,k}
    rsu_params: PyTree      # stacked (R, ...) — w_k
    cloud_params: PyTree    # (...)            — w
    conn: ConnState
    rng: jax.Array


class FlatSimState(NamedTuple):
    """Flat-buffer state: the whole fleet as three contiguous buffers.

    agent_flat/rsu_flat live in the spec's STORAGE dtype (fp32 default;
    bf16 halves fleet HBM + collective bytes, DESIGN.md §3); cloud_flat is
    always the fp32 master."""
    agent_flat: jax.Array   # (A, N)  storage dtype
    rsu_flat: jax.Array     # (R, N)  storage dtype
    cloud_flat: jax.Array   # (N,)    fp32 master
    conn: ConnState
    rng: jax.Array


def init_state(cfg: SimConfig, init_params: PyTree, key) -> SimState:
    return SimState(
        agent_params=broadcast_to_agents(init_params, cfg.n_agents),
        rsu_params=broadcast_to_agents(init_params, cfg.n_rsus),
        cloud_params=init_params,
        conn=init_conn_state(cfg.n_agents),
        rng=key)


def init_flat_state(cfg: SimConfig, spec: flatten.FlatSpec,
                    init_params: PyTree, key) -> FlatSimState:
    vec = spec.ravel(init_params)
    sv = spec.to_storage(vec)
    return FlatSimState(
        agent_flat=jnp.broadcast_to(sv, (cfg.n_agents, spec.n)),
        rsu_flat=jnp.broadcast_to(sv, (cfg.n_rsus, spec.n)),
        cloud_flat=vec,
        conn=init_conn_state(cfg.n_agents),
        rng=key)


def to_flat_state(spec: flatten.FlatSpec, state: SimState) -> FlatSimState:
    return FlatSimState(
        agent_flat=spec.to_storage(spec.ravel_stacked(state.agent_params)),
        rsu_flat=spec.to_storage(spec.ravel_stacked(state.rsu_params)),
        cloud_flat=spec.ravel(state.cloud_params),
        conn=state.conn, rng=state.rng)


def from_flat_state(spec: flatten.FlatSpec, state: FlatSimState) -> SimState:
    return SimState(agent_params=spec.unravel_stacked(state.agent_flat),
                    rsu_params=spec.unravel_stacked(state.rsu_flat),
                    cloud_params=spec.unravel(state.cloud_flat),
                    conn=state.conn, rng=state.rng)


class Cadence(NamedTuple):
    """Static upper bounds for the cadence knobs (DESIGN.md §7/§10).

    When a round body receives a ``Cadence``, ``hp.lar``/``hp.local_epochs``
    may be traced per-scenario scalars: the LAR scan runs to ``lar`` and a
    per-iteration ``live = i < hp.lar`` mask makes padded iterations
    algebra-neutral (carry and metrics pass through unchanged), while the
    minibatch scan runs to ``local_epochs``·spe with the existing
    ``active_steps`` masking.  ``None`` keeps the fully static program."""
    lar: int
    local_epochs: int


def round_keys(k_rounds, n: int) -> jax.Array:
    """The ``n`` per-local-round draw keys, cadence-independent.

    Key i is ``fold_in(k_rounds, i)``, so the first k keys of a padded
    n-bound schedule equal the k keys a lar=k program draws —
    ``jax.random.split(k, lar)`` does NOT have this prefix property (its
    counter layout depends on lar).  Every engine derives its local-round
    keys here so the sweep's masked static-upper-bound padding reproduces
    sequential execution exactly (tests/test_sweep.py).
    """
    return jax.vmap(lambda i: jax.random.fold_in(k_rounds, i))(jnp.arange(n))


def _epoch_cap(local_epochs):
    """randint maxval for the FSR partial-epoch draw; trace-safe (the
    sweep batches ``local_epochs`` as data, so it may be a tracer)."""
    if isinstance(local_epochs, (int, np.integer)):
        return max(int(local_epochs), 1)
    return jnp.maximum(local_epochs, 1)


@jax.named_scope("h2fed.draws")
def round_draws(key, conn: ConnState, het: HeterogeneityModel,
                hp: H2FedParams, n_agents: int, spe: int):
    """One local round's stochastic realization, shared by every engine.

    Returns (conn', mask (A,) bool, active_steps (A,) int): the CSR/SCD
    connectivity draw and the FSR-drawn completed-epoch step counts
    (0 epochs == disconnected).
    """
    k_conn, k_fsr = jax.random.split(key)
    conn, connected = step_connectivity(k_conn, conn, het)
    full = jax.random.bernoulli(k_fsr, het.fsr, (n_agents,))
    epochs = jnp.where(full, hp.local_epochs,
                       jax.random.randint(jax.random.fold_in(k_fsr, 1),
                                          (n_agents,), 0,
                                          _epoch_cap(hp.local_epochs)))
    active_steps = epochs * spe
    mask = connected & (active_steps > 0)
    return conn, mask, active_steps


def _local_train(loss_fn: Callable, x, y, w0: PyTree, w_rsu: PyTree,
                 w_cloud: PyTree, hp: H2FedParams, n_steps: int,
                 active_steps: jax.Array, batch: int) -> PyTree:
    """One agent: ``active_steps`` proximal-SGD minibatch steps from w0.

    n_steps is the static bound (E_max · steps-per-epoch); active_steps the
    FSR-drawn dynamic count — steps beyond it are masked to identity.
    """

    def objective(w, xb, yb):
        return loss_fn(w, xb, yb)

    grad_fn = jax.grad(objective)

    def body(w, step):
        xb, yb = agent_minibatch(x, y, step, batch)
        g = grad_fn(w, xb, yb)
        live = (step < active_steps).astype(jnp.float32)

        def upd(wl, gl, a1, a2):
            step_v = gl + hp.mu1 * (wl - a1) + hp.mu2 * (wl - a2)
            return wl - hp.lr * live * step_v

        return jax.tree.map(upd, w, g, w_rsu, w_cloud), None

    w, _ = jax.lax.scan(body, w0, jnp.arange(n_steps))
    return w


@jax.named_scope("h2fed.local_train")
def _local_train_flat(loss_fn: Callable, spec: flatten.FlatSpec, x, y,
                      w0: jax.Array, w_rsu: jax.Array, w_cloud: jax.Array,
                      hp: H2FedParams, n_steps: int,
                      active_steps: jax.Array, batch: int) -> jax.Array:
    """``_local_train`` on one agent's flat (N,) row: the rows are
    unraveled once at entry, the minibatch scan carries the model's
    leaves, and the result is raveled once at exit.

    The fleet buffers stay flat for aggregation, but a flat scan carry
    made every step unravel the weights and ravel the gradient back: on a
    TPU those relayout copies cost more than the products they fed
    (DESIGN.md §3).

    Compute is always fp32: storage-dtype (bf16) inputs are widened at
    entry (a no-op under the fp32 default) and the carry holds fp32
    leaves, so training precision is independent of the fleet-buffer
    storage dtype; ``loss_fn`` sees each leaf in the template's dtype, as
    ``spec.unravel`` gives it.  The caller casts the returned fp32 vector
    back into storage when writing the buffer."""
    f32 = dataclasses.replace(spec, dtypes=(jnp.float32,) * len(spec.dtypes))
    dtypes = jax.tree_util.tree_unflatten(spec.treedef, spec.dtypes)

    def template_loss(w, xb, yb):
        return loss_fn(jax.tree.map(lambda l, d: l.astype(d), w, dtypes),
                       xb, yb)

    w0 = w0.astype(jnp.float32)
    trained = _local_train(template_loss, x, y, f32.unravel(w0),
                           f32.unravel(w_rsu.astype(jnp.float32)),
                           f32.unravel(w_cloud.astype(jnp.float32)),
                           hp, n_steps, active_steps, batch)
    # columns past spec.n pad the parameter axis of N-sharded and N-tiled
    # rows: no leaf reads them, so they pass through unchanged
    return jnp.concatenate([spec.ravel(trained), w0[spec.n:]])


def _fed_arrays(cfg: SimConfig, hp: H2FedParams, fed: FederatedData, *,
                epochs_bound: Optional[int] = None):
    x_all = jnp.asarray(fed.x)
    y_all = jnp.asarray(fed.y)
    n_per_agent = jnp.asarray(fed.n_per_agent, jnp.float32)
    rsu_assign = jnp.asarray(fed.rsu_assign)
    spe = max(int(fed.x.shape[1]) // cfg.batch, 1)       # steps per epoch
    # static bound on minibatch steps: when the sweep batches local_epochs
    # as data, the group-wide maximum (epochs_bound) sizes the scan and
    # ``active_steps`` masks the tail (DESIGN.md §7)
    epochs = hp.local_epochs if epochs_bound is None else epochs_bound
    n_steps = epochs * spe
    return x_all, y_all, n_per_agent, rsu_assign, spe, n_steps


def _make_flat_round_body(cfg: SimConfig, hp: H2FedParams,
                          het: HeterogeneityModel, fed: FederatedData,
                          spec: flatten.FlatSpec,
                          loss_fn: Callable = mlp.loss_fn, *,
                          fused: bool = True,
                          cadence: Optional[Cadence] = None,
                          faults: Optional[faults_mod.FaultPlan] = None):
    """The flat-buffer global round body: FlatSimState -> FlatSimState
    (un-jitted — callers compose and jit it).

    ``fused=True`` (default) runs the ONE-PASS round: both aggregation
    layers go through the fused aggregate-and-blend entry points
    (``ops.agg_blend`` / ``ops.cloud_blend``), so each (R, N) tile is read
    once and written once — no fresh numerator re-read by a separate
    mass-guard pass.  ``fused=False`` keeps the two-step program
    (aggregation matmul, then the blend) for A/B benchmarking; off-TPU
    both lower to the same XLA ops and are fp32 bit-compatible.  Fleet
    buffers live in ``spec.storage_dtype``; the cloud stays fp32.

    ``cadence`` (sweep-only) pads the LAR/minibatch scans to the group-wide
    static bounds so ``hp.lar``/``hp.local_epochs`` may be traced scalars:
    a per-iteration ``live`` mask gates the scan carry and zeroes the
    per-round masses, so padded iterations are exact no-ops and the padded
    program reproduces the static one bit-for-bit on live iterations.

    ``faults`` (a ``core.faults.FaultPlan``) switches to the fault-gated
    program ``(state, fault_r) -> (state, metrics)``: ``fault_r`` is a
    per-round dict of lowered (lar, A)/(lar, R) mask DATA
    (``FaultSchedule.round_slice``) — churn folds into the connectivity
    mask, RSU outages zero upload weights, corrupted payloads are
    injected post-training and screened by ``screen_updates`` (scrubbed
    + weight-masked, so cohort-mass accounting stays conserved), and
    ``metrics["quarantined"]`` counts rejected weighted rows.  Only the
    plan's guard flags shape the program; the benign lowering is
    bitwise identical to the fault-free body (anchor-pinned).
    """
    x_all, y_all, n_per_agent, rsu_assign, spe, n_steps = _fed_arrays(
        cfg, hp, fed,
        epochs_bound=None if cadence is None else cadence.local_epochs)
    lar_bound = hp.lar if cadence is None else cadence.lar

    train_agents = jax.vmap(
        lambda x, y, w0, wr, wc, act: _local_train_flat(
            loss_fn, spec, x, y, w0, wr, wc, hp, n_steps, act, cfg.batch),
        in_axes=(0, 0, 0, 0, None, 0))

    def global_round(state: FlatSimState, fault_r=None):
        rng, k_rounds = jax.random.split(state.rng)
        # Alg. 2 line 2: RSUs replace w_k with the current cloud model
        rsu_flat = jnp.broadcast_to(spec.to_storage(state.cloud_flat),
                                    (cfg.n_rsus, spec.n))
        keys = round_keys(k_rounds, lar_bound)
        live = (None if cadence is None
                else jnp.arange(lar_bound) < hp.lar)     # (lar_bound,)

        def local_round(carry, inp):
            key = inp if (cadence is None and faults is None) else inp[0]
            f = inp[-1] if faults is not None else None
            rsu_prev, conn_prev, agent_prev = carry
            conn, mask, active_steps = round_draws(
                key, conn_prev, het, hp, cfg.n_agents, spe)
            if faults is not None:
                # churned agents are hard-disconnected this tick
                # (benign lowering: mask & True — identity)
                mask = mask & (f["agent_up"] > 0)
            maskf = mask.astype(jnp.float32)

            # Alg. 2 l.5 / Alg. 1 l.1: every agent starts from its RSU row
            with jax.named_scope("h2fed.local_train"):
                w_start = jnp.take(rsu_prev, rsu_assign, axis=0)  # (A, N)
                agent_flat = spec.to_storage(
                    train_agents(x_all, y_all, w_start, w_start,
                                 state.cloud_flat, active_steps))

            nq = None
            if faults is not None:
                # corrupted submissions (NaN/Inf, byzantine scale, stale
                # replay) enter here, then the quarantine gate scrubs +
                # weight-masks them; uploads to a dark RSU are dropped
                agent_flat = faults_mod.apply_corruption(
                    agent_flat, agent_prev, f)
                up_a = jnp.take(f["rsu_up"], rsu_assign)         # (A,)
                w_pre = n_per_agent * maskf * up_a
                agent_flat, okf, nq = screen_updates(
                    agent_flat, w_start, w_pre,
                    nonfinite=faults.guard_nonfinite,
                    norm_clip=faults.norm_clip)
                maskf = maskf * up_a * okf

            # Alg. 2 line 8: one (R, A) @ (A, N) pass over the fleet
            if fused:
                rsu_flat, mass = ops.agg_blend(
                    agent_flat, n_per_agent, maskf,
                    rsu_assign, cfg.n_rsus, rsu_prev)
            else:
                new_rsu, mass = ops.masked_hier_agg(
                    agent_flat, n_per_agent, maskf,
                    rsu_assign, cfg.n_rsus)
                rsu_flat = jnp.where((mass > 0)[:, None], new_rsu,
                                     rsu_prev).astype(rsu_prev.dtype)
            if cadence is not None:
                # padded LAR iterations are exact no-ops: carry passes
                # through untouched and the round contributes zero mass
                live_i = inp[1]
                rsu_flat, conn, agent_flat = jax.tree.map(
                    lambda n, o: jnp.where(live_i, n, o),
                    (rsu_flat, conn, agent_flat),
                    (rsu_prev, conn_prev, agent_prev))
                mass = jnp.where(live_i, mass, 0.0)
                if nq is not None:
                    nq = jnp.where(live_i, nq, 0)
            out = mass if faults is None else (mass, nq)
            return (rsu_flat, conn, agent_flat), out

        if faults is None:
            xs = keys if cadence is None else (keys, live)
        else:
            xs = ((keys, fault_r) if cadence is None
                  else (keys, live, fault_r))
        (rsu_flat, conn, agent_flat), out = jax.lax.scan(
            local_round, (rsu_flat, state.conn, state.agent_flat), xs)
        masses = out if faults is None else out[0]

        # Alg. 3 line 6: cloud aggregation — the (1, R) @ (R, N) matmul
        total_mass = jnp.sum(masses, axis=0)                     # (R,)
        if fused:
            cloud_flat = ops.cloud_blend(rsu_flat, total_mass,
                                         state.cloud_flat)
        else:
            new_cloud = ops.cloud_agg(rsu_flat, total_mass)
            cloud_flat = jnp.where(jnp.sum(total_mass) > 0,
                                   new_cloud.astype(jnp.float32),
                                   state.cloud_flat)
        new_state = FlatSimState(agent_flat=agent_flat, rsu_flat=rsu_flat,
                                 cloud_flat=cloud_flat, conn=conn, rng=rng)
        if faults is None:
            return new_state
        return new_state, {"quarantined": jnp.sum(out[1])}

    return global_round


def make_flat_global_round(cfg: SimConfig, hp: H2FedParams,
                           het: HeterogeneityModel, fed: FederatedData,
                           spec: flatten.FlatSpec,
                           loss_fn: Callable = mlp.loss_fn, *,
                           fused: bool = True, faults=None):
    """The flat-buffer global round: FlatSimState -> FlatSimState, jitted.

    The input state's buffers are DONATED: the (A, N)/(R, N)/(N,) update is
    in-place at scale (no copy of the fleet per round; verified via the
    dry-run HLO alias analysis, launch/hlo_analysis.donated_params).
    Callers must rebind — ``state = round_fn(state)`` — and never touch the
    consumed input again.  ``fused=False`` keeps the two-pass aggregation
    program for A/B benchmarking (benchmarks/async_round, topology_round).
    With ``faults`` the round is ``(state, fault_r) -> (state, metrics)``
    (see ``_make_flat_round_body``).
    """
    return jax.jit(_make_flat_round_body(cfg, hp, het, fed, spec, loss_fn,
                                         fused=fused, faults=faults),
                   donate_argnums=(0,))


def _make_tree_global_round(cfg: SimConfig, hp: H2FedParams,
                            het: HeterogeneityModel, fed: FederatedData,
                            loss_fn: Callable):
    """The per-leaf tree-map reference round (the original engine)."""
    x_all, y_all, n_per_agent, rsu_assign, spe, n_steps = \
        _fed_arrays(cfg, hp, fed)

    train_agents = jax.vmap(
        lambda x, y, w0, wr, wc, act: _local_train(
            loss_fn, x, y, w0, wr, wc, hp, n_steps, act, cfg.batch),
        in_axes=(0, 0, 0, 0, None, 0))

    def local_round(carry, key):
        rsu_params, conn, cloud_params = carry
        conn, mask, active_steps = round_draws(
            key, conn, het, hp, cfg.n_agents, spe)

        # Alg. 2 line 5 / Alg. 1 line 1: every agent starts from its RSU model
        w_start = gather_rsu_for_agents(rsu_params, rsu_assign)
        agent_params = train_agents(x_all, y_all, w_start, w_start,
                                    cloud_params, active_steps)

        # Alg. 2 line 8: masked weighted per-RSU aggregation
        new_rsu, mass = rsu_aggregate(agent_params, n_per_agent,
                                      mask.astype(jnp.float32), rsu_assign,
                                      cfg.n_rsus)
        rsu_params = blend_on_mass(new_rsu, rsu_params, mass)
        return (rsu_params, conn, cloud_params), (mass, agent_params)

    def global_round(state: SimState) -> SimState:
        rng, k_rounds = jax.random.split(state.rng)
        # Alg. 2 line 2: RSUs replace w_k with the current cloud model
        rsu_params = broadcast_to_agents(state.cloud_params, cfg.n_rsus)
        keys = round_keys(k_rounds, hp.lar)
        (rsu_params, conn, _), (masses, agent_params) = jax.lax.scan(
            local_round, (rsu_params, state.conn, state.cloud_params), keys)
        # Alg. 3 line 6: cloud aggregation, weighted by surviving data mass
        total_mass = jnp.sum(masses, axis=0)              # (R,)
        new_cloud = masked_weighted_mean(rsu_params, total_mass)
        cloud_params = jax.tree.map(
            lambda n, o: jnp.where(jnp.sum(total_mass) > 0, n, o),
            new_cloud, state.cloud_params)
        last_agents = jax.tree.map(lambda l: l[-1], agent_params)
        return SimState(agent_params=last_agents, rsu_params=rsu_params,
                        cloud_params=cloud_params, conn=conn, rng=rng)

    return jax.jit(global_round)


def make_global_round(cfg: SimConfig, hp: H2FedParams,
                      het: HeterogeneityModel, fed: FederatedData,
                      loss_fn: Callable = mlp.loss_fn, *,
                      engine: str = "flat"):
    """Build the jitted SimState -> SimState global round.

    engine="flat" runs the Pallas flat-buffer path (ravel on entry, unravel
    on exit — the standalone ``make_flat_global_round`` avoids even that);
    engine="tree" is the per-leaf reference.
    """
    if engine == "tree":
        return _make_tree_global_round(cfg, hp, het, fed, loss_fn)
    if engine != "flat":
        raise ValueError(f"unknown engine {engine!r} (want 'flat'|'tree')")

    body_cache: Dict[flatten.FlatSpec, Callable] = {}

    @jax.jit
    def global_round(state: SimState) -> SimState:
        # one compiled program: ravel -> flat round -> unravel all fuse, so
        # per-round loops (benchmarks, tests) pay no eager conversion cost.
        # spec_of reads only static metadata, so it works on tracers and
        # the cache is keyed per parameter structure.
        spec = flatten.spec_of(state.cloud_params)
        if spec not in body_cache:
            body_cache[spec] = _make_flat_round_body(
                cfg, hp, het, fed, spec, loss_fn)
        out = body_cache[spec](to_flat_state(spec, state))
        return from_flat_state(spec, out)

    return global_round


def run_simulation(cfg: SimConfig, hp: H2FedParams, het: HeterogeneityModel,
                   fed: FederatedData, init_params: PyTree,
                   n_rounds: int, *, x_test=None, y_test=None,
                   loss_fn: Callable = mlp.loss_fn,
                   eval_fn: Optional[Callable] = None,
                   engine: str = "flat",
                   async_cfg=None,
                   fleet_dtype=None,
                   fused: bool = True,
                   ) -> Tuple[SimState, Dict[str, np.ndarray]]:
    """DEPRECATED: use ``fedsim.run_scenario`` with a ``ScenarioSpec`` —
    the one engine entry point with the shared knob surface (``engine``,
    ``fleet_dtype``, ``fused``, ``fleet_store``; DESIGN.md §8).

    This wrapper builds an ad-hoc scenario around the pre-built arrays and
    delegates; numerics are unchanged (same seed/key discipline,
    equivalence test-pinned in tests/test_api.py).
    """
    if engine not in ("flat", "tree", "async"):
        raise ValueError(
            f"unknown engine {engine!r} (want 'flat'|'tree'|'async')")
    warnings.warn(
        "run_simulation is deprecated; use fedsim.run_scenario with a "
        "ScenarioSpec (engine/fleet knobs are spec fields)",
        DeprecationWarning, stacklevel=2)
    from repro.fedsim import sweep
    res = sweep.adhoc_scenario(
        cfg, hp, het, fed, n_rounds=n_rounds, engine=engine,
        fleet_dtype=fleet_dtype, fused=fused, async_cfg=async_cfg,
        x_test=x_test, y_test=y_test)
    return sweep.run_scenario(res, init_params, loss_fn=loss_fn,
                              eval_fn=eval_fn)


_unravel = jax.jit(flatten.FlatSpec.unravel, static_argnums=0)


def _run_sync(res, init_params: PyTree, *,
              loss_fn: Callable = mlp.loss_fn,
              eval_fn: Optional[Callable] = None,
              ) -> Tuple[SimState, Dict[str, np.ndarray]]:
    """``run_scenario``'s flat/tree dispatch target: run the scenario's
    rounds with the fleet resident in (A, N)/(R, N)/(N,) device buffers
    (pytrees materialize only for eval and the returned final state)."""
    s = res.spec
    cfg, hp, het, fed = res.cfg, s.hp, s.het, res.fed
    engine, fleet_dtype, fused, n_rounds = (s.engine, s.fleet_dtype,
                                            s.fused, s.rounds)
    x_test = res.test.x if res.test is not None else None
    y_test = res.test.y if res.test is not None else None
    hp.validate(), het.validate()
    key = jax.random.key(cfg.seed)
    if eval_fn is None and x_test is not None:
        x_test, y_test = jnp.asarray(x_test), jnp.asarray(y_test)
        eval_fn = jax.jit(lambda p: mlp.accuracy(p, x_test, y_test))

    with jax.profiler.TraceAnnotation("h2fed.build"):
        if engine == "flat":
            spec = flatten.spec_of(
                init_params,
                storage_dtype=flatten.resolve_storage_dtype(fleet_dtype))
            state = init_flat_state(cfg, spec, init_params, key)
            round_fn = make_flat_global_round(cfg, hp, het, fed, spec,
                                              loss_fn, fused=fused,
                                              faults=s.faults)
            # eval_fn is called eagerly so user-supplied non-traceable
            # metrics keep working; the built-in accuracy eval_fn above is
            # already jitted.  The unravel before it is one jitted call:
            # eager, its per-leaf slices cost ~2.5 ms of host a round on a
            # TPU v5e host, as long as the paper cell's device round.
            eval_state = (None if eval_fn is None else
                          (lambda s: eval_fn(_unravel(spec, s.cloud_flat))))
            finalize = lambda s: from_flat_state(spec, s)    # noqa: E731
        elif engine == "tree":
            state = init_state(cfg, init_params, key)
            round_fn = _make_tree_global_round(cfg, hp, het, fed, loss_fn)
            eval_state = (None if eval_fn is None else
                          (lambda s: eval_fn(s.cloud_params)))
            finalize = lambda s: s                           # noqa: E731
        else:
            raise ValueError(
                f"unknown engine {engine!r} (want 'flat'|'tree'|'async')")

    # fault schedules lower once per run to per-tick mask data over the
    # global tick clock (rounds x lar); each round consumes its slice
    sched = None
    if s.faults is not None and engine == "flat":
        sched = s.faults.lower(cfg.n_agents, cfg.n_rsus, n_rounds * hp.lar)

    # host spans on the profiler's clock: each round's dispatch (the
    # first carries the round's trace, lowering and cache load) and its
    # eval with the readback, both tagged with the round index
    accs, rounds, quarantined = [], [], []
    for r in range(n_rounds):
        with jax.profiler.TraceAnnotation("h2fed.round", round=r):
            if sched is None:
                state = round_fn(state)
            else:
                state, fm = round_fn(state, sched.round_slice(r, hp.lar))
                quarantined.append(int(fm["quarantined"]))
        if eval_state is not None and (r % cfg.eval_every == 0
                                       or r == n_rounds - 1):
            with jax.profiler.TraceAnnotation("h2fed.eval", round=r):
                accs.append(float(eval_state(state)))
            rounds.append(r + 1)
    history = {"round": np.asarray(rounds), "acc": np.asarray(accs)}
    if sched is not None:
        history["quarantined"] = np.asarray(quarantined)
    return finalize(state), history
