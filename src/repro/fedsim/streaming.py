"""Cohort-streamed round engines: million-agent fleets on fixed HBM
(DESIGN.md §8).

The resident engines (fedsim/simulator, async_engine) hold the whole fleet
as one device (A, N) buffer, so A is HBM-bound.  But the paper's
participation model is the opposite shape: a CSR-sized cohort of a huge
connected fleet does work each round, and ~90% of agents are
timely-disconnected.  This module makes the device-resident state the
*cohort chunk*, not the fleet:

  * agent rows live in a ``core.fleet_store.FleetStore`` — ``"host"``
    keeps the (A, N) fleet in host numpy memory in the FlatSpec storage
    dtype (fp32 | bf16), ``"device"`` keeps today's resident buffer but
    still bounds the per-step training working set to a chunk;
  * each local round streams the fleet in fixed-size agent chunks through
    ONE jitted ``chunk_step`` (compiled once — tails are zero-padded to
    the static chunk shape): gather the chunk's RSU start models, run the
    existing vmapped dual-proximal training scan, and reduce the chunk's
    arrivals with the chunk-shaped aggregation entry
    (``kernels/ops.chunk_agg``).  The (R, N)/(R,) numerator + mass
    accumulators are DONATED through the chunk loop, so the device
    working set per step is O(chunk·N + R·N), independent of A;
  * transfers are double-buffered: the next chunk's ``jax.device_put`` is
    dispatched BEFORE the current chunk's compute (jax dispatch is async,
    so the h2d copy overlaps the training scan), and the store writeback
    of chunk c-1 is deferred until after chunk c's step is dispatched, so
    the blocking d2h read also overlaps compute;
  * the aggregation ALGEBRA is unchanged: accumulated chunk partial sums
    + one ``normalize_blend`` per local round is exactly the partial-sum
    formulation the sharded engines psum (fedsim/sharded), which is
    test-pinned fp32-equivalent to the resident fused ``agg_blend`` path;
    the semi-async tick absorbs the accumulated arrivals with the same
    ``buffer_absorb`` merge the resident ``agg_absorb`` tick runs.

Both engines stream: ``make_streamed_flat_round`` (the synchronous LAR
round) and ``make_streamed_async_round`` (the semi-async tick loop, with
the in-flight pending rows in a second FleetStore and only the (A,)-sized
bookkeeping vectors device-resident).  Equivalence is test-pinned at
small A: streamed == resident to fp32 tolerance for both engines
(tests/test_streaming.py).

Entry points: ``fedsim.run_scenario`` dispatches here whenever the spec
sets ``fleet_store="host"`` or ``chunk_agents > 0``;
``run_streamed_simulation`` is the direct-call twin of
``run_simulation`` for callers with their own arrays (benchmarks).

Fault injection (DESIGN.md §11): streamed rounds accept a lowered
``FaultSchedule`` round slice.  Churn and RSU outages are *weight data* —
folded into the per-tick aggregation weights host-side (``agent_up`` and
the agent's RSU ``rsu_up`` multiply the draw weights), so dark agents/RSUs
contribute zero mass without touching the compiled chunk program — plus a
per-tick recovery re-anchor and an outage-masked cloud blend in the async
round.  The non-finite quarantine guard runs inside ``chunk_step`` (gated
by plan presence, like the resident engines).  Corrupted-update injection
is NOT supported here (``ScenarioSpec.validate`` rejects it): the streamed
store writebacks are row-masked host ops and cannot stage per-tick payload
corruption without materializing the fleet.  The benign schedule is a
bitwise no-op (``w * 1.0`` folds), pinned by the zero-fault anchor.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flatten
from repro.core.aggregation import buffer_absorb, normalize_blend
from repro.core.fleet_store import (HostFleetStore, make_fleet_store,
                                    resolve_fleet_store)
from repro.core.h2fed import H2FedParams
from repro.core.heterogeneity import (ConnState, HeterogeneityModel,
                                      init_conn_state, sample_latency)
from repro.data.partition import FederatedData
from repro.kernels import ops
from repro.models import mlp
from repro.fedsim.async_engine import _LATENCY_FOLD, AsyncConfig
from repro.fedsim.simulator import (SimConfig, _local_train_flat,
                                    round_draws, round_keys)

PyTree = Any

# auto chunk size when the spec leaves chunk_agents=0: big enough to feed
# the vmapped training scan, small enough that (chunk, N) stays a sliver
# of any fleet worth streaming
DEFAULT_CHUNK = 1024


class ChunkPlan(NamedTuple):
    """Static chunking of the agent axis: ``n_chunks`` chunks of ``chunk``
    rows; the last chunk carries ``pad`` zero rows (weight 0, 0 training
    steps) so every chunk shares ONE compiled chunk_step."""
    chunk: int
    n_chunks: int
    n_agents: int
    pad: int

    @property
    def n_padded(self) -> int:
        return self.n_chunks * self.chunk

    def bounds(self, c: int) -> Tuple[int, int]:
        """(row offset, valid rows) of chunk ``c``."""
        lo = c * self.chunk
        return lo, min(lo + self.chunk, self.n_agents) - lo


def make_chunk_plan(n_agents: int, chunk_agents: int = 0) -> ChunkPlan:
    chunk = chunk_agents if chunk_agents > 0 else DEFAULT_CHUNK
    chunk = max(1, min(chunk, n_agents))
    n_chunks = -(-n_agents // chunk)
    return ChunkPlan(chunk=chunk, n_chunks=n_chunks, n_agents=n_agents,
                     pad=n_chunks * chunk - n_agents)


class NTilePlan(NamedTuple):
    """Static tiling of the PARAMETER axis (DESIGN.md §12): ``n_tiles``
    lane-aligned tiles of ``tile`` columns; the buffers are zero-padded by
    ``pad`` trailing columns so every tile shares one compiled program
    (zero tails are algebra-neutral, exactly like the agent-axis pad)."""
    tile: int
    n_tiles: int
    n: int
    pad: int

    @property
    def n_padded(self) -> int:
        return self.n_tiles * self.tile

    def bounds(self, t: int) -> Tuple[int, int]:
        """(col_lo, col_hi) of tile ``t`` on the padded grid."""
        lo = t * self.tile
        return lo, lo + self.tile


def make_ntile_plan(n: int, chunk_params: int = 0) -> NTilePlan:
    """Tile N into ~``chunk_params``-column lane-aligned tiles
    (``chunk_params=0`` = one tile: the agent-axis-only streamed shape)."""
    from repro.kernels.masked_hier_agg import LANE
    tile = chunk_params if chunk_params > 0 else n
    tile = max(LANE, min(tile, n))
    tile = -(-tile // LANE) * LANE
    n_tiles = max(-(-n // tile), 1)
    return NTilePlan(tile=tile, n_tiles=n_tiles, n=n,
                     pad=n_tiles * tile - n)


def _data_chunks(fed: FederatedData, plan: ChunkPlan):
    """Host-side per-chunk (x, y, rsu_assign) tuples — views into the
    FederatedData arrays (zero-copy; broadcast fleets stay virtual) except
    the zero-padded tail chunk."""
    xs, ys = np.asarray(fed.x), np.asarray(fed.y)
    asg = np.asarray(fed.rsu_assign, np.int32)
    out = []
    for c in range(plan.n_chunks):
        lo, valid = plan.bounds(c)
        x, y, a = xs[lo:lo + valid], ys[lo:lo + valid], asg[lo:lo + valid]
        if valid < plan.chunk:
            p = plan.chunk - valid
            x = np.concatenate([x, np.zeros((p,) + x.shape[1:], x.dtype)])
            y = np.concatenate([y, np.zeros((p,) + y.shape[1:], y.dtype)])
            a = np.concatenate([a, np.zeros((p,), a.dtype)])
        out.append((x, y, a))
    return out


def _pad_tail(rows, chunk: int):
    """Zero-pad a gathered tail chunk of fleet rows to the static shape."""
    valid = rows.shape[0]
    if valid == chunk:
        return rows
    if isinstance(rows, np.ndarray):
        return np.concatenate(
            [rows, np.zeros((chunk - valid, rows.shape[1]), rows.dtype)])
    return jnp.pad(rows, ((0, chunk - valid), (0, 0)))


def streamed_transfer_bytes(plan: ChunkPlan, spec: flatten.FlatSpec,
                            hp: H2FedParams, fed: FederatedData, *,
                            engine: str = "flat",
                            fleet_store: str = "host") -> Dict[str, float]:
    """Analytic host↔device bytes per GLOBAL round of the streamed
    pipeline (the bench-flow / BENCH_PR6 accounting).  The device store
    pays no host traffic (gather/scatter are device slices); the host
    store pays per local round: data chunks up (x, y, assign), trained
    rows down, and — semi-async only — pending rows up plus enqueued rows
    down (counted as an upper bound: every agent could enqueue)."""
    if resolve_fleet_store(fleet_store) == "device":
        return {"h2d": 0.0, "d2h": 0.0, "total": 0.0}
    x, y = np.asarray(fed.x[:1]), np.asarray(fed.y[:1])
    per_agent_data = (x.dtype.itemsize * x[0].size
                     + y.dtype.itemsize * y[0].size + 4)      # + int32 assign
    rows = plan.n_padded * spec.n * jnp.dtype(spec.storage_dtype).itemsize
    h2d = hp.lar * plan.n_padded * per_agent_data
    d2h = hp.lar * rows
    if engine == "async":
        h2d += hp.lar * rows                                  # pending gather
        d2h += hp.lar * rows                                  # enqueue upper bound
    return {"h2d": float(h2d), "d2h": float(d2h), "total": float(h2d + d2h)}


# --------------------------------------------------------------------------
# synchronous (flat) streamed round
# --------------------------------------------------------------------------

class StreamSimState(NamedTuple):
    """Streamed-round state.  ``store`` is a host-side FleetStore object
    (never traced); only the RSU/cloud buffers and the (A,)-sized
    bookkeeping live on device."""
    store: Any              # FleetStore — (A, N) agent rows
    rsu_flat: jax.Array     # (R, N) storage dtype
    cloud_flat: jax.Array   # (N,)   fp32 master
    conn: ConnState
    rng: jax.Array


def init_stream_state(cfg: SimConfig, spec: flatten.FlatSpec,
                      init_params: PyTree, key, *,
                      fleet_store: str = "host") -> StreamSimState:
    vec = spec.ravel(init_params)
    return StreamSimState(
        store=make_fleet_store(fleet_store, vec, cfg.n_agents,
                               spec.storage_dtype),
        rsu_flat=jnp.broadcast_to(spec.to_storage(vec),
                                  (cfg.n_rsus, spec.n)),
        cloud_flat=vec,
        conn=init_conn_state(cfg.n_agents),
        rng=key)


def _fault_weight_fold(fault_r, rsu_assign_np, pad: int):
    """Host-side (lar, A_pad) weight multiplier from one round's fault
    slice: churned agents and agents behind a dark RSU contribute zero
    mass.  Benign schedules fold to all-ones (``w * 1.0`` is exact)."""
    up_a = fault_r["rsu_up"][:, rsu_assign_np]           # (lar, A)
    fold = fault_r["agent_up"] * up_a
    if pad:
        fold = np.pad(fold, ((0, 0), (0, pad)), constant_values=1.0)
    return jnp.asarray(fold, jnp.float32)


def _make_flat_draws_fn(cfg: SimConfig, hp: H2FedParams,
                        het: HeterogeneityModel, plan: ChunkPlan,
                        n_per_agent, spe: int):
    """One global round's stochastic realization, padded to the chunk
    grid: (conn', rng', weights (LAR, A_pad), steps (LAR, A_pad)) — the
    flat-engine key discipline shared by the one- and two-axis streamed
    rounds (they must draw identically to be equivalent)."""
    A = cfg.n_agents

    @jax.jit
    def draws_fn(conn, rng):
        rng, k_rounds = jax.random.split(rng)
        keys = round_keys(k_rounds, hp.lar)

        def draw(conn, key):
            conn, mask, act = round_draws(key, conn, het, hp, A, spe)
            return conn, (n_per_agent * mask.astype(jnp.float32), act)

        conn, (weights, steps) = jax.lax.scan(draw, conn, keys)
        if plan.pad:
            weights = jnp.pad(weights, ((0, 0), (0, plan.pad)))
            steps = jnp.pad(steps, ((0, 0), (0, plan.pad)))
        return conn, rng, weights, steps

    return draws_fn


def make_streamed_flat_round(cfg: SimConfig, hp: H2FedParams,
                             het: HeterogeneityModel, fed: FederatedData,
                             spec: flatten.FlatSpec,
                             loss_fn: Callable = mlp.loss_fn, *,
                             chunk_agents: int = 0, faults=None):
    """Build the streamed synchronous global round:
    StreamSimState -> StreamSimState.

    Same draws / key discipline as ``engine="flat"`` (the per-round scan
    of ``round_draws`` — drawn up-front exactly like the sharded engine);
    the LAR body streams the fleet chunk-by-chunk through one jitted,
    accumulator-donating ``chunk_step`` and closes each local round with
    ``normalize_blend``.  In the sync round agent rows are WRITE-only
    (training starts from RSU rows), so the store is never gathered —
    only the trained rows flow back.

    With ``faults`` (a ``FaultPlan``), ``global_round(state, fault_r)``
    takes one ``FaultSchedule.round_slice``: churn/outage fold into the
    draw weights host-side (see ``_fault_weight_fold``) and the
    non-finite guard screens each chunk inside ``chunk_step``; the round
    then also returns a ``{"quarantined": ...}`` metrics dict.
    """
    A, R, N = cfg.n_agents, cfg.n_rsus, spec.n
    spe = max(int(fed.x.shape[1]) // cfg.batch, 1)
    n_steps = hp.local_epochs * spe
    plan = make_chunk_plan(A, chunk_agents)
    chunks = _data_chunks(fed, plan)
    n_per_agent = jnp.asarray(np.asarray(fed.n_per_agent), jnp.float32)
    rsu_assign_np = np.asarray(fed.rsu_assign, np.int32)
    guard = faults is not None and faults.guard_nonfinite

    train_agents = jax.vmap(
        lambda x, y, w0, wr, wc, act: _local_train_flat(
            loss_fn, spec, x, y, w0, wr, wc, hp, n_steps, act, cfg.batch),
        in_axes=(0, 0, 0, 0, None, 0))

    draws_fn = _make_flat_draws_fn(cfg, hp, het, plan, n_per_agent, spe)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def chunk_step(num_acc, mass_acc, rsu_flat, cloud_flat, x_c, y_c,
                   assign_c, w_c, act_c):
        # Alg. 2 l.5 / Alg. 1 l.1: the chunk's agents start from their RSU
        # row; Alg. 2 l.8 becomes a chunk-shaped partial sum.
        w_start = jnp.take(rsu_flat, assign_c, axis=0)     # (chunk, N)
        stored = spec.to_storage(
            train_agents(x_c, y_c, w_start, w_start, cloud_flat, act_c))
        nq = jnp.zeros((), jnp.int32)
        if guard:
            # quarantine gate, chunk-shaped: non-finite rows are scrubbed
            # back to their RSU start and zero-weighted (benign data all
            # finite -> ok all-True, a bitwise no-op)
            ok = jnp.all(jnp.isfinite(stored.astype(jnp.float32)), axis=1)
            stored = jnp.where(ok[:, None], stored, w_start)
            nq = jnp.sum(((w_c > 0) & ~ok).astype(jnp.int32))
            w_c = w_c * ok.astype(jnp.float32)
        num, mass = ops.chunk_agg(stored, w_c, assign_c, R)
        # the barrier keeps the chunk's partial sum whole: left free, XLA
        # folds the add into the segment-sum's scatter, (acc + x1) + x2
        # instead of acc + (x1 + x2), and the round stops matching the
        # two-axis round's host-side accumulation bit for bit
        num = jax.lax.optimization_barrier(num)
        return num_acc + num, mass_acc + mass, stored, nq

    @jax.jit
    def rsu_update(num_acc, mass_acc, rsu_flat):
        return normalize_blend(num_acc, mass_acc, rsu_flat)

    @jax.jit
    def cloud_update(rsu_flat, total_mass, cloud_flat):
        return ops.cloud_blend(rsu_flat, total_mass, cloud_flat)

    def put_chunk(c: int):
        return jax.device_put(chunks[c])

    def global_round(state: StreamSimState, fault_r=None):
        store = state.store
        conn, rng, weights, steps = draws_fn(state.conn, state.rng)
        if faults is not None:
            weights = weights * _fault_weight_fold(fault_r, rsu_assign_np,
                                                   plan.pad)
        # Alg. 2 line 2: RSUs re-anchor to the cloud model
        rsu_flat = jnp.broadcast_to(spec.to_storage(state.cloud_flat),
                                    (R, N))
        total_mass = jnp.zeros((R,), jnp.float32)
        n_quar = jnp.zeros((), jnp.int32)
        for l in range(hp.lar):
            num_acc = jnp.zeros((R, N), jnp.float32)
            mass_acc = jnp.zeros((R,), jnp.float32)
            nxt, wb = put_chunk(0), None
            for c in range(plan.n_chunks):
                lo, valid = plan.bounds(c)
                cur = nxt
                if c + 1 < plan.n_chunks:
                    # double buffering: dispatch the NEXT chunk's h2d copy
                    # before the current chunk's compute is enqueued
                    nxt = put_chunk(c + 1)
                sl = slice(c * plan.chunk, (c + 1) * plan.chunk)
                num_acc, mass_acc, stored, nq = chunk_step(
                    num_acc, mass_acc, rsu_flat, state.cloud_flat, *cur,
                    weights[l, sl], steps[l, sl])
                n_quar = n_quar + nq
                if wb is not None:
                    # deferred-by-one writeback: the (blocking) d2h read of
                    # chunk c-1 overlaps chunk c's dispatched compute
                    store.scatter(*wb)
                wb = (lo, stored if valid == plan.chunk else stored[:valid])
            if wb is not None:
                store.scatter(*wb)
            rsu_flat = rsu_update(num_acc, mass_acc, rsu_flat)
            total_mass = total_mass + mass_acc
        # Alg. 3 line 6: cloud aggregation over the surviving mass
        cloud_flat = cloud_update(rsu_flat, total_mass, state.cloud_flat)
        out = StreamSimState(store=store, rsu_flat=rsu_flat,
                             cloud_flat=cloud_flat, conn=conn, rng=rng)
        if faults is not None:
            return out, {"quarantined": n_quar}
        return out

    global_round.plan = plan
    global_round.chunk_step = chunk_step
    return global_round


# --------------------------------------------------------------------------
# semi-asynchronous streamed round
# --------------------------------------------------------------------------

class AsyncStreamState(NamedTuple):
    """Streamed semi-async state: the two (A, N) row sets (latest local
    models + in-flight pending updates) live in FleetStores; only the
    (A,)-sized in-flight bookkeeping stays device-resident."""
    store: Any              # FleetStore — (A, N) latest local model rows
    pending_store: Any      # FleetStore — (A, N) in-flight update rows
    rsu_flat: jax.Array     # (R, N) storage dtype
    rsu_mass: jax.Array     # (R,)   running absorbed cohort mass
    cloud_flat: jax.Array   # (N,)   fp32 master
    pending_w: jax.Array    # (A,)   decayed delivery weight
    pending_t: jax.Array    # (A,)   ticks until delivery (0 = none)
    conn: ConnState
    rng: jax.Array
    cloud_macc: jax.Array   # (R,)   mass since last cloud aggregation
    tick: int               # python global tick clock (cloud cadence)


def init_async_stream_state(cfg: SimConfig, spec: flatten.FlatSpec,
                            init_params: PyTree, key, *,
                            fleet_store: str = "host") -> AsyncStreamState:
    vec = spec.ravel(init_params)
    a = cfg.n_agents
    kind = resolve_fleet_store(fleet_store)
    if kind == "host":
        pending = HostFleetStore.zeros(a, spec.n, spec.storage_dtype)
    else:
        from repro.core.fleet_store import DeviceFleetStore
        pending = DeviceFleetStore(jnp.zeros((a, spec.n),
                                             spec.storage_dtype))
    return AsyncStreamState(
        store=make_fleet_store(kind, vec, a, spec.storage_dtype),
        pending_store=pending,
        rsu_flat=jnp.broadcast_to(spec.to_storage(vec),
                                  (cfg.n_rsus, spec.n)),
        rsu_mass=jnp.zeros((cfg.n_rsus,), jnp.float32),
        cloud_flat=vec,
        pending_w=jnp.zeros((a,), jnp.float32),
        pending_t=jnp.zeros((a,), jnp.int32),
        conn=init_conn_state(a),
        rng=key,
        cloud_macc=jnp.zeros((cfg.n_rsus,), jnp.float32),
        tick=0)


def make_streamed_async_round(cfg: SimConfig, hp: H2FedParams,
                              het: HeterogeneityModel, fed: FederatedData,
                              spec: flatten.FlatSpec,
                              acfg: Optional[AsyncConfig] = None,
                              loss_fn: Callable = mlp.loss_fn, *,
                              chunk_agents: int = 0, faults=None):
    """Build the streamed semi-async global round:
    AsyncStreamState -> (AsyncStreamState, metrics).

    The tick algebra is the resident engine's (fedsim/async_engine) with
    the (A, N) work chunked: the per-tick in-flight bookkeeping (busy /
    due / enqueue and their weights) runs on (A,)-sized device vectors,
    the chunk loop accumulates both arrival cohorts' numerators with
    ``ops.chunk_agg``, and the tick closes with the same
    ``buffer_absorb`` merge the fused ``agg_absorb`` tick performs.
    Row-masked store writebacks keep busy agents' rows (``where=~busy``)
    without gathering them first.  Draw/key discipline matches the
    resident engine (latency keys folded with ``_LATENCY_FOLD``), so at
    small A streamed == resident to fp32 tolerance (test-pinned).

    With ``faults``, ``global_round(state, fault_r)`` takes one
    ``FaultSchedule.round_slice``: churn folds into the connectivity
    masks (gating training, immediate uploads AND enqueues), outages
    zero both arrival cohorts' weights and mask the cloud blend, a
    recovering RSU re-anchors at its tick, and the non-finite guard
    screens both cohorts inside ``chunk_step``.
    """
    acfg = (acfg or AsyncConfig()).validate()
    A, R, N = cfg.n_agents, cfg.n_rsus, spec.n
    spe = max(int(fed.x.shape[1]) // cfg.batch, 1)
    n_steps = hp.local_epochs * spe
    plan = make_chunk_plan(A, chunk_agents)
    chunks = _data_chunks(fed, plan)
    n_per_agent = jnp.asarray(np.asarray(fed.n_per_agent), jnp.float32)
    rsu_assign = jnp.asarray(np.asarray(fed.rsu_assign), jnp.int32)
    rsu_assign_np = np.asarray(fed.rsu_assign, np.int32)
    guard = faults is not None and faults.guard_nonfinite
    decay = acfg.agent_decay(rsu_assign, R)
    keep = acfg.rsu_keep(R)
    ce = acfg.cloud_every

    train_agents = jax.vmap(
        lambda x, y, w0, wr, wc, act: _local_train_flat(
            loss_fn, spec, x, y, w0, wr, wc, hp, n_steps, act, cfg.batch),
        in_axes=(0, 0, 0, 0, None, 0))

    @jax.jit
    def draws_fn(conn, rng):
        rng, k_rounds = jax.random.split(rng)
        keys = round_keys(k_rounds, hp.lar)

        def draw(conn, key):
            conn, mask, act = round_draws(key, conn, het, hp, A, spe)
            d = sample_latency(jax.random.fold_in(key, _LATENCY_FOLD),
                               A, het)
            return conn, (mask.astype(jnp.float32), act, d)

        conn, outs = jax.lax.scan(draw, conn, keys)
        return (conn, rng) + outs                # masks/steps/delays (LAR, A)

    @jax.jit
    def tick_prep(pend_w, pend_t, maskf, act_steps, delays):
        """The (A,)-sized in-flight bookkeeping of one tick — identical
        order of operations to the resident tick (countdown, arrivals
        read the pre-enqueue pending weights, then enqueue overwrites)."""
        in_flight = pend_t > 0
        pend_t = jnp.maximum(pend_t - 1, 0)
        due = in_flight & (pend_t == 0)
        busy = in_flight & ~due
        free = ~busy
        act = jnp.where(busy, 0, act_steps)
        w_imm = (n_per_agent * maskf * free
                 * (delays == 0).astype(jnp.float32))
        w_due = jnp.where(due, pend_w, 0.0)
        enq = (maskf > 0) & free & (delays > 0)
        w_enq = n_per_agent * maskf * acfg.weight(delays, decay=decay)
        pend_w = jnp.where(enq, w_enq, pend_w)
        pend_t = jnp.where(enq, delays, pend_t)
        if plan.pad:
            pad = ((0, plan.pad),)
            act, w_imm, w_due = (jnp.pad(act, pad), jnp.pad(w_imm, pad),
                                 jnp.pad(w_due, pad))
        return act, w_imm, w_due, free, enq, pend_w, pend_t

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def chunk_step(num_acc, mass_acc, rsu_flat, cloud_flat, x_c, y_c,
                   assign_c, pend_rows, act_c, w_imm_c, w_due_c):
        w_start = jnp.take(rsu_flat, assign_c, axis=0)
        trained = spec.to_storage(
            train_agents(x_c, y_c, w_start, w_start, cloud_flat, act_c))
        nq = jnp.zeros((), jnp.int32)
        if guard:
            # quarantine gate over BOTH arrival cohorts: fresh trained
            # rows are scrubbed back to their RSU start; non-finite
            # pending deliveries are zero-weighted (their store rows
            # expire with the delivery)
            ok_t = jnp.all(jnp.isfinite(trained.astype(jnp.float32)),
                           axis=1)
            trained = jnp.where(ok_t[:, None], trained, w_start)
            ok_p = jnp.all(jnp.isfinite(pend_rows.astype(jnp.float32)),
                           axis=1)
            nq = (jnp.sum(((w_imm_c > 0) & ~ok_t).astype(jnp.int32))
                  + jnp.sum(((w_due_c > 0) & ~ok_p).astype(jnp.int32)))
            w_imm_c = w_imm_c * ok_t.astype(jnp.float32)
            w_due_c = w_due_c * ok_p.astype(jnp.float32)
        num_i, m_i = ops.chunk_agg(trained, w_imm_c, assign_c, R)
        num_d, m_d = ops.chunk_agg(pend_rows, w_due_c, assign_c, R)
        return num_acc + num_i + num_d, mass_acc + m_i + m_d, trained, nq

    @jax.jit
    def tick_finish(rsu_flat, rsu_mass, num_acc, mass_acc, cloud_macc):
        rsu_flat, rsu_mass = buffer_absorb(rsu_flat, rsu_mass, num_acc,
                                           mass_acc, keep=keep)
        return rsu_flat, rsu_mass, cloud_macc + mass_acc

    @jax.jit
    def cloud_update(rsu_flat, macc, cloud_flat):
        return ops.cloud_blend(rsu_flat, macc, cloud_flat)

    def put_chunk(c: int, pending_store):
        x, y, a = chunks[c]
        lo, valid = plan.bounds(c)
        pend = _pad_tail(pending_store.gather(lo, lo + valid), plan.chunk)
        return jax.device_put((x, y, a, pend))

    def global_round(state: AsyncStreamState, fault_r=None
                     ) -> Tuple[AsyncStreamState, Dict[str, np.ndarray]]:
        store, pending_store = state.store, state.pending_store
        conn, rng, masks, steps, delays = draws_fn(state.conn, state.rng)
        if faults is not None:
            # churn: hard-disconnect beyond the benign latency model —
            # gates immediate uploads and enqueues (due deliveries were
            # dispatched before the disconnect and still land)
            masks = masks * jnp.asarray(fault_r["agent_up"], jnp.float32)
        if ce:
            # decoupled cadence: buffers/mass/accumulator persist across
            # the round boundary (see async_engine for the rationale)
            rsu_flat, rsu_mass = state.rsu_flat, state.rsu_mass
            cloud_macc = state.cloud_macc
        else:
            rsu_flat = jnp.broadcast_to(spec.to_storage(state.cloud_flat),
                                        (R, N))
            rsu_mass = jnp.zeros((R,), jnp.float32)
            cloud_macc = jnp.zeros((R,), jnp.float32)
        cloud_flat = state.cloud_flat
        pend_w, pend_t, gtick = state.pending_w, state.pending_t, state.tick
        absorbed = []

        n_quar = jnp.zeros((), jnp.int32)
        for l in range(hp.lar):
            if faults is not None:
                # recovery re-anchor: an RSU coming back from an outage
                # rejoins at the current cloud master, buffer cleared
                ra = jnp.asarray(fault_r["reanchor"][l]) > 0
                rsu_flat = jnp.where(
                    ra[:, None],
                    jnp.broadcast_to(spec.to_storage(cloud_flat), (R, N)),
                    rsu_flat)
                rsu_mass = jnp.where(ra, 0.0, rsu_mass)
                cloud_macc = jnp.where(ra, 0.0, cloud_macc)
            act, w_imm, w_due, free, enq, pend_w, pend_t = tick_prep(
                pend_w, pend_t, masks[l], steps[l], delays[l])
            if faults is not None:
                # outage: uploads to a dark RSU are dropped — both fresh
                # and due arrival cohorts lose their weight BEFORE the
                # mass partial sums, so conservation holds by construction
                up_a_l = fault_r["rsu_up"][l][rsu_assign_np]
                if plan.pad:
                    up_a_l = np.pad(up_a_l, (0, plan.pad),
                                    constant_values=1.0)
                up_a_l = jnp.asarray(up_a_l, jnp.float32)
                w_imm = w_imm * up_a_l
                w_due = w_due * up_a_l
            free_h, enq_h = np.asarray(free), np.asarray(enq)
            num_acc = jnp.zeros((R, N), jnp.float32)
            mass_acc = jnp.zeros((R,), jnp.float32)
            nxt, wb = put_chunk(0, pending_store), None
            for c in range(plan.n_chunks):
                lo, valid = plan.bounds(c)
                cur = nxt
                if c + 1 < plan.n_chunks:
                    nxt = put_chunk(c + 1, pending_store)
                sl = slice(c * plan.chunk, (c + 1) * plan.chunk)
                num_acc, mass_acc, trained, nq = chunk_step(
                    num_acc, mass_acc, rsu_flat, cloud_flat, *cur,
                    act[sl], w_imm[sl], w_due[sl])
                n_quar = n_quar + nq
                if wb is not None:
                    _flush_async_wb(store, pending_store, *wb)
                rows = trained if valid == plan.chunk else trained[:valid]
                wb = (lo, rows, free_h[lo:lo + valid], enq_h[lo:lo + valid])
            if wb is not None:
                _flush_async_wb(store, pending_store, *wb)
            rsu_flat, rsu_mass, cloud_macc = tick_finish(
                rsu_flat, rsu_mass, num_acc, mass_acc, cloud_macc)
            absorbed.append(mass_acc)
            gtick += 1
            if ce and gtick % ce == 0:
                macc_fire = cloud_macc if faults is None else \
                    cloud_macc * jnp.asarray(fault_r["rsu_up"][l],
                                             jnp.float32)
                cloud_flat = cloud_update(rsu_flat, macc_fire, cloud_flat)
                cloud_macc = jnp.zeros((R,), jnp.float32)

        if not ce:
            macc_end = cloud_macc if faults is None else \
                cloud_macc * jnp.asarray(fault_r["rsu_up"][hp.lar - 1],
                                         jnp.float32)
            cloud_flat = cloud_update(rsu_flat, macc_end, cloud_flat)
            cloud_macc = jnp.zeros((R,), jnp.float32)

        out = AsyncStreamState(
            store=store, pending_store=pending_store, rsu_flat=rsu_flat,
            rsu_mass=rsu_mass, cloud_flat=cloud_flat, pending_w=pend_w,
            pending_t=pend_t, conn=conn, rng=rng, cloud_macc=cloud_macc,
            tick=gtick)
        metrics = {
            "absorbed_mass": jnp.stack(absorbed),            # (LAR, R)
            "pending_mass": jnp.sum(pend_w * (pend_t > 0)),
        }
        if faults is not None:
            metrics["quarantined"] = n_quar
        return out, metrics

    global_round.plan = plan
    global_round.chunk_step = chunk_step
    return global_round


def _flush_async_wb(store, pending_store, lo, rows, free_h, enq_h) -> None:
    """Row-masked writeback of one trained chunk: free agents' rows update
    the fleet (busy keep theirs, matching the resident ``where(busy, old,
    trained)``); enqueuing agents' rows enter the pending store."""
    store.scatter(lo, rows, where=free_h)
    pending_store.scatter(lo, rows, where=enq_h)


# --------------------------------------------------------------------------
# two-axis (agent × parameter) streamed round (DESIGN.md §12)
# --------------------------------------------------------------------------

def init_twoaxis_state(cfg: SimConfig, spec: flatten.FlatSpec,
                       init_params: PyTree, key,
                       tiles: NTilePlan) -> StreamSimState:
    """Two-axis stream state: EVERY persistent N-wide buffer is
    host-resident — agent rows in a ``HostFleetStore``, the (R, N) RSU
    buffer and the fp32 cloud master as numpy arrays, all padded to the
    N-tile grid.  The device only ever holds chunk/tile-shaped slices."""
    from repro.core.fleet_store import np_storage_dtype
    vec = np.asarray(spec.ravel(init_params), np.float32)
    if tiles.pad:
        vec = np.pad(vec, (0, tiles.pad))
    rsu_host = np.empty((cfg.n_rsus, tiles.n_padded),
                        np_storage_dtype(spec.storage_dtype))
    rsu_host[:] = vec.astype(rsu_host.dtype)
    return StreamSimState(
        store=HostFleetStore.broadcast(vec, cfg.n_agents,
                                       spec.storage_dtype),
        rsu_flat=rsu_host,
        cloud_flat=vec.copy(),
        conn=init_conn_state(cfg.n_agents),
        rng=key)


def make_streamed_twoaxis_round(cfg: SimConfig, hp: H2FedParams,
                                het: HeterogeneityModel, fed: FederatedData,
                                spec: flatten.FlatSpec,
                                loss_fn: Callable = mlp.loss_fn, *,
                                chunk_agents: int = 0,
                                chunk_params: int = 0, faults=None):
    """Build the two-axis streamed synchronous round:
    StreamSimState -> StreamSimState (host rsu/cloud buffers, see
    ``init_twoaxis_state``).

    The agent axis streams exactly like ``make_streamed_flat_round``
    (same draws, same chunk grid, same defer-by-one writeback); the
    PARAMETER axis is additionally tiled so no (R, N)-wide buffer ever
    materializes on device:

      * training is necessarily full-N per agent chunk (the gradient
        couples every parameter), so the per-chunk device working set is
        (chunk, N) rows h2d'd from the host RSU buffer;
      * aggregation is per-COLUMN independent, so the chunk's partial
        numerator is computed tile-by-tile — ``ops.chunk_agg`` on a
        (chunk, tile) slice — and d2h-accumulated into a host (R, N)
        numerator: the device aggregation working set is (R, tile);
      * the local-round ``normalize_blend`` close and the round-end
        ``cloud_blend`` run per tile on device ((R, tile) up, blended
        tile down, defer-by-one reads overlapping the next dispatch).

    Column independence of every aggregation stage makes this equivalent
    to the one-axis streamed round (itself pinned to the resident
    engine); the first ``N`` columns of the padded grid carry the model.
    Faults fold exactly like the one-axis round (churn/outage weights +
    the non-finite quarantine guard, benign schedules bitwise no-ops).
    """
    A, R, N = cfg.n_agents, cfg.n_rsus, spec.n
    spe = max(int(fed.x.shape[1]) // cfg.batch, 1)
    n_steps = hp.local_epochs * spe
    plan = make_chunk_plan(A, chunk_agents)
    tiles = make_ntile_plan(N, chunk_params)
    Np = tiles.n_padded
    chunks = _data_chunks(fed, plan)
    n_per_agent = jnp.asarray(np.asarray(fed.n_per_agent), jnp.float32)
    rsu_assign_np = np.asarray(fed.rsu_assign, np.int32)
    guard = faults is not None and faults.guard_nonfinite

    train_agents = jax.vmap(
        lambda x, y, w0, wr, wc, act: _local_train_flat(
            loss_fn, spec, x, y, w0, wr, wc, hp, n_steps, act, cfg.batch),
        in_axes=(0, 0, 0, 0, None, 0))

    draws_fn = _make_flat_draws_fn(cfg, hp, het, plan, n_per_agent, spe)

    @jax.jit
    def chunk_train(w_start, cloud_dev, x_c, y_c, act_c, w_c):
        """Train one agent chunk full-N from its h2d'd RSU rows; the
        non-finite quarantine gate matches the one-axis chunk_step."""
        stored = spec.to_storage(
            train_agents(x_c, y_c, w_start, w_start, cloud_dev, act_c))
        nq = jnp.zeros((), jnp.int32)
        if guard:
            ok = jnp.all(jnp.isfinite(stored.astype(jnp.float32)), axis=1)
            stored = jnp.where(ok[:, None], stored, w_start)
            nq = jnp.sum(((w_c > 0) & ~ok).astype(jnp.int32))
            w_c = w_c * ok.astype(jnp.float32)
        return stored, w_c, nq

    @jax.jit
    def tile_agg(stored_t, w_c, assign_c):
        """One (chunk, tile) slice's partial aggregation — the only
        aggregation buffer the device sees is (R, tile)."""
        return ops.chunk_agg(stored_t, w_c, assign_c, R)

    @jax.jit
    def rsu_update(num_t, mass_acc, rsu_t):
        return normalize_blend(num_t, mass_acc, rsu_t)

    @jax.jit
    def cloud_update(rsu_t, total_mass, cloud_t):
        return ops.cloud_blend(rsu_t, total_mass, cloud_t)

    def put_chunk(c: int, rsu_host):
        x, y, a = chunks[c]
        # host-side gather of the chunk's RSU start rows (padded tail
        # rows read RSU 0 at weight 0 — algebra-neutral, like jnp.take)
        return jax.device_put((x, y, a, rsu_host[a]))

    def global_round(state: StreamSimState, fault_r=None):
        store = state.store
        rsu_host, cloud_host = state.rsu_flat, state.cloud_flat
        conn, rng, weights, steps = draws_fn(state.conn, state.rng)
        if faults is not None:
            weights = weights * _fault_weight_fold(fault_r, rsu_assign_np,
                                                   plan.pad)
        # Alg. 2 line 2: host RSU rows re-anchor to the cloud master
        rsu_host = np.empty_like(rsu_host)
        rsu_host[:] = cloud_host.astype(rsu_host.dtype)
        cloud_dev = jnp.asarray(cloud_host)          # full-N, training ref
        total_mass = jnp.zeros((R,), jnp.float32)
        n_quar = jnp.zeros((), jnp.int32)
        for l in range(hp.lar):
            num_host = np.zeros((R, Np), np.float32)
            mass_acc = jnp.zeros((R,), jnp.float32)
            nxt, wb = put_chunk(0, rsu_host), None
            for c in range(plan.n_chunks):
                lo, valid = plan.bounds(c)
                cur = nxt
                if c + 1 < plan.n_chunks:
                    nxt = put_chunk(c + 1, rsu_host)
                sl = slice(c * plan.chunk, (c + 1) * plan.chunk)
                x_c, y_c, a_c, w_start = cur
                stored, w_eff, nq = chunk_train(
                    w_start, cloud_dev, x_c, y_c, steps[l, sl],
                    weights[l, sl])
                n_quar = n_quar + nq
                # tile-by-tile d2h accumulation: the (R, N) numerator
                # lives on HOST; mass is column-independent (tile 0 only)
                for t in range(tiles.n_tiles):
                    tlo, thi = tiles.bounds(t)
                    num_t, mass_t = tile_agg(stored[:, tlo:thi], w_eff,
                                             a_c)
                    if t == 0:
                        mass_acc = mass_acc + mass_t
                    num_host[:, tlo:thi] += np.asarray(num_t)
                if wb is not None:
                    store.scatter(*wb)
                wb = (lo, stored if valid == plan.chunk
                      else stored[:valid])
            if wb is not None:
                store.scatter(*wb)
            # close the local round per tile: (R, tile) up, blended down,
            # defer-by-one reads so d2h overlaps the next tile's dispatch
            pend = None
            for t in range(tiles.n_tiles):
                tlo, thi = tiles.bounds(t)
                new_t = rsu_update(jnp.asarray(num_host[:, tlo:thi]),
                                   mass_acc,
                                   jnp.asarray(rsu_host[:, tlo:thi]))
                if pend is not None:
                    plo, phi, arr = pend
                    rsu_host[:, plo:phi] = np.asarray(arr)
                pend = (tlo, thi, new_t)
            plo, phi, arr = pend
            rsu_host[:, plo:phi] = np.asarray(arr)
            total_mass = total_mass + mass_acc
        # Alg. 3 line 6: cloud blend, tile by tile
        cloud_host = cloud_host.copy()
        pend = None
        for t in range(tiles.n_tiles):
            tlo, thi = tiles.bounds(t)
            new_c = cloud_update(jnp.asarray(rsu_host[:, tlo:thi]),
                                 total_mass,
                                 jnp.asarray(cloud_host[tlo:thi]))
            if pend is not None:
                plo, phi, arr = pend
                cloud_host[plo:phi] = np.asarray(arr)
            pend = (tlo, thi, new_c)
        plo, phi, arr = pend
        cloud_host[plo:phi] = np.asarray(arr)
        out = StreamSimState(store=store, rsu_flat=rsu_host,
                             cloud_flat=cloud_host, conn=conn, rng=rng)
        if faults is not None:
            return out, {"quarantined": n_quar}
        return out

    global_round.plan = plan
    global_round.tiles = tiles
    global_round.chunk_train = chunk_train
    global_round.tile_agg = tile_agg
    return global_round


# --------------------------------------------------------------------------
# runner
# --------------------------------------------------------------------------

def run_streamed_simulation(cfg: SimConfig, hp: H2FedParams,
                            het: HeterogeneityModel, fed: FederatedData,
                            init_params: PyTree, n_rounds: int, *,
                            engine: str = "flat",
                            acfg: Optional[AsyncConfig] = None,
                            fleet_store: str = "host",
                            chunk_agents: int = 0,
                            chunk_params: int = 0,
                            x_test=None, y_test=None,
                            loss_fn: Callable = mlp.loss_fn,
                            eval_fn: Optional[Callable] = None,
                            fleet_dtype=None, faults=None,
                            ) -> Tuple[Any, Dict[str, np.ndarray]]:
    """Cohort-streamed twin of ``run_simulation``: same rounds and history
    schema, with the (A, N) fleet in a FleetStore and the device working
    set bounded by the chunk.  ``fedsim.run_scenario`` dispatches here for
    ``fleet_store="host"`` / ``chunk_agents > 0`` specs; call directly
    when the arrays are hand-built (benchmarks/streaming_round).  Returns
    the streamed state (``.store.snapshot()`` materializes the fleet — an
    eval/test boundary for small A only)."""
    hp.validate(), het.validate()
    if engine not in ("flat", "async"):
        raise ValueError(f"engine {engine!r} does not stream "
                         f"(want 'flat'|'async'; tree/sharded are "
                         f"device-resident only)")
    if chunk_params and engine != "flat":
        raise ValueError(f"chunk_params={chunk_params} (two-axis "
                         f"streaming) is flat-engine only, got "
                         f"engine {engine!r}")
    spec = flatten.spec_of(
        init_params,
        storage_dtype=flatten.resolve_storage_dtype(fleet_dtype))
    key = jax.random.key(cfg.seed)
    if eval_fn is None and x_test is not None:
        x_test, y_test = jnp.asarray(x_test), jnp.asarray(y_test)
        eval_fn = jax.jit(lambda p: mlp.accuracy(p, x_test, y_test))

    if engine == "flat" and chunk_params > 0:
        tiles = make_ntile_plan(spec.n, chunk_params)
        state: Any = init_twoaxis_state(cfg, spec, init_params, key, tiles)
        round_fn = make_streamed_twoaxis_round(cfg, hp, het, fed, spec,
                                               loss_fn,
                                               chunk_agents=chunk_agents,
                                               chunk_params=chunk_params,
                                               faults=faults)
    elif engine == "flat":
        state = init_stream_state(cfg, spec, init_params, key,
                                  fleet_store=fleet_store)
        round_fn = make_streamed_flat_round(cfg, hp, het, fed, spec,
                                            loss_fn,
                                            chunk_agents=chunk_agents,
                                            faults=faults)
    else:
        state = init_async_stream_state(cfg, spec, init_params, key,
                                        fleet_store=fleet_store)
        round_fn = make_streamed_async_round(cfg, hp, het, fed, spec, acfg,
                                             loss_fn,
                                             chunk_agents=chunk_agents,
                                             faults=faults)
    sched = None
    if faults is not None:
        sched = faults.validate(cfg.n_rsus).lower(cfg.n_agents, cfg.n_rsus,
                                                  n_rounds * hp.lar)

    accs, rounds, absorbed, pending, quarantined = [], [], [], [], []
    for r in range(n_rounds):
        fr = None if sched is None else sched.round_slice(r, hp.lar)
        if engine == "async":
            state, metrics = (round_fn(state) if sched is None
                              else round_fn(state, fr))
            absorbed.append(float(jnp.sum(metrics["absorbed_mass"])))
            pending.append(float(metrics["pending_mass"]))
            if sched is not None:
                quarantined.append(int(metrics["quarantined"]))
        elif sched is not None:
            state, metrics = round_fn(state, fr)
            quarantined.append(int(metrics["quarantined"]))
        else:
            state = round_fn(state)
        if eval_fn is not None and (r % cfg.eval_every == 0
                                    or r == n_rounds - 1):
            accs.append(float(eval_fn(spec.unravel(state.cloud_flat))))
            rounds.append(r + 1)
    history = {"round": np.asarray(rounds), "acc": np.asarray(accs)}
    if engine == "async":
        history["absorbed_mass"] = np.asarray(absorbed)
        history["pending_mass"] = np.asarray(pending)
    if sched is not None:
        history["quarantined"] = np.asarray(quarantined)
    return state, history


def _run_streamed(res, init_params: PyTree, *,
                  loss_fn: Callable = mlp.loss_fn,
                  eval_fn: Optional[Callable] = None):
    """``run_scenario``'s streamed dispatch target (ResolvedScenario in,
    ``run_simulation``-shaped (state, history) out)."""
    s = res.spec
    acfg = None
    if s.engine == "async":
        acfg = AsyncConfig(staleness_decay=s.staleness_decay,
                           schedule=s.schedule, buffer_keep=s.buffer_keep,
                           cloud_every=s.cloud_every)
    x_test = res.test.x if res.test is not None else None
    y_test = res.test.y if res.test is not None else None
    return run_streamed_simulation(
        res.cfg, s.hp, s.het, res.fed, init_params, s.rounds,
        engine=s.engine, acfg=acfg, fleet_store=s.fleet_store,
        chunk_agents=s.chunk_agents, chunk_params=s.chunk_params,
        x_test=x_test, y_test=y_test,
        loss_fn=loss_fn, eval_fn=eval_fn, fleet_dtype=s.fleet_dtype,
        faults=s.faults)
