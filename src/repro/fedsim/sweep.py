"""Vmapped multi-scenario sweep engine (DESIGN.md §7).

The paper's figures are grids — CSR ∈ {0.1..1.0}, μ1/μ2 sweeps,
seed-averaged curves.  Running each cell as its own Python-loop simulation
pays S compiles and S× dispatch overhead for programs that differ only in
a handful of scalars.  This module makes the GRID the compiled unit:

  * S resolved scenarios (``core.scenario.ResolvedScenario``) with equal
    ``static_key`` (same shapes / scan lengths / engine flavor) are stacked
    along a new leading sweep axis — (S, A, N) fleet, (S, R, N) RSU
    buffers, (S,) PRNG keys — and the per-scenario scalars that differ
    (csr / fsr / scd / delay_p, μ1 / μ2 / lr) become (S,)-batched inputs;
  * the flat global round (or the semi-async tick loop) is ``vmap``-ed over
    the sweep axis and jitted ONCE with the state donated, so an entire CSR
    grid or seed-average runs as one compiled scan program instead of S
    sequential simulations — and matches them to fp32 tolerance, because
    the vmapped body IS ``fedsim.simulator._make_flat_round_body`` /
    ``fedsim.async_engine._make_async_round_body`` (tests/test_sweep.py);
  * scenarios that share a dataset / partition (same ``partition_key`` —
    e.g. a μ sweep over one realization) pass the (A, n, D) data block
    UNBATCHED (``in_axes=None``): no S× data copy;
  * fault plans (``core.faults.FaultPlan``) lower to per-round mask DATA
    stacked along the sweep axis — a grid of different fault schedules
    (one guard config, enforced by ``static_key``'s fingerprint) compiles
    to ONE program, trace-count-pinned in tests/test_faults.py;
  * when several host devices are visible and S divides them, the sweep
    axis is laid over a 1-D ('sweep',) mesh — pure data parallelism, zero
    collectives (``sweep_mesh``).  Composed with a
    ``core.topology.HierarchyTopology``: sweeps fill the spare pod axis
    when S ≥ pods, and fold into per-device vmap otherwise (the
    device-mapping table in DESIGN.md §7).

``run_scenarios`` is the one entry point the experiment layer needs: it
resolves specs, groups them by ``static_key``, sweeps each group — the
cadence knobs (``lar`` / ``local_epochs`` / ``cloud_every``) batch as
(S,) data under masked static upper bounds, so mixed-cadence cells share
ONE program — falling back to sequential execution only for the
tree/sharded/streamed/serve engines, and returns per-scenario histories
in input order.  Built programs are memoized in the
``core/program_cache`` registry (and in JAX's persistent compilation
cache), so re-runs skip tracing/compiling.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import flatten, program_cache
from repro.core import faults as faults_mod
from repro.core.heterogeneity import ConnState
from repro.core.scenario import ResolvedScenario, ScenarioSpec
from repro.data.partition import FederatedData
from repro.fedsim import async_engine, simulator
from repro.models import mlp

PyTree = Any

# the per-scenario scalars a sweep may batch along the sweep axis; every
# other field is static program structure and must be EQUAL across the
# group (enforced by grouping on ResolvedScenario.static_key)
DYN_HP = ("mu1", "mu2", "lr")
DYN_HET = ("csr", "fsr", "scd", "delay_p")
# cadence knobs batched as (S,) int32 data under masked static upper
# bounds (DESIGN.md §7 "cadence as data"): the scans pad to the group
# maxima and live masks neutralize the tail, so mixed-cadence cells
# share ONE program instead of one trace per cadence
DYN_CADENCE = ("lar", "local_epochs")        # hp.* int fields
DYN_SPEC = ("cloud_every",)                  # spec.* int fields (async)

# engines whose round body vmaps over the sweep axis
SWEEPABLE = ("flat", "async")


def async_config(spec: ScenarioSpec) -> async_engine.AsyncConfig:
    """The semi-async engine's config from a spec's async knobs."""
    return async_engine.AsyncConfig(
        staleness_decay=spec.staleness_decay, schedule=spec.schedule,
        buffer_keep=spec.buffer_keep, cloud_every=spec.cloud_every)


def run_scenario(res, init_params: Optional[PyTree] = None, *,
                 loss_fn: Callable = mlp.loss_fn,
                 eval_fn: Optional[Callable] = None,
                 mesh=None, topo=None):
    """THE engine entry point (DESIGN.md §8): run ONE scenario through its
    declared engine; returns ``(final state, history)``.

    Every knob is a ``ScenarioSpec`` field — ``engine`` (flat | tree |
    sharded | async), ``fleet_dtype``, ``fused``, ``rsu_sharded``, the
    semi-async schedule, and the cohort-streaming pair ``fleet_store`` /
    ``chunk_agents`` (either one non-default dispatches the streamed
    engines in ``fedsim/streaming``).  The legacy ``run_simulation`` /
    ``run_async_simulation`` / ``run_sharded_simulation`` signatures are
    deprecated wrappers over this function (via ``adhoc_scenario``).

    ``init_params`` defaults to the paper's MLP initialized from the
    spec's data seed; pass a pytree (e.g. the OEM-pretrained model) to
    override.  ``mesh`` (sharded) and ``topo`` (async) pass through to
    those engines; ``eval_fn`` overrides the test-set accuracy eval.
    """
    if isinstance(res, ScenarioSpec):
        res = res.resolve()
    s = res.spec.validate()
    program_cache.watch_compiles()
    if s.program_cache:
        program_cache.enable_persistent_cache()
    if init_params is None:
        from repro.configs.mnist_mlp import CONFIG
        cfg_model = (CONFIG if not s.hidden_dims else
                     dataclasses.replace(
                         CONFIG, hidden_dims=tuple(s.hidden_dims)))
        init_params = mlp.init_params(cfg_model, jax.random.key(s.seed))
    if s.serve_events:
        from repro.fedsim import serving
        return serving._run_serve(res, init_params, loss_fn=loss_fn,
                                  eval_fn=eval_fn)
    if s.engine == "sharded":
        from repro.fedsim import sharded
        return sharded._run_sharded(res, init_params, loss_fn=loss_fn,
                                    mesh=mesh)
    if s.fleet_store != "device" or s.chunk_agents:
        from repro.fedsim import streaming
        return streaming._run_streamed(res, init_params, loss_fn=loss_fn,
                                       eval_fn=eval_fn)
    if s.engine == "async":
        return async_engine._run_async(res, init_params, loss_fn=loss_fn,
                                       eval_fn=eval_fn, topo=topo)
    return simulator._run_sync(res, init_params, loss_fn=loss_fn,
                               eval_fn=eval_fn)


def adhoc_scenario(cfg, hp, het, fed, *, n_rounds: int,
                   engine: str = "flat", fleet_dtype=None,
                   fused: bool = True, rsu_sharded: bool = False,
                   model_shards: int = 1, async_cfg=None,
                   fleet_store: str = "device", chunk_agents: int = 0,
                   chunk_params: int = 0, hidden_dims=(), x_test=None,
                   y_test=None) -> ResolvedScenario:
    """Wrap pre-built arrays (SimConfig + FederatedData + optional test
    set) in the scenario contract so ``run_scenario`` can drive them —
    the deprecated ``run_*_simulation`` wrappers' bridge.  Only ``fed``
    and ``test`` are populated (train/pretrain pools stay ``None``); the
    seed mapping ``seed=0, sim_seed=cfg.seed`` makes ``spec.sim_config()``
    reproduce ``cfg`` exactly, so wrapper numerics are unchanged."""
    dt = flatten.resolve_storage_dtype(fleet_dtype)
    dtype_name = ("bfloat16" if jnp.dtype(dt) == jnp.dtype(jnp.bfloat16)
                  else "float32")
    async_kw = {}
    if async_cfg is not None:
        async_kw = dict(staleness_decay=async_cfg.staleness_decay,
                        schedule=async_cfg.schedule,
                        buffer_keep=async_cfg.buffer_keep,
                        cloud_every=async_cfg.cloud_every)
    spec = ScenarioSpec(
        n_agents=cfg.n_agents, n_rsus=cfg.n_rsus, batch=cfg.batch,
        hp=hp, het=het, engine=engine, fleet_dtype=dtype_name, fused=fused,
        rsu_sharded=rsu_sharded, model_shards=model_shards,
        fleet_store=fleet_store, chunk_agents=chunk_agents,
        chunk_params=chunk_params, hidden_dims=tuple(hidden_dims),
        rounds=n_rounds, eval_every=cfg.eval_every, seed=0,
        sim_seed=cfg.seed, **async_kw)
    test = None
    if x_test is not None:
        from repro.data.synthetic import Dataset
        x_np, y_np = np.asarray(x_test), np.asarray(y_test)
        test = Dataset(x=x_np, y=y_np, n_classes=int(y_np.max()) + 1)
    return ResolvedScenario(spec=spec, train=None, test=test,
                            pretrain_pool=None, fed_pool=None, fed=fed)


# --------------------------------------------------------------------------
# grouping
# --------------------------------------------------------------------------

def group_indices(resolved: Sequence[ResolvedScenario]) -> List[List[int]]:
    """Partition scenario indices into sweep-compatible groups (equal
    ``static_key``), preserving first-seen order."""
    groups: Dict[tuple, List[int]] = {}
    for i, r in enumerate(resolved):
        groups.setdefault(r.static_key, []).append(i)
    return list(groups.values())


def _stack_or_share(arrays):
    """(stacked (S, ...) array, 0) when members differ; (shared array,
    None in_axes) when every scenario references the same object — the
    no-copy path for grids over one dataset realization."""
    first = arrays[0]
    if all(a is first for a in arrays):
        return jnp.asarray(first), None
    return jnp.stack([jnp.asarray(a) for a in arrays]), 0


def _dyn_scalars(specs: Sequence[ScenarioSpec],
                 force: Sequence[str] = ()) -> Dict[str, jax.Array]:
    """(S,)-batched hp/het/cadence scalars — the fields that actually
    differ across the group (equal fields stay baked into the template, so
    a pure seed-average compiles the identical body the single run does).

    ``force`` names fields to batch even when equal within ``specs`` —
    ``run_scenarios`` passes the whole group's varying set so every
    ``max_sweep`` chunk of one group (including a constant tail chunk)
    traces the identical program."""
    force = set(force)
    dyn: Dict[str, jax.Array] = {}

    def _add(key, vals, dtype):
        if key in force or any(v != vals[0] for v in vals[1:]):
            dyn[key] = jnp.asarray(vals, dtype)

    for name in DYN_HP:
        _add(f"hp.{name}", [getattr(s.hp, name) for s in specs],
             jnp.float32)
    for name in DYN_CADENCE:
        _add(f"hp.{name}", [getattr(s.hp, name) for s in specs], jnp.int32)
    for name in DYN_HET:
        _add(f"het.{name}", [getattr(s.het, name) for s in specs],
             jnp.int32 if name == "scd" else jnp.float32)
    for name in DYN_SPEC:
        _add(f"spec.{name}", [getattr(s, name) for s in specs], jnp.int32)
    return dyn


def _stack_fault_rounds(group: Sequence[ResolvedScenario],
                        lar_bound: int) -> Dict[str, np.ndarray]:
    """Per-scenario lowered fault schedules stacked over the sweep axis:
    dict of (S, rounds, lar_bound, A|R) float32 host arrays — the fault
    masks ride the vmapped round as ORDINARY DATA, so a grid of different
    :class:`~repro.core.faults.FaultPlan` schedules (same guard
    fingerprint, enforced by ``static_key`` grouping) still compiles to
    one sweep program.

    Each scenario's plan lowers over its OWN tick clock (``rounds × its
    lar``).  When the group batches cadence, rows are padded to the
    group-wide scan bound by clipping to the round's last live tick —
    those scan iterations are masked dead, so the clipped values never
    land (the same neutrality argument as the cadence live masks)."""
    out: Dict[str, list] = {k: [] for k in faults_mod.FAULT_FIELDS}
    for r in group:
        s = r.spec
        lar = s.hp.lar
        sched = s.faults.validate(s.n_rsus).lower(
            s.n_agents, s.n_rsus, s.rounds * lar)
        pad = np.minimum(np.arange(lar_bound), lar - 1)          # (L,)
        idx = np.minimum(np.arange(s.rounds)[:, None] * lar + pad[None, :],
                         sched.n_ticks - 1)                      # (rounds, L)
        for k in faults_mod.FAULT_FIELDS:
            out[k].append(getattr(sched, k)[idx])
    return {k: np.stack(v) for k, v in out.items()}


def _cadence_bounds(specs: Sequence[ScenarioSpec],
                    dyn_names: Sequence[str]
                    ) -> Optional[simulator.Cadence]:
    """Group-wide static scan bounds when any cadence knob is batched;
    None keeps the fully static (ungated) round body."""
    if not any(f"hp.{n}" in dyn_names for n in DYN_CADENCE):
        return None
    return simulator.Cadence(
        lar=max(s.hp.lar for s in specs),
        local_epochs=max(s.hp.local_epochs for s in specs))


# --------------------------------------------------------------------------
# the batched program
# --------------------------------------------------------------------------

class SweepProgram(NamedTuple):
    """One compiled sweep: ``state = round_fn(state, data, dyn)`` advances
    every scenario one global round (async: returns (state, metrics)).
    Faulted sweeps take a 4th operand — the round's (S, lar, ·) fault
    mask slice — and always return (state, metrics)."""
    round_fn: Callable        # jitted, state donated
    state: Any                # (S,)-batched FlatSimState / AsyncSimState
    data: Dict[str, jax.Array]
    dyn: Dict[str, jax.Array]
    eval_fn: Optional[Callable]   # (cloud (S, N)) -> (S,) accuracies
    engine: str
    fspec: flatten.FlatSpec
    n_scenarios: int
    # (S, rounds, lar_bound, A|R) lowered fault masks (host numpy; None
    # for fault-free groups) — run_sweep slices round r and vmaps it in
    fault_rounds: Optional[Dict[str, np.ndarray]] = None


def sweep_mesh(n_scenarios: int):
    """1-D ('sweep',) mesh over the visible devices when the sweep axis can
    map onto them (S divisible by the device count); None otherwise — the
    sweep then runs vmapped within one device.  With a hierarchy mesh in
    scope the same rule applies per pod: S ≥ pods sweeps across pods,
    smaller sweeps fold into per-device vmap (DESIGN.md §7)."""
    from repro.launch.mesh import make_mesh
    n = len(jax.devices())
    if n <= 1 or n_scenarios % n:
        return None
    return make_mesh((n,), ("sweep",))


def _shard_sweep(tree, mesh):
    """Lay every (S, ...) leaf over the sweep mesh axis (leading dim)."""
    def put(a):
        spec = P(*(("sweep",) + (None,) * (jnp.ndim(a) - 1)))
        return jax.device_put(a, NamedSharding(mesh, spec))
    return jax.tree.map(put, tree)


def _baked_scalars(s0: ScenarioSpec, dyn_names) -> tuple:
    """The hp/het/cadence values a trace bakes in as constants — every
    sweepable scalar NOT batched in ``dyn``.  Part of the program-cache
    key: two groups may share one registry entry exactly when their baked
    constants (and everything else in the key) agree."""
    baked = []
    for name in DYN_HP + DYN_CADENCE:
        if f"hp.{name}" not in dyn_names:
            baked.append((f"hp.{name}", getattr(s0.hp, name)))
    for name in DYN_HET:
        if f"het.{name}" not in dyn_names:
            baked.append((f"het.{name}", getattr(s0.het, name)))
    for name in DYN_SPEC:
        if f"spec.{name}" not in dyn_names:
            baked.append((f"spec.{name}", getattr(s0, name)))
    return tuple(baked)


def build_sweep(group: Sequence[ResolvedScenario], init_params,
                *, loss_fn: Callable = mlp.loss_fn,
                shard: bool = True,
                force_dyn: Sequence[str] = (),
                cadence: Optional[simulator.Cadence] = None
                ) -> SweepProgram:
    """Stack a static-compatible scenario group into one vmapped, jitted
    round program (the ONE jit trace a grid pays).

    ``init_params``: a single parameter pytree shared by every scenario or
    a per-scenario list; sweep state is built from its ravel.

    ``force_dyn`` / ``cadence`` let ``run_scenarios`` pin the batched-field
    set and the scan bounds group-wide, so every ``max_sweep`` chunk of one
    group reuses the identical program (core/program_cache registry hit).
    When the spec opts in (``program_cache=True``, the default) the built
    round/eval programs are memoized under a :class:`ProgramKey` — a
    repeated grid, a later chunk, or a singleton re-run skips tracing.
    """
    specs = [r.spec for r in group]
    s0, cfg = specs[0], group[0].cfg
    S, A, R = len(group), s0.n_agents, s0.n_rsus
    engine = s0.engine
    if engine not in SWEEPABLE:
        raise ValueError(f"engine {engine!r} is not sweepable "
                         f"(want one of {SWEEPABLE})")
    if s0.serve_events:
        raise ValueError("serve-mode scenarios (serve_events > 0) are "
                         "event-driven and cannot be vmapped into a sweep; "
                         "run them through run_scenario")

    params_list = (list(init_params) if isinstance(init_params, (list, tuple))
                   else [init_params] * S)
    if len(params_list) != S:
        raise ValueError(f"init_params list must have one entry per "
                         f"scenario ({S}), got {len(params_list)}")
    fspec = flatten.spec_of(
        params_list[0],
        storage_dtype=flatten.resolve_storage_dtype(s0.fleet_dtype))
    if all(p is params_list[0] for p in params_list):
        vecs = jnp.broadcast_to(fspec.ravel(params_list[0]), (S, fspec.n))
    else:
        vecs = jnp.stack([fspec.ravel(p) for p in params_list])

    # per-scenario draw keys — the exact ``jax.random.key(cfg.seed)`` the
    # sequential engines build, stacked
    seeds = jnp.asarray([r.cfg.seed for r in group], jnp.uint32)
    keys = jax.vmap(jax.random.key)(seeds)

    # data blocks: unbatched (in_axes=None) when the group shares one
    # FederatedData realization, stacked otherwise
    feds = [r.fed for r in group]
    data, data_axes = {}, {}
    for name in ("x", "y", "n_per_agent", "rsu_assign"):
        data[name], data_axes[name] = _stack_or_share(
            [getattr(f, name) for f in feds])
    dyn = _dyn_scalars(specs, force=force_dyn)
    if cadence is None:
        cadence = _cadence_bounds(specs, dyn)

    # fault plans: guard structure (fingerprint) is in static_key, so the
    # group is all-faulted or all-clean with ONE guard config; the
    # schedules themselves become a per-round vmapped data operand
    plan0 = s0.faults
    fault_rounds = None
    if plan0 is not None:
        fault_rounds = _stack_fault_rounds(
            group, cadence.lar if cadence is not None else s0.hp.lar)

    hp0, het0 = s0.hp, s0.het

    def _materialize(dyn_i):
        hp_kw = {k.split(".", 1)[1]: v for k, v in dyn_i.items()
                 if k.startswith("hp.")}
        het_kw = {k.split(".", 1)[1]: v for k, v in dyn_i.items()
                  if k.startswith("het.")}
        hp = dataclasses.replace(hp0, **hp_kw) if hp_kw else hp0
        het = dataclasses.replace(het0, **het_kw) if het_kw else het0
        return hp, het

    # eval axes enter the program key too (shared vs stacked test set is
    # a different eval trace)
    x_t, ax_x = _stack_or_share([r.test.x for r in group])
    y_t, ax_y = _stack_or_share([r.test.y for r in group])
    mesh = sweep_mesh(S) if shard else None

    if engine == "flat":
        def one_round(state, data_i, dyn_i, fault_i=None):
            program_cache.note_trace("sweep_round")
            hp, het = _materialize(dyn_i)
            fed = FederatedData(**data_i)
            body = simulator._make_flat_round_body(
                cfg, hp, het, fed, fspec, loss_fn, fused=s0.fused,
                cadence=cadence, faults=plan0)
            return body(state) if plan0 is None else body(state, fault_i)

        sv = fspec.to_storage(vecs)
        state: Any = simulator.FlatSimState(
            agent_flat=jnp.broadcast_to(sv[:, None, :], (S, A, fspec.n)),
            rsu_flat=jnp.broadcast_to(sv[:, None, :], (S, R, fspec.n)),
            cloud_flat=vecs.astype(jnp.float32),
            conn=ConnState(jnp.zeros((S, A), jnp.int32)),
            rng=keys)
    else:
        acfg = async_config(s0).validate()

        def one_round(state, data_i, dyn_i, fault_i=None):
            program_cache.note_trace("sweep_round")
            hp, het = _materialize(dyn_i)
            a = acfg
            if "spec.cloud_every" in dyn_i:
                a = dataclasses.replace(
                    acfg, cloud_every=dyn_i["spec.cloud_every"])
            fed = FederatedData(**data_i)
            body = async_engine._make_async_round_body(
                cfg, hp, het, fed, fspec, a, loss_fn, fused=s0.fused,
                cadence=cadence, faults=plan0)
            return body(state) if plan0 is None else body(state, fault_i)

        sv = fspec.to_storage(vecs)
        state = async_engine.AsyncSimState(
            agent_flat=jnp.broadcast_to(sv[:, None, :], (S, A, fspec.n)),
            rsu_flat=jnp.broadcast_to(sv[:, None, :], (S, R, fspec.n)),
            rsu_mass=jnp.zeros((S, R), jnp.float32),
            cloud_flat=vecs.astype(jnp.float32),
            pending_x=jnp.zeros((S, A, fspec.n), fspec.storage_dtype),
            pending_w=jnp.zeros((S, A), jnp.float32),
            pending_t=jnp.zeros((S, A), jnp.int32),
            conn=ConnState(jnp.zeros((S, A), jnp.int32)),
            rng=keys,
            cloud_macc=jnp.zeros((S, R), jnp.float32),
            tick=jnp.zeros((S,), jnp.int32))

    def _build_programs():
        axes = ((0, data_axes, 0) if plan0 is None
                else (0, data_axes, 0, 0))
        round_fn = jax.jit(jax.vmap(one_round, in_axes=axes),
                           donate_argnums=(0,))
        # batched eval on the (S, N) cloud master — shared test set when
        # every scenario references the same arrays
        eval_fn = jax.jit(jax.vmap(
            lambda v, x, y: mlp.accuracy(fspec.unravel(v), x, y),
            in_axes=(0, ax_x, ax_y)))
        return round_fn, eval_fn

    if s0.program_cache:
        program_cache.enable_persistent_cache()
    prog_key = program_cache.ProgramKey(
        kind="sweep",
        static_key=group[0].static_key,
        n_scenarios=S,
        dyn_names=tuple(sorted(dyn)),
        baked=(_baked_scalars(s0, dyn), loss_fn),
        cadence=cadence,
        data_axes=(tuple(sorted(data_axes.items(),
                                key=lambda kv: kv[0])), ax_x, ax_y),
        donation=(0,),
        devices=program_cache.device_fingerprint(),
        mesh=program_cache.mesh_fingerprint(mesh),
        flags=program_cache.ops_flags(s0.fused))
    round_fn, eval_fn = program_cache.get_or_build(
        prog_key, _build_programs, enabled=s0.program_cache)
    eval_closed = lambda cloud: eval_fn(cloud, x_t, y_t)    # noqa: E731

    if mesh is not None:
        state = _shard_sweep(state, mesh)
        dyn = _shard_sweep(dyn, mesh)
        # stacked (S, ...) data blocks live sweep-sharded too; shared
        # (in_axes=None) blocks stay replicated
        data = {k: (_shard_sweep(v, mesh) if data_axes[k] == 0 else v)
                for k, v in data.items()}

    return SweepProgram(round_fn=round_fn, state=state, data=data, dyn=dyn,
                        eval_fn=eval_closed, engine=engine, fspec=fspec,
                        n_scenarios=S, fault_rounds=fault_rounds)


def run_sweep(group: Sequence[ResolvedScenario], init_params, *,
              loss_fn: Callable = mlp.loss_fn, shard: bool = True,
              force_dyn: Sequence[str] = (),
              cadence: Optional[simulator.Cadence] = None,
              ) -> List[Dict[str, np.ndarray]]:
    """Run one static-compatible group as a single compiled sweep; returns
    per-scenario histories (same schema as ``run_simulation``'s; async
    scenarios additionally record absorbed/pending mass, faulted ones the
    per-round quarantine counts)."""
    prog = build_sweep(group, init_params, loss_fn=loss_fn, shard=shard,
                       force_dyn=force_dyn, cadence=cadence)
    s0 = group[0].spec
    state = prog.state
    faulted = prog.fault_rounds is not None
    accs, rounds = [], []
    absorbed, pending = [], []
    quar, blocked = [], []
    for r in range(s0.rounds):
        args = (state, prog.data, prog.dyn)
        if faulted:
            # round r's (S, lar, ·) mask slice rides in as vmapped data
            args += ({k: jnp.asarray(v[:, r])
                      for k, v in prog.fault_rounds.items()},)
        if prog.engine == "async":
            state, metrics = prog.round_fn(*args)
            absorbed.append(np.asarray(
                jnp.sum(metrics["absorbed_mass"], axis=(1, 2))))   # (S,)
            pending.append(np.asarray(metrics["pending_mass"]))    # (S,)
            if faulted:
                quar.append(np.asarray(
                    jnp.sum(metrics["quarantined"], axis=1)))      # (S,)
                blocked.append(np.asarray(
                    jnp.sum(metrics["blocked_mass"], axis=1)))
        elif faulted:
            state, metrics = prog.round_fn(*args)
            quar.append(np.asarray(metrics["quarantined"]))        # (S,)
        else:
            state = prog.round_fn(*args)
        if r % s0.eval_every == 0 or r == s0.rounds - 1:
            accs.append(np.asarray(prog.eval_fn(state.cloud_flat)))
            rounds.append(r + 1)
    acc_mat = np.stack(accs, axis=1)                        # (S, T)
    out = []
    for i in range(prog.n_scenarios):
        h = {"round": np.asarray(rounds), "acc": acc_mat[i]}
        if prog.engine == "async":
            h["absorbed_mass"] = np.asarray([a[i] for a in absorbed])
            h["pending_mass"] = np.asarray([p[i] for p in pending])
        if faulted:
            h["quarantined"] = np.asarray([q[i] for q in quar])
            if prog.engine == "async":
                h["blocked_mass"] = np.asarray([b[i] for b in blocked])
        out.append(h)
    return out


def run_scenarios(specs_or_resolved: Sequence, init_params, *,
                  loss_fn: Callable = mlp.loss_fn, shard: bool = True,
                  max_sweep: int = 0) -> List[Dict[str, np.ndarray]]:
    """Run a whole grid: group by ``static_key``, sweep every compatible
    group as one compiled program, fall back to sequential execution for
    non-sweepable engines.  Returns histories in input order.

    Sweepable singleton groups run through the (cached) one-cell sweep
    program rather than the sequential engines, so a lone spec re-run is a
    warm program-cache hit (DESIGN.md §10).

    ``init_params``: one shared pytree, a per-scenario list, or a callable
    ``spec -> pytree`` (e.g. the per-dataset pretrained model).
    ``max_sweep`` > 0 chunks oversized groups (memory bound: the sweep
    state is S× the single-scenario fleet).  A short tail chunk is padded
    to ``max_sweep`` with duplicates of its last cell (results sliced
    off), and the batched-field set + cadence bounds are pinned group-wide,
    so every chunk of a group runs the SAME compiled program.
    """
    resolved = [s.resolve() if isinstance(s, ScenarioSpec) else s
                for s in specs_or_resolved]
    if callable(init_params):
        params_list = [init_params(r.spec) for r in resolved]
    elif isinstance(init_params, (list, tuple)):
        params_list = list(init_params)
    else:
        params_list = [init_params] * len(resolved)
    if len(params_list) != len(resolved):
        raise ValueError("need one init_params per scenario")

    out: List[Optional[Dict[str, np.ndarray]]] = [None] * len(resolved)
    for idx in group_indices(resolved):
        s0 = resolved[idx[0]].spec
        if (s0.engine not in SWEEPABLE or s0.fleet_store != "device"
                or s0.chunk_agents or s0.serve_events):
            for i in idx:
                _, hist = run_scenario(resolved[i], params_list[i],
                                       loss_fn=loss_fn)
                out[i] = hist
            continue
        # pin the batched fields + cadence bounds across the WHOLE group
        # so every max_sweep chunk traces (or registry-hits) one program
        group_specs = [resolved[i].spec for i in idx]
        force_dyn = tuple(sorted(_dyn_scalars(group_specs)))
        cadence = _cadence_bounds(group_specs, force_dyn)
        chunks = ([idx] if not max_sweep else
                  [idx[i:i + max_sweep]
                   for i in range(0, len(idx), max_sweep)])
        for chunk in chunks:
            # pad a short tail chunk to max_sweep with duplicates of its
            # last cell — same program as the full chunks; the duplicate
            # lanes are algebra-neutral (vmap lanes are independent) and
            # their histories are sliced off below
            pad = (max_sweep - len(chunk)
                   if max_sweep and len(idx) > max_sweep else 0)
            cidx = list(chunk) + [chunk[-1]] * pad
            hists = run_sweep([resolved[i] for i in cidx],
                              [params_list[i] for i in cidx],
                              loss_fn=loss_fn, shard=shard,
                              force_dyn=force_dyn, cadence=cadence)
            for i, h in zip(chunk, hists):
                out[i] = h
    return out
