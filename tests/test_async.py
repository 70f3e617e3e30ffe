"""Semi-async engine tests (DESIGN.md §6).

Pins the staleness algebra (monotone decay, running cohort-mass
conservation, the all-arrivals-stale edge case), the scatter-accumulate
kernel routes, the buffer-donation no-copy guarantee of the flat/async
round jits, and the hard correctness anchor: with zero latencies and decay
disabled ``engine="async"`` reproduces ``engine="flat"`` to fp32 tolerance.
Multi-device cases run through the shared ``forced_devices_run`` fixture.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from prop_compat import given, settings, st

from repro.core import flatten
from repro.core.aggregation import (buffer_absorb, scatter_accumulate,
                                    staleness_weights)
from repro.core.heterogeneity import HeterogeneityModel, sample_latency
from repro.kernels import ops
from repro.kernels import masked_hier_agg as mha
from repro.kernels.ref import scatter_accumulate_ref

F32 = np.float32

# decay disabled + replace-on-arrivals + per-round cloud cadence: the
# configuration under which the async engine must equal engine="flat"
SYNC_LIMIT = dict(staleness_decay=1.0, buffer_keep=0.0, cloud_every=0)


def _run_sim(cfg, hp, het, fed, params, rounds, *, x_test, y_test, **kw):
    from repro.fedsim.sweep import adhoc_scenario, run_scenario
    res = adhoc_scenario(cfg, hp, het, fed, n_rounds=rounds,
                         x_test=x_test, y_test=y_test, **kw)
    return run_scenario(res, params)


@pytest.fixture(scope="module")
def small_fed(tiny_task, fed_small):
    from repro.configs.mnist_mlp import CONFIG as MLP_CFG
    from repro.models import mlp
    _, test = tiny_task
    params = mlp.init_params(MLP_CFG, jax.random.key(0))
    return fed_small, test, params


class TestStalenessAlgebra:
    @settings(max_examples=20, deadline=None)
    @given(decay=st.floats(0.0, 1.0, width=32),
           schedule=st.sampled_from(["exp", "poly"]))
    def test_monotone_decay_in_staleness(self, decay, schedule):
        tau = jnp.arange(8)
        s = np.asarray(staleness_weights(tau, decay=decay,
                                         schedule=schedule))
        assert s[0] == 1.0                       # fresh is never decayed
        assert np.all(np.diff(s) <= 1e-7), s     # monotone non-increasing
        assert np.all((0.0 <= s) & (s <= 1.0))

    def test_decay_disabled_is_identity(self):
        tau = jnp.arange(6)
        np.testing.assert_array_equal(
            np.asarray(staleness_weights(tau, decay=1.0, schedule="exp")),
            1.0)
        np.testing.assert_array_equal(
            np.asarray(staleness_weights(tau, decay=0.0, schedule="poly")),
            1.0)

    def test_unknown_schedule_raises(self):
        with pytest.raises(ValueError):
            staleness_weights(jnp.arange(3), schedule="nope")

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 1000), keep=st.floats(0.0, 1.0, width=32))
    def test_buffer_absorb_mass_accounting(self, seed, keep):
        """M' == keep·M + m_new exactly, and the merged buffer is the
        exactly-normalized weighted mean of retained state + arrivals."""
        rng = np.random.default_rng(seed)
        R, N = 4, 9
        buf = jnp.asarray(rng.standard_normal((R, N)), F32)
        M = jnp.asarray(rng.uniform(0, 5, R), F32)
        num = jnp.asarray(rng.standard_normal((R, N)), F32)
        m_new = jnp.asarray(rng.uniform(0, 3, R), F32)
        out, M2 = buffer_absorb(buf, M, num, m_new, keep=keep)
        np.testing.assert_allclose(np.asarray(M2),
                                   keep * np.asarray(M) + np.asarray(m_new),
                                   rtol=1e-6)
        expect = (keep * np.asarray(M)[:, None] * np.asarray(buf)
                  + np.asarray(num)) / np.asarray(M2)[:, None]
        live = np.asarray(M2) > 0
        np.testing.assert_allclose(np.asarray(out)[live], expect[live],
                                   atol=1e-5)
        # zero total mass keeps the old buffer row
        np.testing.assert_array_equal(np.asarray(out)[~live],
                                      np.asarray(buf)[~live])

    def test_buffer_absorb_keep_zero_is_replace(self):
        """keep=0 reproduces the synchronous replace-on-arrivals RSU
        semantics (the normalized mean of the tick's arrivals alone)."""
        rng = np.random.default_rng(0)
        buf = jnp.asarray(rng.standard_normal((3, 5)), F32)
        num = jnp.asarray(rng.standard_normal((3, 5)), F32)
        m = jnp.asarray([2.0, 0.0, 1.0], F32)
        out, M2 = buffer_absorb(buf, jnp.full((3,), 7.0), num, m, keep=0.0)
        np.testing.assert_allclose(np.asarray(out)[0],
                                   np.asarray(num)[0] / 2.0, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out)[1],
                                      np.asarray(buf)[1])
        np.testing.assert_array_equal(np.asarray(M2), np.asarray(m))

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_scatter_accumulate_routes_agree(self, seed):
        """ops route == segment-sum reference == Pallas interpret route."""
        rng = np.random.default_rng(seed)
        A, R, N = 11, 3, 17
        x = jnp.asarray(rng.standard_normal((A, N)), F32)
        w = jnp.asarray(rng.uniform(0, 2, A) * (rng.random(A) < 0.7), F32)
        assign = jnp.asarray(rng.integers(0, R, A), jnp.int32)
        num0, m0 = scatter_accumulate(x, w, assign, R)
        for num, m in (ops.masked_scatter_accumulate(x, w, assign, R),
                       scatter_accumulate_ref(x, w, assign, R),
                       mha.scatter_accumulate(x, w, assign, R,
                                              interpret=True)):
            np.testing.assert_allclose(np.asarray(m), np.asarray(m0),
                                       rtol=1e-6)
            np.testing.assert_allclose(np.asarray(num), np.asarray(num0),
                                       atol=2e-5)

    def test_sample_latency_bounds_and_limits(self):
        key = jax.random.key(0)
        het0 = HeterogeneityModel()                      # sync default
        np.testing.assert_array_equal(
            np.asarray(sample_latency(key, 16, het0)), 0)
        het1 = HeterogeneityModel(max_delay=3, delay_p=1.0)  # all-stale
        np.testing.assert_array_equal(
            np.asarray(sample_latency(key, 16, het1)), 3)
        het = HeterogeneityModel(max_delay=3, delay_p=0.5)
        d = np.asarray(sample_latency(key, 500, het))
        assert d.min() >= 0 and d.max() <= 3
        assert (d == 0).mean() > 0.3                     # geometric head


class TestSyncLimit:
    """The hard correctness anchor: zero latencies + decay disabled
    reproduces engine="flat" to fp32 tolerance."""

    def test_matches_flat_engine(self, small_fed):
        from repro.core.baselines import h2fed
        from repro.fedsim.async_engine import AsyncConfig
        from repro.fedsim.simulator import SimConfig
        fed, test, params = small_fed
        cfg = SimConfig(n_agents=fed.n_agents, n_rsus=4, batch=16, seed=0)
        hp = h2fed(mu1=0.05, mu2=0.01, lar=2, lr=0.1)
        het = HeterogeneityModel(csr=0.6, lar=hp.lar)    # max_delay=0
        sf, hf = _run_sim(cfg, hp, het, fed, params, 3,
                          x_test=test.x, y_test=test.y, engine="flat")
        sa, ha = _run_sim(cfg, hp, het, fed, params, 3,
                          x_test=test.x, y_test=test.y, engine="async",
                          async_cfg=AsyncConfig(**SYNC_LIMIT))
        np.testing.assert_allclose(hf["acc"], ha["acc"], atol=2e-3)
        spec = flatten.spec_of(params)
        np.testing.assert_allclose(
            np.asarray(spec.ravel(sf.cloud_params)),
            np.asarray(sa.cloud_flat), atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(spec.ravel_stacked(sf.agent_params)),
            np.asarray(sa.agent_flat), atol=1e-4, rtol=1e-4)
        assert float(jnp.sum(sa.pending_w)) == 0.0       # nothing in flight

    @settings(max_examples=2, deadline=None)
    @given(seed=st.integers(0, 100), csr=st.floats(0.2, 1.0))
    def test_sync_limit_property(self, small_fed, seed, csr):
        from repro.core.baselines import h2fed
        from repro.fedsim.async_engine import AsyncConfig
        from repro.fedsim.simulator import SimConfig
        fed, test, params = small_fed
        cfg = SimConfig(n_agents=fed.n_agents, n_rsus=4, batch=16,
                        seed=seed)
        hp = h2fed(mu1=0.01, mu2=0.005, lar=2, lr=0.1)
        het = HeterogeneityModel(csr=float(csr), lar=hp.lar)
        _, hf = _run_sim(cfg, hp, het, fed, params, 2,
                         x_test=test.x, y_test=test.y, engine="flat")
        _, ha = _run_sim(cfg, hp, het, fed, params, 2,
                         x_test=test.x, y_test=test.y, engine="async",
                         async_cfg=AsyncConfig(**SYNC_LIMIT))
        np.testing.assert_allclose(hf["acc"], ha["acc"], atol=2e-3)


class TestLateMerges:
    def _run_rounds(self, small_fed, het, acfg, n_rounds=3):
        from repro.core.baselines import h2fed
        from repro.fedsim.async_engine import (init_async_state,
                                               make_async_global_round)
        from repro.fedsim.simulator import SimConfig
        fed, _, params = small_fed
        cfg = SimConfig(n_agents=fed.n_agents, n_rsus=4, batch=16, seed=0)
        hp = h2fed(mu1=0.01, mu2=0.005, lar=2, lr=0.1)
        spec = flatten.spec_of(params)
        round_fn = make_async_global_round(cfg, hp, het, fed, spec, acfg)
        state = init_async_state(cfg, spec, params, jax.random.key(0))
        per_round = []
        for _ in range(n_rounds):
            state, metrics = round_fn(state)
            per_round.append({k: np.asarray(v) for k, v in metrics.items()})
        return state, per_round

    def test_cohort_mass_conservation(self, small_fed):
        """Every enqueued in-flight weight is absorbed exactly once (or is
        still pending at the end): Σ enqueued − Σ due == pending_end, and
        per tick absorbed == immediate + due."""
        from repro.fedsim.async_engine import AsyncConfig
        het = HeterogeneityModel(csr=0.8, max_delay=3, delay_p=0.6)
        acfg = AsyncConfig(staleness_decay=0.5, buffer_keep=0.4)
        state, rounds = self._run_rounds(small_fed, het, acfg, n_rounds=4)
        enq = sum(r["enqueued_mass"].sum() for r in rounds)
        due = sum(r["due_mass"].sum() for r in rounds)
        pend_end = float(rounds[-1]["pending_mass"])
        np.testing.assert_allclose(enq - due, pend_end, rtol=1e-5)
        for r in rounds:
            np.testing.assert_allclose(
                r["absorbed_mass"].sum(axis=1),
                r["immediate_mass"] + r["due_mass"], rtol=1e-5)
        # late merges actually happened in this configuration
        assert due > 0

    def test_all_agents_stale(self, small_fed):
        """delay_p=1 pins every arrival at max_delay: no tick ever sees a
        fresh update, yet the buffers absorb the stale cohort and stay
        finite (the all-agents-stale edge case)."""
        from repro.fedsim.async_engine import AsyncConfig
        het = HeterogeneityModel(csr=1.0, max_delay=2, delay_p=1.0)
        acfg = AsyncConfig(staleness_decay=0.5, buffer_keep=0.5)
        state, rounds = self._run_rounds(small_fed, het, acfg, n_rounds=3)
        for r in rounds:
            np.testing.assert_array_equal(r["immediate_mass"], 0.0)
        assert sum(r["due_mass"].sum() for r in rounds) > 0
        assert np.isfinite(np.asarray(state.cloud_flat)).all()
        assert np.isfinite(np.asarray(state.rsu_flat)).all()

    def test_decay_downweights_stragglers(self, small_fed):
        """Stronger decay => strictly less absorbed straggler mass."""
        from repro.fedsim.async_engine import AsyncConfig
        het = HeterogeneityModel(csr=1.0, max_delay=2, delay_p=1.0)
        _, soft = self._run_rounds(
            small_fed, het, AsyncConfig(staleness_decay=1.0), n_rounds=2)
        _, hard = self._run_rounds(
            small_fed, het, AsyncConfig(staleness_decay=0.25), n_rounds=2)
        m_soft = sum(r["due_mass"].sum() for r in soft)
        m_hard = sum(r["due_mass"].sum() for r in hard)
        assert m_hard < m_soft
        np.testing.assert_allclose(m_hard, m_soft * 0.25 ** 2, rtol=1e-5)


class TestCloudCadence:
    """Satellite: the cloud cadence is decoupled from the LAR scan — a
    global tick counter carried in the state lets ``cloud_every`` span
    global-round boundaries (cloud_every=0 keeps the per-round anchor)."""

    def _round_fn(self, small_fed, acfg, het=None):
        from repro.core.baselines import h2fed
        from repro.fedsim.async_engine import (init_async_state,
                                               make_async_global_round)
        from repro.fedsim.simulator import SimConfig
        fed, _, params = small_fed
        cfg = SimConfig(n_agents=fed.n_agents, n_rsus=4, batch=16, seed=0)
        hp = h2fed(mu1=0.01, mu2=0.005, lar=2, lr=0.1)
        het = het or HeterogeneityModel(csr=0.8, lar=hp.lar,
                                        max_delay=2, delay_p=0.5)
        spec = flatten.spec_of(params)
        rf = make_async_global_round(cfg, hp, het, fed, spec, acfg)
        return rf, init_async_state(cfg, spec, params, jax.random.key(0)), hp

    def test_tick_counter_advances(self, small_fed):
        from repro.fedsim.async_engine import AsyncConfig
        rf, state, hp = self._round_fn(small_fed, AsyncConfig(**SYNC_LIMIT))
        for _ in range(3):
            state, _ = rf(state)
        assert int(state.tick) == 3 * hp.lar

    def test_cadence_spans_rounds(self, small_fed):
        """cloud_every beyond the total tick budget: the cloud model is
        never aggregated (no forced round-end aggregation) and the mass
        accumulator carries across rounds."""
        from repro.fedsim.async_engine import AsyncConfig
        rf, state, _ = self._round_fn(small_fed,
                                      AsyncConfig(cloud_every=1000))
        v0 = np.asarray(state.cloud_flat).copy()
        for _ in range(2):
            state, _ = rf(state)
        np.testing.assert_array_equal(np.asarray(state.cloud_flat), v0)
        assert float(jnp.sum(state.cloud_macc)) > 0

    def test_cadence_fires_mid_round(self, small_fed):
        """cloud_every=3 with LAR=2 fires at global tick 3 — inside the
        SECOND round, impossible under the old round-bounded gate."""
        from repro.fedsim.async_engine import AsyncConfig
        rf, state, _ = self._round_fn(small_fed, AsyncConfig(cloud_every=3))
        v0 = np.asarray(state.cloud_flat).copy()
        state, _ = rf(state)                     # ticks 1, 2: no fire
        np.testing.assert_array_equal(np.asarray(state.cloud_flat), v0)
        state, _ = rf(state)                     # tick 3 fires
        assert not np.array_equal(np.asarray(state.cloud_flat), v0)


class TestPerRsuStaleness:
    """Satellite: (R,)-vector decay/keep schedules (scalar broadcast keeps
    the uniform behavior exactly)."""

    def test_staleness_weights_vector_decay(self):
        tau = jnp.asarray([0, 1, 2, 3])
        dec = jnp.asarray([1.0, 0.5, 0.5, 0.25])
        s = np.asarray(staleness_weights(tau, decay=dec, schedule="exp"))
        np.testing.assert_allclose(s, [1.0, 0.5, 0.25, 0.25 ** 3])

    def test_buffer_absorb_vector_keep(self):
        rng = np.random.default_rng(0)
        R, N = 3, 7
        buf = jnp.asarray(rng.standard_normal((R, N)), F32)
        M = jnp.asarray(rng.uniform(1, 3, R), F32)
        num = jnp.asarray(rng.standard_normal((R, N)), F32)
        m = jnp.asarray(rng.uniform(0.5, 2, R), F32)
        keep = jnp.asarray([0.0, 0.5, 1.0], F32)
        out_v, M_v = buffer_absorb(buf, M, num, m, keep=keep)
        for r, k in enumerate([0.0, 0.5, 1.0]):
            out_s, M_s = buffer_absorb(buf[r:r + 1], M[r:r + 1],
                                       num[r:r + 1], m[r:r + 1], keep=k)
            np.testing.assert_allclose(np.asarray(out_v)[r],
                                       np.asarray(out_s)[0], rtol=1e-6)
            np.testing.assert_allclose(np.asarray(M_v)[r],
                                       np.asarray(M_s)[0], rtol=1e-6)

    def test_uniform_vector_matches_scalar_engine(self, small_fed):
        from repro.core.baselines import h2fed
        from repro.fedsim.async_engine import AsyncConfig
        from repro.fedsim.simulator import SimConfig
        fed, test, params = small_fed
        cfg = SimConfig(n_agents=fed.n_agents, n_rsus=4, batch=16, seed=0)
        hp = h2fed(mu1=0.01, mu2=0.005, lar=2, lr=0.1)
        het = HeterogeneityModel(csr=0.8, lar=hp.lar, max_delay=2,
                                 delay_p=0.5)
        _, h_s = _run_sim(cfg, hp, het, fed, params, 2,
                          x_test=test.x, y_test=test.y, engine="async",
                          async_cfg=AsyncConfig(staleness_decay=0.5))
        _, h_v = _run_sim(cfg, hp, het, fed, params, 2,
                          x_test=test.x, y_test=test.y, engine="async",
                          async_cfg=AsyncConfig(
                              staleness_decay=(0.5,) * 4))
        np.testing.assert_array_equal(h_s["acc"], h_v["acc"])
        np.testing.assert_array_equal(h_s["absorbed_mass"],
                                      h_v["absorbed_mass"])

    def test_vector_decay_targets_one_rsu(self, small_fed):
        """All-stale regime: halving one RSU's decay rate scales ONLY that
        RSU's absorbed straggler mass (delays pinned at max_delay=2 →
        factor decay^2)."""
        from repro.core.baselines import h2fed
        from repro.fedsim.async_engine import (AsyncConfig,
                                               init_async_state,
                                               make_async_global_round)
        from repro.fedsim.simulator import SimConfig
        fed, _, params = small_fed
        cfg = SimConfig(n_agents=fed.n_agents, n_rsus=4, batch=16, seed=0)
        hp = h2fed(mu1=0.01, mu2=0.005, lar=2, lr=0.1)
        het = HeterogeneityModel(csr=1.0, max_delay=2, delay_p=1.0)
        spec = flatten.spec_of(params)

        def absorbed(decay):
            rf = make_async_global_round(cfg, hp, het, fed, spec,
                                         AsyncConfig(staleness_decay=decay))
            state = init_async_state(cfg, spec, params, jax.random.key(0))
            tot = np.zeros((4,))
            for _ in range(3):
                state, m = rf(state)
                tot += np.asarray(m["absorbed_mass"]).sum(axis=0)
            return tot

        base = absorbed(1.0)
        tgt = absorbed((0.5, 1.0, 1.0, 1.0))
        np.testing.assert_allclose(tgt[0], base[0] * 0.25, rtol=1e-5)
        np.testing.assert_allclose(tgt[1:], base[1:], rtol=1e-5)

    def test_wrong_length_vector_raises(self, small_fed):
        from repro.fedsim.async_engine import AsyncConfig
        acfg = AsyncConfig(staleness_decay=(0.5, 0.5)).validate()
        with pytest.raises(ValueError, match="one entry per RSU"):
            acfg.agent_decay(jnp.zeros((8,), jnp.int32), n_rsus=4)


class TestBufferDonation:
    """The ROADMAP donation item: FlatSimState buffers are donated through
    the round jit, so the (A, N) update is in-place — verified via the
    dry-run HLO alias analysis (no-copy shows as input_output_alias)."""

    def _flat_round(self, small_fed):
        from repro.core.baselines import h2fed
        from repro.fedsim.simulator import (SimConfig, init_flat_state,
                                            make_flat_global_round)
        fed, _, params = small_fed
        cfg = SimConfig(n_agents=fed.n_agents, n_rsus=4, batch=16, seed=0)
        hp = h2fed(mu1=0.01, mu2=0.005, lar=1, lr=0.1)
        het = HeterogeneityModel(csr=0.8)
        spec = flatten.spec_of(params)
        round_fn = make_flat_global_round(cfg, hp, het, fed, spec)
        state = init_flat_state(cfg, spec, params, jax.random.key(0))
        return round_fn, state, cfg, spec

    def test_flat_round_aliases_fleet_buffers(self, small_fed):
        from repro.launch import hlo_analysis as H
        round_fn, state, cfg, spec = self._flat_round(small_fed)
        txt = round_fn.lower(state).compile().as_text()
        donated = H.donated_params(txt)
        assert donated, "no input_output_alias: donation was dropped"
        shapes = H.param_shapes(txt)
        a_n = f"f32[{cfg.n_agents},{spec.n}]"
        assert any(a_n in shapes.get(p, "") for p in donated), \
            (donated, {p: shapes.get(p) for p in donated})

    def test_donated_state_is_consumed(self, small_fed):
        """Donation is real: the input state's buffers are invalidated, so
        reuse must fail loudly rather than silently read stale memory."""
        round_fn, state, _, _ = self._flat_round(small_fed)
        out = round_fn(state)
        jax.block_until_ready(out.cloud_flat)
        with pytest.raises(RuntimeError, match="deleted|donated"):
            _ = float(jnp.sum(state.agent_flat))

    def test_donated_params_parser(self):
        """The alias parser on a minimal donated jit + a non-donated one."""
        from repro.launch import hlo_analysis as H

        def f(s):
            return {"a": s["a"] * 2.0, "b": s["b"] + 1.0}

        arg = {"a": jnp.ones((8, 16)), "b": jnp.zeros((4,))}
        txt_d = jax.jit(f, donate_argnums=(0,)).lower(arg).compile().as_text()
        assert len(H.donated_params(txt_d)) >= 1
        txt_n = jax.jit(f).lower(arg).compile().as_text()
        assert H.donated_params(txt_n) == []


CODE_ASYNC_8DEV = """
import jax, numpy as np
from repro.configs.mnist_mlp import CONFIG as MLP_CFG
from repro.core.baselines import h2fed
from repro.core.heterogeneity import HeterogeneityModel
from repro.data.partition import scenario_two
from repro.data.synthetic import mnist_class_task
from repro.fedsim.async_engine import AsyncConfig
from repro.fedsim.simulator import SimConfig
from repro.fedsim.sweep import adhoc_scenario, run_scenario
from repro.models import mlp

def run(cfg, hp, het, fed, params, rounds, **kw):
    return run_scenario(adhoc_scenario(cfg, hp, het, fed, n_rounds=rounds,
                                       x_test=test.x, y_test=test.y, **kw),
                        params)

assert len(jax.devices()) == 8, len(jax.devices())
train, test = mnist_class_task(n_train=2000, n_test=400, seed=0)
fed = scenario_two(train, n_agents=8, n_rsus=4, seed=0)
params = mlp.init_params(MLP_CFG, jax.random.key(0))
cfg = SimConfig(n_agents=8, n_rsus=4, batch=16, seed=0)
hp = h2fed(mu1=0.01, mu2=0.005, lar=2, lr=0.1)
het = HeterogeneityModel(csr=0.6, lar=hp.lar)
_, hf = run(cfg, hp, het, fed, params, 2, engine="flat")
_, ha = run(cfg, hp, het, fed, params, 2, engine="async",
            async_cfg=AsyncConfig(staleness_decay=1.0, buffer_keep=0.0))
np.testing.assert_allclose(hf["acc"], ha["acc"], atol=2e-3)
het_d = HeterogeneityModel(csr=0.6, lar=hp.lar, max_delay=2, delay_p=0.5)
_, hd = run(cfg, hp, het_d, fed, params, 2, engine="async")
assert np.isfinite(hd["acc"]).all()
print("async-8dev-ok")
"""

CODE_SPMD_ASYNC = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from repro.launch.h2fed_round import make_h2fed_round
from repro.core.h2fed import H2FedParams
from repro.configs.registry import get_reduced_config
from repro.models import model as M

mesh = make_test_mesh((2, 4, 1))
cfg = get_reduced_config('qwen3-0.6b', n_layers=2, d_model=128, d_ff=256,
                         vocab_size=128, n_heads=4, n_kv_heads=2)
hp = H2FedParams(mu1=0.05, mu2=0.01, lar=2, local_epochs=1, lr=0.1)
A, b, S = 8, 2, 16
rng = np.random.default_rng(0)
params = M.init_params(cfg, jax.random.key(0))
batch = {'tokens': jnp.asarray(rng.integers(0, 128, (hp.lar, A, b, S)), jnp.int32),
         'labels': jnp.asarray(rng.integers(0, 128, (hp.lar, A, b, S)), jnp.int32)}
mask = jnp.asarray(rng.integers(0, 2, (hp.lar, A)), jnp.float32)
mask = mask.at[:, 0].set(1.0)
n_data = jnp.asarray(rng.uniform(1, 3, (A,)), jnp.float32)
zeros_d = jnp.zeros((hp.lar, A), jnp.int32)
with mesh:
    o_s, m_s = jax.jit(make_h2fed_round(cfg, hp, mesh, flat_agg=True))(
        params, batch, mask, n_data)
    o_a, m_a = jax.jit(make_h2fed_round(cfg, hp, mesh, flat_agg=True,
                                        async_rounds=2))(
        params, batch, mask, n_data, zeros_d)
    for x, y in zip(jax.tree.leaves(o_s), jax.tree.leaves(o_a)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=1e-6)
    assert float(m_s['surviving_mass']) == float(m_a['surviving_mass'])
    # stale regime runs and absorbs less-than-sync mass
    delays = jnp.asarray(rng.integers(0, 3, (hp.lar, A)), jnp.int32)
    o_d, m_d = jax.jit(make_h2fed_round(cfg, hp, mesh, flat_agg=True,
                                        async_rounds=2, buffer_keep=0.5))(
        params, batch, mask, n_data, delays)
    assert float(m_d['surviving_mass']) <= float(m_s['surviving_mass'])
    assert all(np.isfinite(np.asarray(l, np.float32)).all()
               for l in jax.tree.leaves(o_d))
    # per-pod (== per-RSU) decay vector: uniform vector == scalar exactly
    o_v, m_v = jax.jit(make_h2fed_round(cfg, hp, mesh, flat_agg=True,
                                        async_rounds=2, buffer_keep=0.5,
                                        staleness_decay=(0.5, 0.5)))(
        params, batch, mask, n_data, delays)
    for x, y in zip(jax.tree.leaves(o_d), jax.tree.leaves(o_v)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=1e-7)
print("spmd-async-ok")
"""


CODE_RSU_SHARDED_ASYNC = """
import jax, numpy as np
from repro.configs.mnist_mlp import CONFIG as MLP_CFG
from repro.core.baselines import h2fed
from repro.core.heterogeneity import HeterogeneityModel
from repro.data.partition import scenario_two
from repro.data.synthetic import mnist_class_task
from repro.fedsim.async_engine import AsyncConfig
from repro.fedsim.sharded import make_fleet_mesh, resolve_topology
from repro.fedsim.simulator import SimConfig
from repro.fedsim.sweep import adhoc_scenario, run_scenario
from repro.models import mlp

assert len(jax.devices()) == 8, len(jax.devices())
train, test = mnist_class_task(n_train=1000, n_test=200, seed=0)
fed = scenario_two(train, n_agents=8, n_rsus=4, seed=0)
params = mlp.init_params(MLP_CFG, jax.random.key(0))
cfg = SimConfig(n_agents=8, n_rsus=4, batch=16, seed=0)
hp = h2fed(mu1=0.05, mu2=0.01, lar=2, lr=0.1)
mesh = make_fleet_mesh(8, n_pods=2)
topo = resolve_topology(cfg, fed, mesh, rsu_sharded=True)

def run(het, rounds, *, topo=None, **kw):
    return run_scenario(adhoc_scenario(cfg, hp, het, fed, n_rounds=rounds,
                                       x_test=test.x, y_test=test.y, **kw),
                        params, topo=topo)

# sync-limit anchor: RSU-sharded async == flat
het = HeterogeneityModel(csr=0.6, lar=hp.lar)
_, hf = run(het, 2, engine="flat")
_, hs = run(het, 2, engine="async", topo=topo,
            async_cfg=AsyncConfig(staleness_decay=1.0, buffer_keep=0.0))
np.testing.assert_allclose(hf["acc"], hs["acc"], atol=2e-3)

# delayed regime: RSU-sharded == replicated async (same draws, same
# staleness algebra, block-local merge)
het_d = HeterogeneityModel(csr=0.8, lar=hp.lar, max_delay=2, delay_p=0.5)
acfg = AsyncConfig(staleness_decay=0.5, buffer_keep=0.4, cloud_every=3)
_, hu = run(het_d, 2, engine="async", async_cfg=acfg)
_, hq = run(het_d, 2, engine="async", topo=topo, async_cfg=acfg)
np.testing.assert_allclose(hu["acc"], hq["acc"], atol=2e-3)
np.testing.assert_allclose(hu["absorbed_mass"], hq["absorbed_mass"],
                           rtol=1e-5)
np.testing.assert_allclose(hu["pending_mass"], hq["pending_mass"],
                           rtol=1e-5)
print("rsu-sharded-async-ok")
"""


class TestMultiDevice:
    def test_async_engine_on_8_devices(self, forced_devices_run):
        out = forced_devices_run(CODE_ASYNC_8DEV, devices=8, timeout=900)
        assert "async-8dev-ok" in out

    def test_spmd_async_round_on_8_devices(self, forced_devices_run):
        """launch/h2fed_round --async-rounds on a 2x4x1 pod/data mesh: the
        zero-delay limit equals the synchronous flat_agg program."""
        out = forced_devices_run(CODE_SPMD_ASYNC, devices=8, timeout=900)
        assert "spmd-async-ok" in out

    def test_rsu_sharded_async_on_8_devices(self, forced_devices_run):
        """The semi-async tick loop on an RSU-sharded 2x4 topology: the
        buffer merge runs on the local (R_local, N) shard, yet matches the
        flat sync anchor and the replicated async engine exactly."""
        out = forced_devices_run(CODE_RSU_SHARDED_ASYNC, devices=8,
                                 timeout=900)
        assert "rsu-sharded-async-ok" in out
