"""Flat-buffer engine tests (DESIGN.md §3): ravel/unravel round-trips and
numerical equivalence of the flat Pallas aggregation path against the
tree-map reference (core/aggregation) over random masks/weights, including
the all-agents-dropped and empty-cohort edge cases."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from prop_compat import given, settings, st

from repro.core import flatten
from repro.core.aggregation import (blend_on_mass, masked_weighted_mean,
                                    rsu_aggregate)
from repro.kernels import ops
from repro.kernels.masked_hier_agg import cloud_agg, masked_hier_agg

F32 = np.float32


def _tree(seed, a=None, bf16=False):
    """Random MLP-shaped pytree; leading fleet axis when ``a`` is given."""
    rng = np.random.default_rng(seed)
    lead = () if a is None else (a,)
    t = {"w0": rng.standard_normal(lead + (7, 4)).astype(F32),
         "b0": rng.standard_normal(lead + (4,)).astype(F32),
         "nested": {"w1": rng.standard_normal(lead + (4, 3)).astype(F32),
                    "b1": rng.standard_normal(lead + (3,)).astype(F32)}}
    t = jax.tree.map(jnp.asarray, t)
    if bf16:
        t["nested"]["w1"] = t["nested"]["w1"].astype(jnp.bfloat16)
    return t


class TestRoundTrip:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_ravel_unravel_identity(self, seed):
        t = _tree(seed)
        spec = flatten.spec_of(t)
        vec = spec.ravel(t)
        assert vec.shape == (spec.n,) and vec.dtype == jnp.float32
        back = spec.unravel(vec)
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 1000), a=st.integers(1, 9))
    def test_stacked_round_trip(self, seed, a):
        t = _tree(seed, a=a)
        spec = flatten.spec_of_stacked(t)
        mat = spec.ravel_stacked(t)
        assert mat.shape == (a, spec.n)
        back = spec.unravel_stacked(mat)
        for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_bf16_dtype_preserved(self):
        t = _tree(0, bf16=True)
        spec = flatten.spec_of(t)
        back = spec.unravel(spec.ravel(t))
        assert back["nested"]["w1"].dtype == jnp.bfloat16

    def test_spec_consistency_between_variants(self):
        """spec_of(template) and spec_of_stacked(broadcast) agree, so flat
        states can be built from either view."""
        t = _tree(3)
        stacked = jax.tree.map(
            lambda l: jnp.broadcast_to(l, (5,) + l.shape), t)
        s1, s2 = flatten.spec_of(t), flatten.spec_of_stacked(stacked)
        assert s1.n == s2.n and s1.shapes == s2.shapes
        row = s2.ravel_stacked(stacked)[2]
        np.testing.assert_array_equal(np.asarray(row),
                                      np.asarray(s1.ravel(t)))

    def test_grad_flows_through_unravel(self):
        """d/dvec of a loss on the unraveled tree == raveled per-leaf grad —
        the identity the flat training loop relies on."""
        t = _tree(7)
        spec = flatten.spec_of(t)
        vec = spec.ravel(t)

        def loss_vec(v):
            tr = spec.unravel(v)
            return sum(jnp.sum(l ** 2) for l in jax.tree.leaves(tr))

        def loss_tree(tr):
            return sum(jnp.sum(l ** 2) for l in jax.tree.leaves(tr))

        g_vec = jax.grad(loss_vec)(vec)
        g_tree = spec.ravel(jax.grad(loss_tree)(t))
        np.testing.assert_allclose(np.asarray(g_vec), np.asarray(g_tree),
                                   atol=1e-6)


class TestFlatAggEquivalence:
    """The flat Pallas path == tree-map reference to fp32 tolerance."""

    def _setup(self, seed, A=12, R=3, csr=0.5):
        rng = np.random.default_rng(seed)
        tree = _tree(seed, a=A)
        wts = jnp.asarray(rng.uniform(1, 5, A), F32)
        mask = jnp.asarray((rng.random(A) < csr), F32)
        assign = jnp.asarray(rng.integers(0, R, A), jnp.int32)
        return tree, wts, mask, assign

    def _check(self, tree, wts, mask, assign, R):
        spec = flatten.spec_of_stacked(tree)
        flat = spec.ravel_stacked(tree)

        tree_out, tree_mass = rsu_aggregate(tree, wts, mask, assign, R)
        for flat_out, flat_mass in (
                masked_hier_agg(flat, wts, mask, assign, R, interpret=True),
                ops.masked_hier_agg(flat, wts, mask, assign, R)):
            np.testing.assert_allclose(np.asarray(flat_mass),
                                       np.asarray(tree_mass), rtol=1e-6)
            rec = spec.unravel_stacked(flat_out)
            live = np.asarray(tree_mass) > 0
            for a, b in zip(jax.tree.leaves(rec), jax.tree.leaves(tree_out)):
                np.testing.assert_allclose(
                    np.asarray(a, F32)[live], np.asarray(b, F32)[live],
                    atol=2e-5)
        return tree_mass

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_rsu_layer_matches(self, seed):
        tree, wts, mask, assign = self._setup(seed)
        self._check(tree, wts, mask, assign, R=3)

    def test_all_agents_dropped(self):
        """CSR=0: zero mass everywhere; blend keeps the old model on every
        RSU in both formulations."""
        tree, wts, _, assign = self._setup(0, csr=1.0)
        mask = jnp.zeros(12, F32)
        mass = self._check(tree, wts, mask, assign, R=3)
        assert float(jnp.sum(mass)) == 0.0
        old = _tree(99, a=3)
        out, m = rsu_aggregate(tree, wts, mask, assign, 3)
        kept = blend_on_mass(out, old, m)
        for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(old)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_empty_cohort(self):
        """An RSU with no assigned agents gets zero mass and an all-zero
        row from both paths."""
        tree, wts, mask, _ = self._setup(1)
        assign = jnp.asarray([0, 1] * 6, jnp.int32)      # RSU 2 empty
        mass = self._check(tree, wts, mask, assign, R=3)
        assert float(mass[2]) == 0.0
        spec = flatten.spec_of_stacked(tree)
        flat_out, _ = masked_hier_agg(spec.ravel_stacked(tree), wts, mask,
                                      assign, 3, interpret=True)
        np.testing.assert_array_equal(np.asarray(flat_out)[2], 0.0)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 500))
    def test_cloud_layer_matches(self, seed):
        rng = np.random.default_rng(seed)
        tree = _tree(seed, a=5)
        wts = jnp.asarray(rng.uniform(0, 3, 5), F32)
        spec = flatten.spec_of_stacked(tree)
        flat = spec.ravel_stacked(tree)
        tree_out = masked_weighted_mean(tree, wts)
        for vec in (cloud_agg(flat, wts, interpret=True),
                    ops.cloud_agg(flat, wts)):
            rec = spec.unravel(vec)
            for a, b in zip(jax.tree.leaves(rec), jax.tree.leaves(tree_out)):
                np.testing.assert_allclose(np.asarray(a, F32),
                                           np.asarray(b, F32), atol=2e-5)


class TestEngineEquivalence:
    """run_scenario(engine='flat') == engine='tree' end to end."""

    @pytest.fixture(scope="class")
    def small_sim(self, tiny_task, fed_small):
        from repro.configs.mnist_mlp import CONFIG as MLP_CFG
        from repro.models import mlp
        train, test = tiny_task
        params = mlp.init_params(MLP_CFG, jax.random.key(0))
        return fed_small, test, params

    def test_flat_matches_tree_engine(self, small_sim):
        from repro.core.baselines import h2fed
        from repro.core.heterogeneity import HeterogeneityModel
        from repro.fedsim.simulator import SimConfig
        from repro.fedsim.sweep import adhoc_scenario, run_scenario
        fed, test, params = small_sim
        cfg = SimConfig(n_agents=fed.n_agents, n_rsus=4, batch=16, seed=0)
        hp = h2fed(mu1=0.05, mu2=0.01, lar=2, lr=0.1)
        het = HeterogeneityModel(csr=0.6, lar=hp.lar)

        def run(engine):
            res = adhoc_scenario(cfg, hp, het, fed, n_rounds=3,
                                 x_test=test.x, y_test=test.y, engine=engine)
            return run_scenario(res, params)

        sf, hf = run("flat")
        st, ht = run("tree")
        np.testing.assert_allclose(hf["acc"], ht["acc"], atol=2e-3)
        for a, b in zip(jax.tree.leaves(sf.cloud_params),
                        jax.tree.leaves(st.cloud_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_make_global_round_engines_agree(self, small_sim):
        from repro.core.baselines import h2fed
        from repro.core.heterogeneity import HeterogeneityModel
        from repro.fedsim.simulator import (SimConfig, init_state,
                                            make_global_round)
        fed, _, params = small_sim
        cfg = SimConfig(n_agents=fed.n_agents, n_rsus=4, batch=16, seed=0)
        hp = h2fed(mu1=0.01, mu2=0.005, lar=1, lr=0.05)
        het = HeterogeneityModel(csr=1.0)
        state = init_state(cfg, params, jax.random.key(0))
        out_f = make_global_round(cfg, hp, het, fed, engine="flat")(state)
        out_t = make_global_round(cfg, hp, het, fed, engine="tree")(state)
        for a, b in zip(jax.tree.leaves(out_f.cloud_params),
                        jax.tree.leaves(out_t.cloud_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_unknown_engine_raises(self, small_sim):
        from repro.core.baselines import h2fed
        from repro.core.heterogeneity import HeterogeneityModel
        from repro.fedsim.simulator import SimConfig, make_global_round
        fed, _, params = small_sim
        cfg = SimConfig(n_agents=fed.n_agents, n_rsus=4)
        with pytest.raises(ValueError):
            make_global_round(cfg, h2fed(), HeterogeneityModel(), fed,
                              engine="nope")


class TestLeafCarryTraining:
    """``simulator._local_train_flat`` takes and returns flat rows, but its
    minibatch scan carries the model's leaves: the flat (N,) carry made
    every step relayout the 784x40 weight on a TPU."""

    A, SAMPLES, BATCH = 4, 12, 4          # three minibatches an epoch

    @pytest.fixture(scope="class")
    def paper_mlp(self):
        from repro.configs.mnist_mlp import CONFIG as MLP_CFG
        from repro.models import mlp
        params = mlp.init_params(MLP_CFG, jax.random.key(3))
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.uniform(0, 1.5, (self.A, self.SAMPLES, 784)), F32)
        y = jnp.asarray(rng.integers(0, 10, (self.A, self.SAMPLES)))
        return mlp.loss_fn, params, x, y

    def _train(self, loss_fn, spec, x, y, w_start, w_cloud, n_steps, act):
        from repro.core.baselines import h2fed
        from repro.fedsim.simulator import _local_train_flat
        hp = h2fed(mu1=0.05, mu2=0.02, lar=2, lr=0.1)
        train = jax.vmap(
            lambda x, y, w0, wr, wc, a: _local_train_flat(
                loss_fn, spec, x, y, w0, wr, wc, hp, n_steps, a,
                self.BATCH),
            in_axes=(0, 0, 0, 0, None, 0))
        return hp, train, (x, y, w_start, w_start, w_cloud, act)

    @pytest.mark.parametrize("storage,leaf", [
        ("float32", "float32"), ("bfloat16", "float32"),
        ("float32", "bfloat16")],
        ids=["f32", "bf16_storage", "bf16_leaf"])
    @pytest.mark.parametrize("n_steps", [1, 3])
    def test_matches_per_leaf_reference(self, paper_mlp, n_steps, storage,
                                        leaf):
        """Bit for bit on the CPU against a per-leaf proximal-SGD scan on
        fp32 leaves, with one agent at 0 steps, one partial and the rest
        full.  bf16 storage rows widen to fp32 at entry; a bf16 leaf of
        the template is trained in fp32 and cast only where the loss
        reads it."""
        from repro.data.pipeline import agent_minibatch
        loss_fn, params, x, y = paper_mlp
        params = dict(params, w1=params["w1"].astype(leaf))
        spec = flatten.spec_of(params, storage_dtype=storage)
        rng = np.random.default_rng(n_steps)
        rows = jnp.asarray(rng.standard_normal((self.A, spec.n)) * 0.1, F32)
        w_start = spec.to_storage(rows)
        w_cloud = jnp.asarray(rng.standard_normal(spec.n) * 0.1, F32)
        act = jnp.asarray([0, 1, n_steps, n_steps], jnp.int32)
        hp, train, args = self._train(loss_fn, spec, x, y, w_start, w_cloud,
                                      n_steps, act)
        got = jax.jit(train)(*args)
        assert got.shape == (self.A, spec.n) and got.dtype == jnp.float32

        def fp32_leaves(row):
            return jax.tree_util.tree_unflatten(spec.treedef, [
                row[o:o + n].astype(F32).reshape(s) for o, n, s in
                zip(spec.offsets, spec.sizes, spec.shapes)])

        grad = jax.grad(lambda w, xb, yb: loss_fn(
            dict(w, w1=w["w1"].astype(leaf)), xb, yb))
        cloud = fp32_leaves(w_cloud)

        def one_agent(x, y, row, a):
            start = fp32_leaves(row)

            def step(w, s):
                g = grad(w, *agent_minibatch(x, y, s, self.BATCH))
                live = (s < a).astype(jnp.float32)
                return jax.tree.map(
                    lambda wl, gl, r, c: wl - hp.lr * live * (
                        gl + hp.mu1 * (wl - r) + hp.mu2 * (wl - c)),
                    w, g, start, cloud), None

            w, _ = jax.lax.scan(step, start, jnp.arange(n_steps))
            return jnp.concatenate([l.reshape(-1)
                                    for l in jax.tree.leaves(w)])

        want = jax.jit(jax.vmap(one_agent))(x, y, w_start, act)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # an agent with no completed step hands back its (widened) start
        np.testing.assert_array_equal(
            np.asarray(got[0]), np.asarray(w_start[0].astype(jnp.float32)))
        assert not np.array_equal(np.asarray(got[1]),
                                  np.asarray(w_start[1].astype(jnp.float32)))

    def test_padded_tail_passes_through(self, paper_mlp):
        """N-sharded and N-tiled engines pass rows padded past ``spec.n``
        (tails equal across start rows and cloud): the model's columns
        train as unpadded rows do, and the tail comes back unchanged."""
        loss_fn, params, x, y = paper_mlp
        spec = flatten.spec_of(params)
        rng = np.random.default_rng(5)
        rows = jnp.asarray(rng.standard_normal((self.A, spec.n)) * 0.1, F32)
        cloud = jnp.asarray(rng.standard_normal(spec.n) * 0.1, F32)
        tail = jnp.asarray(rng.standard_normal(190), F32)
        act = jnp.asarray([0, 1, 3, 3], jnp.int32)
        _, train, args = self._train(loss_fn, spec, x, y, rows, cloud, 3, act)
        want = jax.jit(train)(*args)
        tails = jnp.broadcast_to(tail, (self.A, 190))
        padded = (jnp.concatenate([rows, tails], axis=1),
                  jnp.concatenate([cloud, tail]))
        _, train, args = self._train(loss_fn, spec, x, y, *padded, 3, act)
        got = jax.jit(train)(*args)
        assert got.shape == (self.A, spec.n + 190)
        np.testing.assert_array_equal(np.asarray(got[:, :spec.n]),
                                      np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got[:, spec.n:]),
                                      np.asarray(tails))

    def test_scan_carries_leaves_not_the_flat_row(self, paper_mlp):
        """The minibatch scan's carry is the (A, 784, 40) weight and its
        siblings; no (A, N) vector is carried from step to step."""
        from jax.extend.core import ClosedJaxpr, Jaxpr
        loss_fn, params, x, y = paper_mlp
        spec = flatten.spec_of(params)
        w_start = jnp.zeros((self.A, spec.n), F32)
        _, train, args = self._train(loss_fn, spec, x, y, w_start,
                                     jnp.zeros((spec.n,), F32), 3,
                                     jnp.full((self.A,), 3, jnp.int32))

        def scan_carries(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "scan":
                    k, c = eqn.params["num_consts"], eqn.params["num_carry"]
                    yield sorted(tuple(v.aval.shape)
                                 for v in eqn.invars[k:k + c])
                for p in eqn.params.values():
                    for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                        if isinstance(sub, ClosedJaxpr):
                            yield from scan_carries(sub.jaxpr)
                        elif isinstance(sub, Jaxpr):
                            yield from scan_carries(sub)

        carries = list(scan_carries(jax.make_jaxpr(train)(*args).jaxpr))
        leaves = sorted((self.A,) + s for s in spec.shapes)
        assert (self.A, 784, 40) in leaves
        assert carries == [leaves]
