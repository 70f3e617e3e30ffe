"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.dual_proximal_sgd import dual_proximal_sgd, \
    dual_proximal_sgd_tree
from repro.kernels.flash_attention import flash_attention
from repro.kernels.masked_hier_agg import (build_weight_matrix, cloud_agg,
                                           masked_hier_agg,
                                           weighted_agg_matmul)

INTERP = dict(interpret=True)


def _rand(shape, dtype, seed, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) * scale).astype(dtype)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

ATTN_SWEEP = [
    # (B, S, H, KV, D, window, causal)
    (1, 64, 2, 2, 32, 0, True),        # MHA
    (2, 128, 4, 2, 64, 0, True),       # GQA 2:1
    (1, 100, 8, 2, 64, 0, True),       # ragged S (padding path)
    (1, 128, 4, 1, 64, 0, True),       # MQA
    (2, 96, 4, 2, 32, 40, True),       # sliding window
    (1, 80, 2, 2, 32, 16, True),       # small window, ragged
    (1, 64, 2, 2, 32, 0, False),       # non-causal (cross-attn style)
]


@pytest.mark.parametrize("B,S,H,KV,D,window,causal", ATTN_SWEEP)
def test_flash_attention_matches_ref(B, S, H, KV, D, window, causal):
    q = _rand((B, S, H, D), jnp.float32, 0)
    k = _rand((B, S, KV, D), jnp.float32, 1)
    v = _rand((B, S, KV, D), jnp.float32, 2)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_k=32, **INTERP)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, atol):
    q = _rand((1, 64, 4, 64), dtype, 3)
    k = _rand((1, 64, 2, 64), dtype, 4)
    v = _rand((1, 64, 2, 64), dtype, 5)
    out = flash_attention(q, k, v, block_q=32, block_k=32, **INTERP)
    exp = ref.flash_attention_ref(q, k, v)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=atol, rtol=atol)


@pytest.mark.parametrize("bq,bk", [(16, 64), (64, 16), (128, 128)])
def test_flash_attention_block_shape_invariance(bq, bk):
    """Output must not depend on the VMEM tile shape."""
    q = _rand((1, 130, 4, 32), jnp.float32, 6)
    k = _rand((1, 130, 2, 32), jnp.float32, 7)
    v = _rand((1, 130, 2, 32), jnp.float32, 8)
    out = flash_attention(q, k, v, block_q=bq, block_k=bk, **INTERP)
    exp = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_matches_model_path():
    """Kernel vs the model's chunked_attention (the XLA production path)."""
    from repro.models.attention import chunked_attention
    q = _rand((2, 64, 4, 32), jnp.float32, 9)
    k = _rand((2, 64, 2, 32), jnp.float32, 10)
    v = _rand((2, 64, 2, 32), jnp.float32, 11)
    pos = jnp.arange(64)
    a = flash_attention(q, k, v, window=20, block_q=32, block_k=32, **INTERP)
    b = chunked_attention(q, k, v, pos, pos, window=20, chunk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------
# dual-proximal SGD
# --------------------------------------------------------------------------

DPS_SWEEP = [
    ((17,), jnp.float32),              # tiny, heavy padding
    ((1024,), jnp.float32),            # exactly one tile
    ((1000, 3), jnp.float32),          # 2D, padded
    ((8, 128), jnp.bfloat16),          # bf16 params
    ((5, 7, 11), jnp.float32),         # 3D odd
]


@pytest.mark.parametrize("shape,dtype", DPS_SWEEP)
def test_dual_proximal_sgd_sweep(shape, dtype):
    w = _rand(shape, dtype, 0)
    g = _rand(shape, dtype, 1, 0.1)
    a1 = _rand(shape, dtype, 2)
    a2 = _rand(shape, dtype, 3)
    kw = dict(lr=0.05, mu1=0.01, mu2=0.005)
    out = dual_proximal_sgd(w, g, a1, a2, **kw, **INTERP)
    exp = ref.dual_proximal_sgd_ref(w, g, a1, a2, **kw)
    assert out.shape == shape and out.dtype == dtype
    atol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=atol)


@pytest.mark.parametrize("mu1,mu2", [(0.0, 0.0), (0.3, 0.0), (0.0, 0.3),
                                     (1.0, 1.0)])
def test_dual_proximal_sgd_mu_grid(mu1, mu2):
    """mu=0 branches (FedAvg / FedProx limits) share the same kernel."""
    shape = (333,)
    w, g, a1, a2 = (_rand(shape, jnp.float32, i) for i in range(4))
    out = dual_proximal_sgd(w, g, a1, a2, lr=0.1, mu1=mu1, mu2=mu2, **INTERP)
    exp = ref.dual_proximal_sgd_ref(w, g, a1, a2, lr=0.1, mu1=mu1, mu2=mu2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-6)


def test_dual_proximal_sgd_tree_matches_core():
    """Kernel tree update == repro.core.h2fed.proximal_sgd_step."""
    from repro.core.h2fed import H2FedParams, proximal_sgd_step
    tree = {"a": _rand((40, 10), jnp.float32, 0),
            "b": _rand((10,), jnp.float32, 1)}
    g = jax.tree.map(lambda l: l * 0.01, tree)
    a1 = jax.tree.map(lambda l: l + 0.1, tree)
    a2 = jax.tree.map(lambda l: l - 0.1, tree)
    hp = H2FedParams(mu1=0.05, mu2=0.02, lr=0.03)
    got = dual_proximal_sgd_tree(tree, g, a1, a2, lr=hp.lr, mu1=hp.mu1,
                                 mu2=hp.mu2, interpret=True)
    want = proximal_sgd_step(tree, g, a1, a2, hp)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


# --------------------------------------------------------------------------
# masked hierarchical aggregation
# --------------------------------------------------------------------------

AGG_SWEEP = [
    (4, 1, 64, jnp.float32),           # tiny
    (100, 10, 2000, jnp.float32),      # the paper's topology (A=100, R=10)
    (32, 4, 777, jnp.float32),         # ragged N
    (16, 4, 512, jnp.bfloat16),        # bf16 params
    (7, 7, 130, jnp.float32),          # R == A
]


@pytest.mark.parametrize("A,R,N,dtype", AGG_SWEEP)
def test_masked_hier_agg_sweep(A, R, N, dtype):
    rng = np.random.default_rng(A * 7 + R)
    x = jnp.asarray(rng.standard_normal((A, N))).astype(dtype)
    w = jnp.asarray(rng.uniform(1, 5, A), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, A), jnp.float32)
    assign = jnp.asarray(rng.integers(0, R, A), jnp.int32)
    got, mass_g = masked_hier_agg(x, w, mask, assign, R, **INTERP)
    exp, mass_e = ref.masked_hier_agg_ref(x, w, mask, assign, R)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32),
                               atol=atol, rtol=atol)
    np.testing.assert_allclose(np.asarray(mass_g), np.asarray(mass_e),
                               rtol=1e-6)


@pytest.mark.parametrize("A,R,N,dtype", AGG_SWEEP)
def test_block_local_agg_matches_ref(A, R, N, dtype):
    """The block-local (unnormalized) variant vs its segment-sum oracle —
    and against the global kernel restricted to one pod's RSU block."""
    from repro.kernels.masked_hier_agg import block_local_agg
    rng = np.random.default_rng(A * 13 + R)
    x = jnp.asarray(rng.standard_normal((A, N))).astype(dtype)
    w = jnp.asarray(rng.uniform(0, 4, A) * (rng.random(A) < 0.8),
                    jnp.float32)
    assign = jnp.asarray(rng.integers(0, R, A), jnp.int32)
    num, mass = block_local_agg(x, w, assign, R, **INTERP)
    num_e, mass_e = ref.block_local_agg_ref(x, w, assign, R)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(num, np.float32),
                               np.asarray(num_e, np.float32),
                               atol=atol, rtol=atol)
    np.testing.assert_allclose(np.asarray(mass), np.asarray(mass_e),
                               rtol=1e-6)


def test_block_local_agg_is_weight_matrix_block():
    """A pod's block-local call == the matching row-block of the global
    unnormalized weight-matrix matmul (the block-diagonal structure the
    RSU-sharded engine exploits, DESIGN.md §4)."""
    from repro.core.aggregation import unnormalized_weight_matrix
    from repro.core.topology import HierarchyTopology
    from repro.kernels.masked_hier_agg import block_local_agg
    rng = np.random.default_rng(3)
    A, R, N, pods = 12, 4, 96, 2

    class _Mesh:
        shape = {"pod": pods, "data": 2}
        axis_names = ("pod", "data")

    topo = HierarchyTopology(A, R, _Mesh(), rsu_sharded=True)
    x = jnp.asarray(rng.standard_normal((A, N)), jnp.float32)
    w = jnp.asarray(rng.uniform(1, 2, A), jnp.float32)
    W = unnormalized_weight_matrix(
        w, jnp.ones((A,)), jnp.asarray(topo.rsu_assign), R)   # (R, A)
    full = np.asarray(W @ x)
    x_p = np.asarray(x)[topo.agent_perm]
    w_p = np.asarray(w)[topo.agent_perm]
    a_pp, r_pp = A // pods, topo.rsu_per_pod
    for p in range(pods):
        sl = slice(p * a_pp, (p + 1) * a_pp)
        num, _ = block_local_agg(
            jnp.asarray(x_p[sl]), jnp.asarray(w_p[sl]),
            jnp.asarray(topo.local_assign[sl]), r_pp, **INTERP)
        np.testing.assert_allclose(np.asarray(num),
                                   full[p * r_pp:(p + 1) * r_pp],
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("N", [96, 130, 333, 1100, 3333])
def test_weighted_agg_matmul_ragged_n(N):
    """Non-multiple-of-128 N must stay full-lane tiled (pad-up plan, no
    degrade-to-tiny-tiles fallback) on BOTH routes: the Pallas kernel
    (interpret) and the XLA dot the ops facade uses off-TPU."""
    from repro.kernels.masked_hier_agg import _tile_plan
    from repro.kernels import ops
    rng = np.random.default_rng(N)
    R, A = 5, 23
    W = jnp.asarray(rng.standard_normal((R, A)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((A, N)), jnp.float32)
    exp = np.asarray(W) @ np.asarray(x)
    got_pl = weighted_agg_matmul(W, x, **INTERP)
    np.testing.assert_allclose(np.asarray(got_pl), exp, atol=2e-5,
                               rtol=2e-5)
    got_ops = ops.weighted_agg_matmul(W, x)        # XLA route on CPU
    np.testing.assert_allclose(np.asarray(got_ops), exp, atol=2e-5,
                               rtol=2e-5)
    n_pad, bn = _tile_plan(N, [(A, 4), (R, 4)])
    assert bn % 128 == 0 and n_pad % bn == 0 and n_pad >= N
    assert n_pad - N < bn + 128                    # bounded pad waste


@pytest.mark.parametrize("A,itemsize", [(100, 4), (2000, 4), (2000, 2),
                                        (8192, 2)])
def test_tile_plan_fits_vmem(A, itemsize):
    """The N tile shrinks with the fleet width and dtype so the pipelined
    blocks stay inside VMEM_BUDGET (double-buffered across a multi-step
    grid, single-buffered when one tile covers N)."""
    from repro.kernels.masked_hier_agg import (LANE, VMEM_BUDGET,
                                               _tile_plan, _vmem_rows)
    R = 10
    for N in (68, 31_810, 9_540_000):
        n_pad, bn = _tile_plan(N, [(A, itemsize), (R, 4), (R, 4)])
        assert bn % LANE == 0 and n_pad % bn == 0 and n_pad >= N
        bufs = 1 if n_pad == bn else 2
        col = _vmem_rows(A, itemsize) * itemsize + 2 * _vmem_rows(R, 4) * 4
        assert bufs * col * bn <= VMEM_BUDGET
    # the narrow paper fleet keeps the full default tile
    assert _tile_plan(31_810, [(100, 4), (R, 4), (R, 4)]) == (32_768, 2048)


def test_tile_plan_refuses_past_one_lane_tile():
    from repro.kernels.masked_hier_agg import _tile_plan
    with pytest.raises(ValueError, match="A-blocked reduction grid"):
        _tile_plan(31_810, [(20_000, 4), (10, 4), (10, 4)])
    # a one-step grid is single-buffered, so the same rows fit at N = 68
    assert _tile_plan(68, [(20_000, 4), (10, 4), (10, 4)]) == (128, 128)


# --------------------------------------------------------------------------
# fused aggregate-and-blend (one-pass rounds)
# --------------------------------------------------------------------------

FUSED_SWEEP = [
    (4, 1, 64, jnp.float32),
    (100, 10, 2000, jnp.float32),
    (32, 4, 777, jnp.float32),          # ragged N
    (16, 4, 512, jnp.bfloat16),         # bf16 fleet storage
    (7, 7, 130, jnp.float32),
]


@pytest.mark.parametrize("A,R,N,dtype", FUSED_SWEEP)
def test_agg_blend_matches_ref(A, R, N, dtype):
    """Fused aggregate+blend == the un-fused two-pass oracle on both the
    Pallas (interpret) and the ops XLA routes, incl. kept (zero-mass)
    rows."""
    from repro.kernels import ops
    from repro.kernels.masked_hier_agg import agg_blend
    rng = np.random.default_rng(A + R + N)
    x = jnp.asarray(rng.standard_normal((A, N))).astype(dtype)
    w = jnp.asarray(rng.uniform(1, 5, A), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, A), jnp.float32)
    assign = jnp.asarray(rng.integers(0, R, A), jnp.int32)
    prev = jnp.asarray(rng.standard_normal((R, N))).astype(dtype)
    exp, mass_e = ref.agg_blend_ref(x, w, mask, assign, R, prev)
    atol = 2e-5 if dtype == jnp.float32 else 5e-2
    for got, mass in (agg_blend(x, w, mask, assign, R, prev, **INTERP),
                      ops.agg_blend(x, w, mask, assign, R, prev)):
        assert got.dtype == prev.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32),
                                   atol=atol, rtol=atol)
        np.testing.assert_allclose(np.asarray(mass), np.asarray(mass_e),
                                   rtol=1e-6)
    # zero-mass rows keep prev EXACTLY (no arithmetic touches them)
    dead = np.asarray(mass_e) == 0
    got_pl, _ = agg_blend(x, w, mask, assign, R, prev, **INTERP)
    np.testing.assert_array_equal(np.asarray(got_pl)[dead],
                                  np.asarray(prev)[dead])


@pytest.mark.parametrize("A,R,N,dtype", FUSED_SWEEP)
@pytest.mark.parametrize("keep", [0.0, 0.6])
def test_agg_absorb_matches_ref(A, R, N, dtype, keep):
    """Fused two-cohort scatter-absorb == scatter+scatter+add+absorb
    oracle on both routes (the semi-async tick's RSU layer)."""
    from repro.kernels import ops
    from repro.kernels.masked_hier_agg import agg_absorb
    rng = np.random.default_rng(A * 3 + R + N + int(keep * 10))
    x1 = jnp.asarray(rng.standard_normal((A, N))).astype(dtype)
    x2 = jnp.asarray(rng.standard_normal((A, N))).astype(dtype)
    w1 = jnp.asarray(rng.uniform(0, 4, A) * (rng.random(A) < 0.7),
                     jnp.float32)
    w2 = jnp.asarray(rng.uniform(0, 2, A) * (rng.random(A) < 0.4),
                     jnp.float32)
    assign = jnp.asarray(rng.integers(0, R, A), jnp.int32)
    buf = jnp.asarray(rng.standard_normal((R, N))).astype(dtype)
    bmass = jnp.asarray(rng.uniform(0, 5, R), jnp.float32)
    arr = ((x1, w1), (x2, w2))
    exp, total_e, new_e = ref.agg_absorb_ref(arr, assign, R, buf, bmass,
                                             keep=keep)
    atol = 2e-5 if dtype == jnp.float32 else 6e-2
    for got, total, new in (
            agg_absorb(arr, assign, R, buf, bmass, keep=keep, **INTERP),
            ops.agg_absorb(arr, assign, R, buf, bmass, keep=keep)):
        assert got.dtype == buf.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32),
                                   atol=atol, rtol=atol)
        np.testing.assert_allclose(np.asarray(total), np.asarray(total_e),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(new), np.asarray(new_e),
                                   rtol=1e-5)


def test_agg_absorb_per_rsu_keep_vector():
    """(R,)-vector keep (per-RSU adaptive retention) matches the oracle."""
    from repro.kernels.masked_hier_agg import agg_absorb
    rng = np.random.default_rng(5)
    A, R, N = 12, 3, 200
    x = jnp.asarray(rng.standard_normal((A, N)), jnp.float32)
    w = jnp.asarray(rng.uniform(0, 2, A), jnp.float32)
    assign = jnp.asarray(rng.integers(0, R, A), jnp.int32)
    buf = jnp.asarray(rng.standard_normal((R, N)), jnp.float32)
    bmass = jnp.asarray(rng.uniform(1, 4, R), jnp.float32)
    keep = jnp.asarray([0.0, 0.5, 1.0], jnp.float32)
    got, total, _ = agg_absorb(((x, w),), assign, R, buf, bmass,
                               keep=keep, **INTERP)
    exp, total_e, _ = ref.agg_absorb_ref(((x, w),), assign, R, buf, bmass,
                                         keep=keep)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(total_e),
                               rtol=1e-6)


def test_cloud_blend_matches_ref():
    from repro.kernels import ops
    from repro.kernels.masked_hier_agg import cloud_blend
    rng = np.random.default_rng(6)
    R, N = 6, 777
    x = jnp.asarray(rng.standard_normal((R, N)), jnp.float32)
    w = jnp.asarray(rng.uniform(0, 3, R), jnp.float32)
    prev = jnp.asarray(rng.standard_normal((N,)), jnp.float32)
    exp = ref.cloud_blend_ref(x, w, prev)
    for got in (cloud_blend(x, w, prev, **INTERP),
                ops.cloud_blend(x, w, prev)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)
    # dead fleet: the cloud master is kept bit-exactly, even from a bf16
    # RSU buffer (the fp32-master dtype policy)
    xb = x.astype(jnp.bfloat16)
    got0 = cloud_blend(xb, jnp.zeros((R,)), prev, **INTERP)
    assert got0.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got0), np.asarray(prev))


def test_ops_interpret_override(monkeypatch):
    """ops._interpret: explicit override > env var > backend detection,
    and reset-safe for tests that force platforms."""
    from repro.kernels import ops
    try:
        ops.set_interpret(True)
        assert ops._interpret() is True
        ops.set_interpret(False)
        assert ops._interpret() is False
        ops.set_interpret(None)                       # back to detection
        auto = ops._interpret()
        assert auto == (jax.default_backend() != "tpu")
        monkeypatch.setenv("REPRO_INTERPRET", "0")
        assert ops._interpret() is False
        monkeypatch.setenv("REPRO_INTERPRET", "1")
        assert ops._interpret() is True
        monkeypatch.delenv("REPRO_INTERPRET")
        assert ops._interpret() == auto
        # explicit override beats the env var
        monkeypatch.setenv("REPRO_INTERPRET", "0")
        ops.set_interpret(True)
        assert ops._interpret() is True
    finally:
        ops.set_interpret(None)


def test_cloud_agg_matches_ref():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((10, 333)), jnp.float32)
    w = jnp.asarray(rng.uniform(0, 3, 10), jnp.float32)
    got = cloud_agg(x, w, **INTERP)
    exp = ref.cloud_agg_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5)


def test_weight_matrix_rows_normalized():
    rng = np.random.default_rng(1)
    A, R = 30, 5
    w = jnp.asarray(rng.uniform(1, 2, A), jnp.float32)
    mask = jnp.ones((A,))
    assign = jnp.asarray(rng.integers(0, R, A), jnp.int32)
    W = build_weight_matrix(w, mask, assign, R)
    sums = np.asarray(W).sum(axis=1)
    live = np.asarray(
        jax.ops.segment_sum(w, assign, num_segments=R)) > 0
    np.testing.assert_allclose(sums[live], 1.0, rtol=1e-6)


def test_agg_kernel_matches_core_aggregation():
    """Kernel path == repro.core.aggregation.rsu_aggregate on a real pytree."""
    from repro.core.aggregation import rsu_aggregate
    rng = np.random.default_rng(2)
    A, R = 12, 3
    tree = {"w": jnp.asarray(rng.standard_normal((A, 6, 4)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((A, 4)), jnp.float32)}
    wts = jnp.asarray(rng.uniform(1, 2, A), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, A), jnp.float32)
    assign = jnp.asarray(rng.integers(0, R, A), jnp.int32)

    core_out, core_mass = rsu_aggregate(tree, wts, mask, assign, R)

    # flatten agent-stacked tree -> (A, N), run kernel, unflatten
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate([l.reshape(A, -1) for l in leaves], axis=1)
    k_out, k_mass = masked_hier_agg(flat, wts, mask, assign, R, **INTERP)
    np.testing.assert_allclose(np.asarray(core_mass), np.asarray(k_mass),
                               rtol=1e-6)
    off = 0
    # jax.tree.leaves sorts dict keys: "b" before "w"
    for l, name in zip(leaves, ("b", "w")):
        n = int(np.prod(l.shape[1:]))
        krec = np.asarray(k_out[:, off:off + n]).reshape((R,) + l.shape[1:])
        mass_pos = np.asarray(core_mass) > 0
        np.testing.assert_allclose(
            krec[mass_pos], np.asarray(core_out[name])[mass_pos], atol=2e-5)
        off += n
