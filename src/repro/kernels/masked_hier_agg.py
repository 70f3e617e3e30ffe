"""Masked hierarchical aggregation Pallas kernel (paper Alg. 2 l.8 / Alg. 3 l.6).

The RSU layer aggregates A stacked agent parameter vectors into R RSU
vectors with CSR-masked, data-volume weights; the cloud layer is the R→1
special case.  Both are the same computation:

    out[r, n] = Σ_a  W[r, a] · X[a, n]

where ``W`` is the (R, A) row-normalized masked weight matrix (zero outside
each RSU's cohort; core/aggregation.build_weight_matrix is the reference).
That is a skinny matmul — MXU work, not gather work — which is exactly how
the TPU wants hierarchy aggregation expressed (the GPU-native formulation
would be a segmented reduction; DESIGN.md §2).  The flat-buffer simulation
engine (DESIGN.md §3) calls this every round via the kernels/ops facade,
which routes to the equivalent XLA dot off-TPU.

Tiling: W stays fully resident in VMEM; the grid walks column blocks of
X (the parameter axis, potentially billions of elements) and each program
computes a (R, block_n) = (R, A) @ (A, block_n) tile on the MXU.  The
tile width is chosen from A and the dtypes (``_tile_plan``) so the
double-buffered blocks fit ``VMEM_BUDGET``; a fleet too wide for even one
128-lane tile is refused, since that needs an A-blocked reduction grid.

One-pass rounds (DESIGN.md §3): the engines' round programs are
bandwidth-bound on streaming the (A, N)/(R, N) buffers through HBM, so the
consumers of the aggregation output — the mass-guard blend
(``jnp.where(mass>0, new, old)``), the cloud keep-guard, and the semi-async
``buffer_absorb`` renormalizing merge — are folded INTO the grid here:
``agg_blend`` / ``agg_absorb`` / ``cloud_blend`` read each N-tile once
(inputs + previous buffer) and write it once, instead of materializing a
fresh (R, N) numerator that a separate elementwise pass re-reads.  All
three are one shared kernel, ``_fused_agg_blend``:

    out[r, n] = where(guard[r],
                      (retained[r]·buf[r, n] + Σ_i W_i[r, :] @ X_i[:, n])
                        / safe[r],
                      buf[r, n])

with per-row coefficients prepared by the (cheap, O(R)/O(A)) host-side
weighting algebra.  The synchronous blend is the ``retained=0, safe=1,
W`` row-normalized case; the async absorb passes the unnormalized weight
matrices of both arrival cohorts (fresh + due) so ONE grid pass replaces
two scatter-accumulates, a numerator add and the buffer merge.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the weighting algebra lives in core.aggregation (the reference
# implementation tests pin this kernel against); re-exported for callers
# that treat this module as the aggregation entry point.
from repro.core.aggregation import (build_weight_matrix, cohort_mass,  # noqa: F401
                                    normalized_weights,
                                    unnormalized_weight_matrix)

LANE = 128
# VMEM the pipelined blocks of one aggregation call may take: Mosaic's
# default scoped-VMEM limit (16 MiB on v5e) less 2 MiB for the kernel's
# own (R, block_n) temporaries (the fp32 buffer tile, the accumulator)
VMEM_BUDGET = 14 * 2**20
# the aggregation is a weighted mean: on a TPU an fp32 matmul at default
# precision takes one bf16 pass, so the fp32 aggregation dots (the
# kernels' and the XLA route's) ask for fp32 passes (no-op on the CPU)
FP32 = jax.lax.Precision.HIGHEST


def _vmem_rows(rows: int, itemsize: int) -> int:
    """Rows rounded up to the dtype's sublane tile (8 rows of 32-bit
    words; 16-bit dtypes pack 16 rows, 8-bit 32)."""
    pack = 8 * (4 // itemsize)
    return -(-rows // pack) * pack


def _tile_plan(n: int, tiled, resident: int = 0, block_n: int = 2048):
    """Lane-aligned N-axis tiling that fits ``VMEM_BUDGET``.

    ``tiled`` lists ``(rows, itemsize)`` of every block the grid walks
    column-wise (the X inputs, the previous buffer, the output);
    ``resident`` is the bytes of the blocks every step reuses (W, coef).
    N pads up to a LANE multiple and the tile is the widest LANE multiple
    up to ``block_n`` whose blocks fit: double-buffered when the grid has
    more than one step, single-buffered when one tile covers N.  The pad
    waste is bounded by one tile, and the tile never changes how an output
    column is computed.  Raises ``ValueError`` when even one 128-lane tile
    does not fit — such a fleet needs an A-blocked reduction grid."""
    lane_n = -(-n // LANE) * LANE
    col = sum(_vmem_rows(r, s) * s for r, s in tiled)
    if lane_n <= block_n and col * lane_n + resident <= VMEM_BUDGET:
        return lane_n, lane_n                      # one step, one buffer
    fit = (VMEM_BUDGET // 2 - resident) // col // LANE * LANE
    bn = min(max(block_n // LANE * LANE, LANE), fit, lane_n)
    if bn < LANE:
        raise ValueError(
            f"aggregation tile does not fit VMEM: blocks of rows "
            f"{[r for r, _ in tiled]} need {2 * (col * LANE + resident)} "
            f"bytes at one {LANE}-lane tile, over the {VMEM_BUDGET}-byte "
            f"budget; a fleet this wide needs an A-blocked reduction grid")
    n_pad = -(-lane_n // bn) * bn
    return n_pad, bn


def _resident_bytes(*shapes) -> int:
    """VMEM bytes of fp32 blocks that stay resident across the grid."""
    return sum(_vmem_rows(r, 4) * (-(-c // LANE) * LANE) * 4
               for r, c in shapes)


def _pad_cols(x: jax.Array, n_pad: int) -> jax.Array:
    pad = n_pad - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def _contract(w, x):
    """(R, A) @ (A, BN) accumulated in fp32, in the fleet's dtype: an fp32
    fleet contracts at fp32 precision, a narrower one reads X as stored
    and rounds the small weight matrix to it — the XLA route's contract
    (``kernels/ops._xla_agg_matmul``)."""
    f32 = jnp.dtype(x.dtype) == jnp.dtype(jnp.float32)
    return jax.lax.dot_general(
        w.astype(x.dtype), x, (((1,), (0,)), ((), ())),
        precision=FP32 if f32 else None,
        preferred_element_type=jnp.float32)


def _agg_kernel(w_ref, x_ref, o_ref):
    o_ref[...] = _contract(w_ref[...], x_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def weighted_agg_matmul(weight_matrix: jax.Array, stacked: jax.Array, *,
                        block_n: int = 2048,
                        interpret: bool = False) -> jax.Array:
    """(R, A) @ (A, N) with N-axis VMEM tiling.  stacked may be any dtype;
    accumulation is fp32."""
    R, A = weight_matrix.shape
    A2, N = stacked.shape
    assert A == A2, (A, A2)
    n_pad, block_n = _tile_plan(
        N, [(A, stacked.dtype.itemsize), (R, stacked.dtype.itemsize)],
        _resident_bytes((R, A)), block_n)
    xs = _pad_cols(stacked, n_pad)
    grid = (n_pad // block_n,)

    out = pl.pallas_call(
        _agg_kernel, grid=grid,
        in_specs=[
            pl.BlockSpec((R, A), lambda i: (0, 0)),          # W resident
            pl.BlockSpec((A, block_n), lambda i: (0, i)),    # X column tile
        ],
        out_specs=pl.BlockSpec((R, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((R, n_pad), stacked.dtype),
        interpret=interpret,
    )(weight_matrix, xs)
    return out[:, :N] if n_pad != N else out


def masked_hier_agg(stacked_flat: jax.Array, weights: jax.Array,
                    mask: jax.Array, rsu_assign: jax.Array, n_rsus: int, *,
                    interpret: bool = False):
    """RSU aggregation on flattened stacked params.

    stacked_flat: (A, N) — one row per agent's flattened parameter vector.
    Returns (rsu_params (R, N), mass (R,)).
    """
    W = build_weight_matrix(weights, mask, rsu_assign, n_rsus)
    mass = cohort_mass(weights, mask, rsu_assign, n_rsus)
    return weighted_agg_matmul(W, stacked_flat, interpret=interpret), mass


def block_local_agg(stacked_flat: jax.Array, weights: jax.Array,
                    local_assign: jax.Array, n_rsus_local: int, *,
                    interpret: bool = False):
    """Block-local unnormalized aggregation (DESIGN.md §4, RSU-sharded mode):

        num[r, n] = Σ_{a: assign(a)=r}  w_a · X[a, n],   mass[r] = Σ w_a

    with ``local_assign`` holding SHARD-LOCAL RSU ids in
    ``[0, n_rsus_local)``.  When ``core.topology.HierarchyTopology``
    co-locates agents with their RSU's pod, the global (R, A) weight matrix
    is block-diagonal over pods and this is one pod's
    ``(R_local, A_local) @ (A_local, N)`` diagonal block — the whole RSU
    layer with no cross-pod traffic.  On TPU the small unnormalized weight
    matrix stays resident in VMEM and the grid walks parameter-axis tiles
    (same MXU formulation as the normalized aggregation); weights carry
    mask x data-volume (x staleness decay) folded in, so zero-weight rows
    contribute nothing.  The segment-sum oracle is
    ``core.aggregation.scatter_accumulate`` — the global (replicated) call
    is just this with global ids, and ``scatter_accumulate`` below
    delegates here.
    """
    W = unnormalized_weight_matrix(weights, jnp.ones_like(weights),
                                   local_assign, n_rsus_local)  # (R_loc, A)
    mass = jnp.sum(W, axis=1)
    num = weighted_agg_matmul(W, stacked_flat.astype(jnp.float32),
                              interpret=interpret)
    return num, mass


def scatter_accumulate(stacked_flat: jax.Array, weights: jax.Array,
                       rsu_assign: jax.Array, n_rsus: int, *,
                       interpret: bool = False):
    """Unnormalized batched late-merge (semi-async engine, DESIGN.md §6) —
    the global-ids case of ``block_local_agg`` (kept as the named entry the
    async tests/ops facade pin)."""
    return block_local_agg(stacked_flat, weights, rsu_assign, n_rsus,
                           interpret=interpret)


def cloud_agg(rsu_flat: jax.Array, rsu_weights: jax.Array, *,
              interpret: bool = False) -> jax.Array:
    """Cloud aggregation: the R→1 case.  rsu_flat: (R, N) -> (N,)."""
    wn, _ = normalized_weights(rsu_weights)
    return weighted_agg_matmul(wn[None, :], rsu_flat,
                               interpret=interpret)[0]


# --------------------------------------------------------------------------
# fused aggregate-and-blend (the one-pass round entry points)
# --------------------------------------------------------------------------

def _make_fused_kernel(n_pairs: int):
    """Kernel for ``_fused_agg_blend`` with ``n_pairs`` (W, X) inputs.

    refs layout: coef (R, 3) [retained | safe | guard], then W_i (R, A_i) /
    X_i (A_i, BN) interleaved, then buf (R, BN), then the output tile."""

    def kernel(*refs):
        coef = refs[0][...].astype(jnp.float32)            # (R, 3)
        buf = refs[1 + 2 * n_pairs][...].astype(jnp.float32)
        o_ref = refs[2 + 2 * n_pairs]
        acc = coef[:, 0:1] * buf                           # retained·buf
        for i in range(n_pairs):
            acc += _contract(refs[1 + 2 * i][...],         # (R, A_i)
                             refs[2 + 2 * i][...])         # (A_i, BN)
        merged = acc / coef[:, 1:2]                        # / safe
        o_ref[...] = jnp.where(coef[:, 2:3] > 0, merged,
                               buf).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _fused_agg_blend(coef: jax.Array, weight_mats, stackeds,
                     buf: jax.Array, *, block_n: int = 2048,
                     interpret: bool = False) -> jax.Array:
    """One grid pass of ``out = where(guard, (retained·buf + Σ W_i@X_i)
    / safe, buf)``: each N-tile of every input (and of the previous
    buffer) is read once and the output tile written once.  coef: (R, 3)
    rows of [retained, safe, guard]; out dtype == buf dtype."""
    R, N = buf.shape
    n_pad, block_n = _tile_plan(
        N,
        [(x.shape[0], x.dtype.itemsize) for x in stackeds]
        + [(R, buf.dtype.itemsize)] * 2,                       # buf + out
        _resident_bytes((R, 3), *(w.shape for w in weight_mats)),
        block_n)
    kernel = _make_fused_kernel(len(weight_mats))

    in_specs = [pl.BlockSpec((R, 3), lambda i: (0, 0))]    # coef resident
    args = [coef]
    for w, x in zip(weight_mats, stackeds):
        a = w.shape[1]
        assert x.shape == (a, N), (w.shape, x.shape, buf.shape)
        in_specs.append(pl.BlockSpec((R, a), lambda i: (0, 0)))
        in_specs.append(pl.BlockSpec((a, block_n), lambda i: (0, i)))
        args += [w, _pad_cols(x, n_pad)]
    in_specs.append(pl.BlockSpec((R, block_n), lambda i: (0, i)))
    args.append(_pad_cols(buf, n_pad))

    out = pl.pallas_call(
        kernel, grid=(n_pad // block_n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((R, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((R, n_pad), buf.dtype),
        interpret=interpret,
    )(*args)
    return out[:, :N] if n_pad != N else out


def agg_blend(stacked_flat: jax.Array, weights: jax.Array, mask: jax.Array,
              rsu_assign: jax.Array, n_rsus: int, prev: jax.Array, *,
              interpret: bool = False):
    """Fused ``masked_hier_agg`` + mass-guard blend (DESIGN.md §3):

        out[r] = where(mass[r] > 0, W_norm[r] @ X, prev[r])

    in ONE pass over the parameter axis.  Returns (rsu' (R, N) in
    ``prev``'s dtype, mass (R,)).  Oracle: ``kernels/ref.agg_blend_ref``.
    """
    W = build_weight_matrix(weights, mask, rsu_assign, n_rsus)
    mass = cohort_mass(weights, mask, rsu_assign, n_rsus)
    coef = jnp.stack([jnp.zeros_like(mass), jnp.ones_like(mass),
                      (mass > 0).astype(jnp.float32)], axis=1)
    out = _fused_agg_blend(coef, (W,), (stacked_flat,), prev,
                           interpret=interpret)
    return out, mass


def agg_absorb(arrivals, rsu_assign: jax.Array, n_rsus: int,
               buf: jax.Array, buf_mass: jax.Array, *, keep=0.0,
               interpret: bool = False):
    """Fused multi-cohort scatter-accumulate + staleness-buffer merge
    (DESIGN.md §6): for ``arrivals`` = sequence of (x (A, N), w (A,))
    cohorts,

        out[r] = (keep·M[r]·buf[r] + Σ_cohorts Σ_{a∈r} w_a·x_a)
                   / (keep·M[r] + m_new[r])        [buf[r] if zero mass]

    in ONE pass — the semi-async tick's two scatter-accumulates, the
    numerator add and the ``buffer_absorb`` renormalization share each
    N-tile.  Returns (buf' in buf's dtype, total_mass (R,), new_mass (R,)).
    Oracle: ``kernels/ref.agg_absorb_ref``."""
    mats, xs = [], []
    new_mass = jnp.zeros((n_rsus,), jnp.float32)
    for x, w in arrivals:
        wm = unnormalized_weight_matrix(w, jnp.ones_like(w), rsu_assign,
                                        n_rsus)
        mats.append(wm)
        xs.append(x)
        new_mass = new_mass + jnp.sum(wm, axis=1)
    retained = jnp.asarray(keep, jnp.float32) * buf_mass.astype(jnp.float32)
    retained = jnp.broadcast_to(retained, new_mass.shape)
    total = retained + new_mass
    coef = jnp.stack([retained, jnp.where(total > 0, total, 1.0),
                      (total > 0).astype(jnp.float32)], axis=1)
    out = _fused_agg_blend(coef, tuple(mats), tuple(xs), buf,
                           interpret=interpret)
    return out, total, new_mass


def cloud_blend(rsu_flat: jax.Array, rsu_weights: jax.Array,
                prev: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Fused cloud aggregation + keep-guard (Alg. 3 l.6): ``where(Σ mass >
    0, wn @ rsu_flat, prev)`` in one pass; out dtype == prev dtype (the
    fp32 cloud master).  Oracle: ``kernels/ref.cloud_blend_ref``."""
    w = rsu_weights.astype(jnp.float32)
    total = jnp.sum(w)
    wn = jnp.where(total > 0, w / jnp.where(total > 0, total, 1.0),
                   jnp.zeros_like(w))
    guard = (total > 0).astype(jnp.float32)
    coef = jnp.stack([jnp.zeros((1,), jnp.float32),
                      jnp.ones((1,), jnp.float32), guard[None]], axis=1)
    return _fused_agg_blend(coef, (wn[None, :],), (rsu_flat,),
                            prev[None, :], interpret=interpret)[0]
