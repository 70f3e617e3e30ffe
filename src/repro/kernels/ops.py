"""Public jit'd wrappers around the Pallas kernels.

On a TPU backend these lower to Mosaic.  Off-TPU the *pointwise/scan*
kernels run in ``interpret=True`` mode (kernel body as jax ops, identical
semantics); the *aggregation matmuls* instead route to the equivalent
XLA ``dot_general`` formulation — interpret-mode grid walking is a
debugging tool, not the CPU deploy path (see benchmarks/kernels_micro), and
the hot simulation loop (fedsim/simulator engine="flat") calls these every
round.  Tests pin both lowerings against kernels/ref.py.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.aggregation import (build_weight_matrix, buffer_absorb,
                                    cohort_mass, normalized_weights,
                                    scatter_accumulate as _scatter_ref)
from repro.kernels import dual_proximal_sgd as _dps
from repro.kernels import flash_attention as _fa
from repro.kernels import masked_hier_agg as _mha

# explicit backend-route override (None = auto-detect).  Set via
# ``set_interpret`` or the REPRO_INTERPRET env var ("1"/"0"); tests that
# force platforms call ``set_interpret(None)`` to drop back to detection.
_FORCE_INTERPRET: Optional[bool] = None


@functools.lru_cache(maxsize=1)
def _backend_interpret() -> bool:
    return jax.default_backend() != "tpu"


def set_interpret(value: Optional[bool]) -> None:
    """Override the Pallas-vs-XLA route: True forces interpret/XLA
    fallbacks, False forces the compiled Pallas route, None restores
    backend auto-detection (and re-reads the backend, so tests that
    switch ``jax.default_backend`` mid-process stay correct)."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = value
    _backend_interpret.cache_clear()


def _interpret() -> bool:
    if _FORCE_INTERPRET is not None:
        return _FORCE_INTERPRET
    env = os.environ.get("REPRO_INTERPRET")
    if env not in (None, ""):
        return env.lower() not in ("0", "false", "no")
    return _backend_interpret()


def interpret_mode() -> bool:
    """The effective Pallas interpret flag (force > env > backend) — part
    of the compiled-program cache key (core/program_cache, DESIGN.md §10):
    programs traced under different interpret modes are different programs.
    """
    return _interpret()


def _xla_agg_matmul(weight_matrix, stacked):
    """The aggregation matmul as one XLA dot — same contract as
    ``masked_hier_agg.weighted_agg_matmul`` (fp32 accumulate, param dtype
    out).  The small (R, A) weight matrix is cast to the FLEET dtype
    instead of widening the dominant (A, N) buffer to fp32 (which would
    materialize a full-precision copy and forfeit the bf16 storage
    policy's HBM savings); fp32 fleets are unchanged bit-for-bit."""
    out = jax.lax.dot_general(
        weight_matrix.astype(stacked.dtype), stacked,
        (((1,), (0,)), ((), ())), precision=_mha.FP32,
        preferred_element_type=jnp.float32)
    return out.astype(stacked.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


def dual_proximal_sgd(w, g, a1, a2, *, lr: float, mu1: float, mu2: float):
    return _dps.dual_proximal_sgd(w, g, a1, a2, lr=lr, mu1=mu1, mu2=mu2,
                                  interpret=_interpret())


def dual_proximal_sgd_tree(w, g, a1, a2, *, lr: float, mu1: float,
                           mu2: float):
    return _dps.dual_proximal_sgd_tree(w, g, a1, a2, lr=lr, mu1=mu1,
                                       mu2=mu2, interpret=_interpret())


def weighted_agg_matmul(weight_matrix, stacked):
    """(R, A) @ (A, N) aggregation matmul — the raw kernel, for callers
    (e.g. the sharded engine) that build their own partial weight matrix."""
    if _interpret():
        return _xla_agg_matmul(weight_matrix, stacked)
    return _mha.weighted_agg_matmul(weight_matrix, stacked, interpret=False)


def masked_hier_agg(stacked_flat, weights, mask, rsu_assign, n_rsus: int):
    W = build_weight_matrix(weights, mask, rsu_assign, n_rsus)
    mass = cohort_mass(weights, mask, rsu_assign, n_rsus)
    return weighted_agg_matmul(W, stacked_flat), mass


def block_local_agg(stacked_flat, weights, local_assign, n_rsus_local: int):
    """Block-local unnormalized RSU aggregation for the RSU-sharded engines
    (DESIGN.md §4): ``(num (R_local, N), mass (R_local,)) = Σ_a w_a·x_a``
    grouped by SHARD-LOCAL RSU id — one pod's diagonal block of the global
    weight matrix, so the RSU layer needs no cross-pod traffic.

    TPU: the Pallas aggregation matmul with the local weight matrix
    resident in VMEM; off-TPU: the XLA ``segment_sum`` reference from
    ``core.aggregation`` (same contract, shard-local ids).
    """
    if _interpret():
        return _scatter_ref(stacked_flat, weights, local_assign,
                            n_rsus_local)
    return _mha.block_local_agg(stacked_flat, weights, local_assign,
                                n_rsus_local, interpret=False)


def masked_scatter_accumulate(stacked_flat, weights, rsu_assign,
                              n_rsus: int):
    """Batched late-merge accumulate for the semi-async engine:
    ``(num (R, N), mass (R,)) = Σ_a w_a·x_a`` grouped by RSU, weights
    unnormalized (mask x data volume x staleness decay folded in).

    TPU: the Pallas aggregation matmul with the unnormalized weight matrix
    resident in VMEM (MXU work); off-TPU: the XLA ``segment_sum``
    scatter-add reference from ``core.aggregation``.
    """
    if _interpret():
        return _scatter_ref(stacked_flat, weights, rsu_assign, n_rsus)
    return _mha.scatter_accumulate(stacked_flat, weights, rsu_assign,
                                   n_rsus, interpret=False)


def chunk_agg(chunk_flat, weights, rsu_assign, n_rsus: int):
    """Chunk-shaped aggregation entry for the cohort-streamed engines
    (fedsim/streaming, DESIGN.md §8): ``(num (R, N), mass (R,)) =
    Σ_a w_a·x_a`` over ONE agent chunk, grouped by GLOBAL RSU id with
    weights unnormalized (mask × data volume × any staleness decay folded
    in).  The caller accumulates num/mass across chunks and normalizes
    once per local round (``core.aggregation.normalize_blend`` /
    ``buffer_absorb``) — the same partial-sum algebra the sharded engines
    psum, so streamed results match the resident fused ``agg_blend`` /
    ``agg_absorb`` rounds to fp32 tolerance.

    TPU: the Pallas aggregation matmul with the (R, chunk) weight matrix
    resident in VMEM; off-TPU: the XLA ``segment_sum`` reference.  Padded
    tail rows ride along with weight 0 (and assignment 0), so the entry is
    shape-static across a round's chunk stream.
    """
    if _interpret():
        return _scatter_ref(chunk_flat, weights, rsu_assign, n_rsus)
    return _mha.scatter_accumulate(chunk_flat, weights, rsu_assign,
                                   n_rsus, interpret=False)


def cloud_agg(rsu_flat, rsu_weights):
    wn, _ = normalized_weights(rsu_weights)
    return weighted_agg_matmul(wn[None, :], rsu_flat)[0]


# --------------------------------------------------------------------------
# fused aggregate-and-blend entry points (one-pass rounds, DESIGN.md §3/§6)
# --------------------------------------------------------------------------
# Both layers launch the same kernel; the named scopes (h2fed.rsu_agg,
# h2fed.cloud_blend) tell the launches, and their pads and slices, apart
# in a profile.

@jax.named_scope("h2fed.rsu_agg")
def agg_blend(stacked_flat, weights, mask, rsu_assign, n_rsus: int, prev):
    """Fused RSU aggregation + mass-guard blend:
    ``out[r] = where(mass[r] > 0, W_norm[r] @ X, prev[r])`` with each
    N-tile read/written once.  Returns (rsu' in prev's dtype, mass (R,)).

    TPU: one Pallas grid pass (``masked_hier_agg.agg_blend``); off-TPU the
    exact un-fused XLA composition the flat engine ran before (dot +
    where), so fp32 results are bit-compatible with the two-step path.
    """
    if _interpret():
        W = build_weight_matrix(weights, mask, rsu_assign, n_rsus)
        mass = cohort_mass(weights, mask, rsu_assign, n_rsus)
        new = _xla_agg_matmul(W, stacked_flat)
        out = jnp.where((mass > 0)[:, None], new.astype(jnp.float32),
                        prev.astype(jnp.float32))
        return out.astype(prev.dtype), mass
    return _mha.agg_blend(stacked_flat, weights, mask, rsu_assign, n_rsus,
                          prev, interpret=False)


@jax.named_scope("h2fed.rsu_agg")
def agg_absorb(arrivals, rsu_assign, n_rsus: int, buf, buf_mass, *,
               keep=0.0):
    """Fused multi-cohort scatter-accumulate + staleness-buffer merge
    (the semi-async tick's whole RSU layer in one pass).  ``arrivals`` is
    a sequence of (x (A, N), w (A,)) cohorts; returns (buf' in buf's
    dtype, total_mass (R,), new_mass (R,)).

    TPU: one Pallas grid pass; off-TPU: fp32 fleets run the exact
    segment-sum + ``buffer_absorb`` chain the async engine ran before
    (bit-compatible with today), storage-dtype (bf16) fleets run the
    weight-matrix dot formulation instead — the segment-sum route would
    materialize a full fp32 copy of the (A, N) buffer, forfeiting the
    dtype policy's HBM savings; the dot reads the fleet in storage dtype
    and accumulates fp32.
    """
    if _interpret():
        from repro.core.aggregation import unnormalized_weight_matrix
        f32_fleet = all(jnp.dtype(x.dtype) == jnp.dtype(jnp.float32)
                        for x, _ in arrivals)
        num = jnp.zeros(buf.shape, jnp.float32)
        new_mass = jnp.zeros((n_rsus,), jnp.float32)
        for x, w in arrivals:
            if f32_fleet:
                n, m = _scatter_ref(x, w, rsu_assign, n_rsus)
            else:
                wm = unnormalized_weight_matrix(
                    w, jnp.ones_like(w), rsu_assign, n_rsus)
                n = jax.lax.dot_general(
                    wm.astype(x.dtype), x, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m = jnp.sum(wm, axis=1)
            num = num + n
            new_mass = new_mass + m
        out, total = buffer_absorb(buf, buf_mass, num, new_mass, keep=keep)
        return out, total, new_mass
    return _mha.agg_absorb(arrivals, rsu_assign, n_rsus, buf, buf_mass,
                           keep=keep, interpret=False)


@jax.named_scope("h2fed.cloud_blend")
def cloud_blend(rsu_flat, rsu_weights, prev):
    """Fused cloud aggregation + keep-guard:
    ``where(Σ mass > 0, wn @ rsu_flat, prev)`` in one pass; out dtype
    follows ``prev`` (the fp32 cloud master, independent of the fleet
    storage dtype)."""
    if _interpret():
        w = rsu_weights.astype(jnp.float32)
        total = jnp.sum(w)
        wn, _ = normalized_weights(rsu_weights)
        new = jax.lax.dot_general(
            wn[None, :], rsu_flat.astype(jnp.float32),
            (((1,), (0,)), ((), ())), precision=_mha.FP32,
            preferred_element_type=jnp.float32)[0]
        return jnp.where(total > 0, new,
                         prev.astype(jnp.float32)).astype(prev.dtype)
    return _mha.cloud_blend(rsu_flat, rsu_weights, prev, interpret=False)


def slstm_scan(wx, r_gates, b_gates, *, block_s: int = 256):
    from repro.kernels import slstm_scan as _ss
    return _ss.slstm_scan(wx, r_gates, b_gates, block_s=block_s,
                          interpret=_interpret())
