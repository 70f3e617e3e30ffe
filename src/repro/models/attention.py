"""Attention: GQA (RoPE, qk-norm, sliding window), MLA, cross-attention.

Prefill uses a chunked online-softmax scan over KV blocks (flash-style,
memory-bounded — the Pallas kernel in repro.kernels.flash_attention implements
the same blocking for TPU VMEM; this file is the pure-jnp/XLA path).
Decode uses either a linear KV cache (full causal) or a ring buffer
(sliding window), so a 524k-token context costs O(window) memory.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import apply_rope, dense_init, rms_normalize

NEG_INF = -1e30


def maybe_constrain(x, *spec):
    """with_sharding_constraint IF a physical mesh with the named axes is
    active and the dims divide; a no-op on un-meshed CPU tests.

    Needed because GSPMD occasionally picks a catastrophic layout for scan
    carries (observed: the KV-chunk carry sharded over (KV, head_dim) on the
    data axis, forcing a partial-score all-reduce of (S × chunk) slabs every
    chunk step × every layer — §Perf hillclimb B)."""
    from jax._src.mesh import thread_resources
    pm = thread_resources.env.physical_mesh
    if pm.empty:
        return x
    # inside shard_map some axes are Manual — the constraint may only name
    # Auto axes (the abstract mesh carries the per-trace axis types)
    auto = set(pm.axis_names)
    am = jax.sharding.get_abstract_mesh()
    if am is not None and not am.empty:
        auto = {a for a in am.axis_names
                if am._name_to_type[a] == jax.sharding.AxisType.Auto}
    fixed = []
    for dim, ax in zip(x.shape, spec):
        if (ax is None or ax not in pm.axis_names or ax not in auto
                or dim % pm.shape[ax]):
            fixed.append(None)
        else:
            fixed.append(ax)
    if all(a is None for a in fixed):
        return x
    try:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(*fixed))
    except ValueError:   # exotic axis-type contexts: the hint is optional
        return x


# --------------------------------------------------------------------------
# chunked online-softmax attention (shared by GQA & MLA prefill)
# --------------------------------------------------------------------------

def chunked_attention(q, k, v, q_pos, kv_pos, *, window: int = 0,
                      chunk: int = 1024, causal: bool = True):
    """Memory-bounded attention via online softmax over KV chunks.

    q: (B, S, H, D); k/v: (B, T, KV, D) with H % KV == 0.
    q_pos: (S,), kv_pos: (T,) absolute positions for masking.
    Returns (B, S, H, D).
    """
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = D ** -0.5

    chunk = min(chunk, T)
    n_chunks = -(-T // chunk)
    pad = n_chunks * chunk - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-(1 << 30))
    kc = k.reshape(B, n_chunks, chunk, KV, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, D).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(n_chunks, chunk)
    # pin layouts: batch over data, q-heads over model (see maybe_constrain)
    q = maybe_constrain(q, "data", None, "model", None)
    kc = maybe_constrain(kc, None, "data", None, None, None)
    vc = maybe_constrain(vc, None, "data", None, None, None)

    def step(carry, inp):
        m, l, acc = carry                       # (B,S,H), (B,S,H), (B,S,H,D)
        k_i, v_i, p_i = inp                     # (B,c,KV,D), (B,c,KV,D), (c,)
        # flat-H score layout (§Perf hillclimb B): repeating the KV chunk to
        # all H q-heads keeps the einsum sharded purely on H (H % model == 0
        # for every assigned arch), whereas the grouped (KV, G) layout makes
        # GSPMD split the head_dim contraction when KV < model-axis size and
        # all-reduce full (S × T) score slabs.
        kh = jnp.repeat(k_i, G, axis=2)         # (B,c,H,D)
        vh = jnp.repeat(v_i, G, axis=2)
        s = jnp.einsum("bshd,bchd->bshc", q, kh,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((S, chunk), bool)
        if causal:
            mask &= p_i[None, :] <= q_pos[:, None]
        if window:
            mask &= p_i[None, :] > q_pos[:, None] - window
        mask &= p_i[None, :] >= 0
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        m_i = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_i)
        p = jnp.exp(s - m_i[..., None])
        l_i = l * alpha + jnp.sum(p, axis=-1)
        acc_i = acc * alpha[..., None] + jnp.einsum(
            "bshc,bchd->bshd", p.astype(vh.dtype), vh,
            preferred_element_type=jnp.float32)
        return (m_i, l_i, acc_i), None

    m0 = jnp.full((B, S, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, H), jnp.float32)
    a0 = jnp.zeros((B, S, H, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, kv_pos, cur_pos, *, window: int = 0):
    """Single-token attention over a (possibly ring-buffered) KV cache.

    q: (B, 1, H, D); k/v_cache: (B, T, KV, D); kv_pos: (B, T) absolute
    positions (-1 for unwritten slots); cur_pos: (B,) current position.
    """
    B, _, H, D = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = D ** -0.5
    qg = q.reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    valid = (kv_pos >= 0) & (kv_pos <= cur_pos[:, None])
    if window:
        valid &= kv_pos > (cur_pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, D).astype(q.dtype)


# --------------------------------------------------------------------------
# KV cache containers
# --------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array          # (B, T, KV, D)
    v: jax.Array          # (B, T, KV, D)
    pos: jax.Array        # (B, T) int32 absolute positions, -1 = empty
    idx: jax.Array        # (B,) int32 next write slot (ring index)


def init_kv_cache(batch: int, length: int, n_kv: int, head_dim: int, dtype):
    return KVCache(
        k=jnp.zeros((batch, length, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, length, n_kv, head_dim), dtype),
        pos=jnp.full((batch, length), -1, jnp.int32),
        idx=jnp.zeros((batch,), jnp.int32),
    )


def cache_append(cache: KVCache, k_new, v_new, positions) -> KVCache:
    """Write one token's k/v at the ring slot. k_new: (B, 1, KV, D)."""
    T = cache.k.shape[1]
    slot = cache.idx % T

    def write(buf, new):
        return jax.vmap(
            lambda b, n, s: jax.lax.dynamic_update_slice(b, n, (s, 0, 0))
        )(buf, new, slot)

    pos = jax.vmap(
        lambda p, s, val: jax.lax.dynamic_update_slice(p, val[None], (s,))
    )(cache.pos, slot, positions.astype(jnp.int32))
    return KVCache(k=write(cache.k, k_new), v=write(cache.v, v_new),
                   pos=pos, idx=cache.idx + 1)


# --------------------------------------------------------------------------
# GQA self-attention module
# --------------------------------------------------------------------------

def gqa_init(cfg: ArchConfig, key):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    wd = cfg.weight_dtype
    p = {"wq": dense_init(ks[0], (d, H * hd), wd),
         "wk": dense_init(ks[1], (d, KV * hd), wd),
         "wv": dense_init(ks[2], (d, KV * hd), wd),
         "wo": dense_init(ks[3], (H * hd, d), wd)}
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((H * hd,), wd)
        p["bk"] = jnp.zeros((KV * hd,), wd)
        p["bv"] = jnp.zeros((KV * hd,), wd)
    return p


def _gqa_qkv(cfg: ArchConfig, p, x, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q, k = rms_normalize(q), rms_normalize(k)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_prefill(cfg: ArchConfig, p, x, positions, *, causal: bool = True):
    """positions: (S,) — shared across batch during prefill."""
    q, k, v = _gqa_qkv(cfg, p, x, positions[None, :])
    out = chunked_attention(q, k, v, positions, positions,
                            window=cfg.attn_window, chunk=cfg.attn_chunk,
                            causal=causal)
    B, S = x.shape[:2]
    return out.reshape(B, S, -1) @ p["wo"]


def gqa_decode(cfg: ArchConfig, p, x, cache: KVCache, cur_pos):
    """x: (B, 1, d); cur_pos: (B,) absolute position of the new token."""
    q, k, v = _gqa_qkv(cfg, p, x, cur_pos[:, None])
    cache = cache_append(cache, k, v, cur_pos)
    out = decode_attention(q, cache.k, cache.v, cache.pos, cur_pos,
                           window=cfg.attn_window)
    B = x.shape[0]
    return out.reshape(B, 1, -1) @ p["wo"], cache


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2) — compressed KV cache + decoupled RoPE
# --------------------------------------------------------------------------

class MLACache(NamedTuple):
    ckv: jax.Array        # (B, T, kv_lora)
    krope: jax.Array      # (B, T, rope_hd)
    pos: jax.Array        # (B, T)
    idx: jax.Array        # (B,)


def init_mla_cache(batch: int, length: int, cfg: ArchConfig, dtype):
    m = cfg.mla
    return MLACache(
        ckv=jnp.zeros((batch, length, m.kv_lora_rank), dtype),
        krope=jnp.zeros((batch, length, m.rope_head_dim), dtype),
        pos=jnp.full((batch, length), -1, jnp.int32),
        idx=jnp.zeros((batch,), jnp.int32),
    )


def mla_init(cfg: ArchConfig, key):
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    wd = cfg.weight_dtype
    return {
        # queries: nope + rope parts
        "wq": dense_init(ks[0], (d, H * (m.q_head_dim + m.rope_head_dim)), wd),
        # compressed kv + shared k-rope
        "wdkv": dense_init(ks[1], (d, m.kv_lora_rank + m.rope_head_dim), wd),
        "wuk": dense_init(ks[2], (m.kv_lora_rank, H * m.q_head_dim), wd),
        "wuv": dense_init(ks[3], (m.kv_lora_rank, H * m.v_head_dim), wd),
        "wo": dense_init(ks[4], (H * m.v_head_dim, d), wd),
    }


def _mla_q(cfg: ArchConfig, p, x, positions):
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, H, m.q_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., :m.q_head_dim], q[..., m.q_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv(cfg: ArchConfig, p, x, positions):
    m = cfg.mla
    dkv = x @ p["wdkv"]
    ckv, krope = dkv[..., :m.kv_lora_rank], dkv[..., m.kv_lora_rank:]
    krope = apply_rope(krope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return ckv, krope


def _mla_expand(cfg: ArchConfig, p, ckv):
    """Up-project compressed cache to per-head k_nope / v."""
    m, H = cfg.mla, cfg.n_heads
    B, T, _ = ckv.shape
    k_nope = (ckv @ p["wuk"]).reshape(B, T, H, m.q_head_dim)
    v = (ckv @ p["wuv"]).reshape(B, T, H, m.v_head_dim)
    return k_nope, v


def mla_prefill(cfg: ArchConfig, p, x, positions):
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    q_nope, q_rope = _mla_q(cfg, p, x, positions[None, :])
    ckv, krope = _mla_kv(cfg, p, x, positions[None, :])
    k_nope, v = _mla_expand(cfg, p, ckv)
    # fold rope part in as extra head dims (shared krope broadcast per head)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope[:, :, None, :],
                                  (B, S, H, m.rope_head_dim))], axis=-1)
    # pad v to match head_dim for the shared kernel, then slice back
    out = chunked_attention(q, k,
                            jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                                        (0, q.shape[-1] - m.v_head_dim))),
                            positions, positions, window=cfg.attn_window,
                            chunk=cfg.attn_chunk)
    out = out[..., :m.v_head_dim].reshape(B, S, -1)
    return out @ p["wo"]


def mla_decode(cfg: ArchConfig, p, x, cache: MLACache, cur_pos):
    """Weight-absorbed MLA decode (DeepSeek-V2): scores are computed in the
    compressed kv_lora space — q_nope is absorbed through w_uk and the
    context is read in compressed space then expanded through w_uv, so the
    per-step cost is O(T · kv_lora) instead of O(T · H · head_dim)."""
    m, H = cfg.mla, cfg.n_heads
    B = x.shape[0]
    q_nope, q_rope = _mla_q(cfg, p, x, cur_pos[:, None])   # (B,1,H,·)
    ckv_new, krope_new = _mla_kv(cfg, p, x, cur_pos[:, None])
    T = cache.ckv.shape[1]
    slot = cache.idx % T
    wr = jax.vmap(lambda b, n, s: jax.lax.dynamic_update_slice(b, n, (s, 0)))
    cache = MLACache(
        ckv=wr(cache.ckv, ckv_new, slot),
        krope=wr(cache.krope, krope_new, slot),
        pos=jax.vmap(lambda pbuf, s, val:
                     jax.lax.dynamic_update_slice(pbuf, val[None], (s,)))(
                         cache.pos, slot, cur_pos.astype(jnp.int32)),
        idx=cache.idx + 1)
    # absorb w_uk into q: q_c (B,H,lora)
    wuk = p["wuk"].reshape(m.kv_lora_rank, H, m.q_head_dim)
    q_c = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0], wuk)
    scale = (m.q_head_dim + m.rope_head_dim) ** -0.5
    s_nope = jnp.einsum("bhl,btl->bht", q_c.astype(jnp.float32),
                        cache.ckv.astype(jnp.float32))
    s_rope = jnp.einsum("bhr,btr->bht", q_rope[:, 0].astype(jnp.float32),
                        cache.krope.astype(jnp.float32))
    s = (s_nope + s_rope) * scale
    valid = (cache.pos >= 0) & (cache.pos <= cur_pos[:, None])
    if cfg.attn_window:
        valid &= cache.pos > (cur_pos[:, None] - cfg.attn_window)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    ctx_c = jnp.einsum("bht,btl->bhl", w,
                       cache.ckv.astype(jnp.float32))      # compressed ctx
    wuv = p["wuv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bhl,lhd->bhd", ctx_c,
                     wuv.astype(jnp.float32)).astype(x.dtype)
    out = out.reshape(B, 1, H * m.v_head_dim)
    return out @ p["wo"], cache


# --------------------------------------------------------------------------
# cross-attention (whisper decoder -> encoder memory)
# --------------------------------------------------------------------------

def xattn_init(cfg: ArchConfig, key):
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    wd = cfg.weight_dtype
    return {"wq": dense_init(ks[0], (d, H * hd), wd),
            "wk": dense_init(ks[1], (cfg.encoder.d_embed or d, H * hd), wd),
            "wv": dense_init(ks[2], (cfg.encoder.d_embed or d, H * hd), wd),
            "wo": dense_init(ks[3], (H * hd, d), wd)}


def xattn_apply(cfg: ArchConfig, p, x, memory):
    """x: (B, S, d); memory: (B, M, d_embed). Non-causal full attention."""
    B, S, _ = x.shape
    M = memory.shape[1]
    H, hd = cfg.n_heads, cfg.head_dim_
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (memory @ p["wk"]).reshape(B, M, H, hd)
    v = (memory @ p["wv"]).reshape(B, M, H, hd)
    pos_q = jnp.arange(S)
    pos_kv = jnp.arange(M)
    out = chunked_attention(q, k, v, pos_q, pos_kv, chunk=min(cfg.attn_chunk, M),
                            causal=False)
    return out.reshape(B, S, -1) @ p["wo"]
