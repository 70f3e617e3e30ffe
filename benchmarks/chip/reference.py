"""Plain reference of the flat H²-Fed round (paper Alg. 1-3), in fp32.

Written from the algorithm, importing nothing of the program:

1. every RSU starts the global round from the cloud model;
2. LAR local rounds, each: the connectivity draw (CSR with a stable
   connection of SCD rounds) and the FSR epoch draw; every agent runs its
   completed epochs of minibatch SGD on the dual-proximal objective
   ``F(w) + mu1/2 |w - w_rsu|^2 + mu2/2 |w - w_cloud|^2`` from its RSU's
   model, on cyclic minibatches of its shard; each RSU takes the
   data-weighted mean of its connected agents, or keeps its model when none
   connected;
3. the cloud takes the mean of the RSU models weighted by the data mass
   that reached each RSU over the LAR rounds, or keeps its model.

The draws follow the simulator's published key discipline (one split of
the round key per global round, ``fold_in`` per local round, then a split
into a connectivity and an FSR key), so the same seed gives the same
realization.  Agents are trained and summed in blocks, so the reference
fits beside nothing else on the chip.  Matrix products go through
``make_dot``: fp32 at the highest precision, or the three-pass bfloat16
product that is the control.

The algebra is the same for every model: the model's own plain loss,
``loss(params, x, y, dot)``, comes from its kind's module
(``models/<kind>.py``) and is the only part of training that depends on
the model.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, jax.Array]
BLOCK_BYTES = 1 << 30      # one (block, N) fp32 copy of the agents' models


def _bf16x3(a, b):
    """a @ b from three bfloat16 products accumulated in fp32 (the TPU's
    ``high`` precision): each factor splits into a bfloat16 head and a
    bfloat16 tail, and the tail x tail term is dropped.  The split rounds
    with ``reduce_precision``, which the compiler keeps; a round trip
    through a bfloat16 cast may be folded away as excess precision."""
    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)
    (ah, al), (bh, bl) = split(a), split(b)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def _fp32(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def make_dot(mode: str) -> Callable:
    """Matrix product whose forward and backward products are all taken in
    ``mode``: "fp32" (highest precision) or "bf16x3" (the control)."""
    mm = {"fp32": _fp32, "bf16x3": _bf16x3}[mode]

    @jax.custom_vjp
    def dot(a, b):
        return mm(a, b)

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return mm(g, b.T), mm(a.T, g)

    dot.defvjp(fwd, bwd)
    return dot


def draws(key, remaining, t: Dict, n_agents: int, spe: int):
    """One local round's realization: (remaining', connected-and-trained
    mask (A,), completed minibatch steps (A,))."""
    k_conn, k_fsr = jax.random.split(key)
    rem = jnp.maximum(remaining - 1, 0)
    hit = jax.random.bernoulli(k_conn, t["csr"], (n_agents,))
    rem = jnp.where((rem == 0) & hit, t["scd"], rem)
    full = jax.random.bernoulli(k_fsr, t["fsr"], (n_agents,))
    e = t["local_epochs"]
    epochs = jnp.where(full, e, jax.random.randint(
        jax.random.fold_in(k_fsr, 1), (n_agents,), 0, max(e, 1)))
    steps = epochs * spe
    return rem, (rem > 0) & (steps > 0), steps


def round_keys(rng, lar: int):
    """(rng', the LAR local-round keys) of one global round."""
    rng, k = jax.random.split(rng)
    return rng, [jax.random.fold_in(k, i) for i in range(lar)]


def realized(sim_seed: int, t: Dict, n_agents: int, n_rounds: int,
             spe: int, rsu_assign: np.ndarray, n_rsus: int) -> Dict:
    """What the draws of ``n_rounds`` global rounds leave to compute, per
    local round: the agents that reach their RSU, their completed
    minibatch steps, and the RSUs that receive data; per global round, the
    RSUs that carry mass to the cloud."""
    assign = jnp.asarray(rsu_assign)

    @jax.jit
    def one_round(rng, rem):
        rng, keys = round_keys(rng, t["lar"])

        def local(rem, key):
            rem, mask, steps = draws(key, rem, t, n_agents, spe)
            per_rsu = jax.ops.segment_sum(mask.astype(jnp.int32), assign,
                                          num_segments=n_rsus)
            return rem, (jnp.sum(mask), jnp.sum(jnp.where(mask, steps, 0)),
                         jnp.sum(per_rsu > 0), per_rsu)

        rem, (conn, steps, hit, per_rsu) = jax.lax.scan(
            local, rem, jnp.stack(keys))
        return rng, rem, (conn, steps, hit, jnp.sum(jnp.sum(per_rsu, 0) > 0))

    rng = jax.random.key(sim_seed)
    rem = jnp.zeros((n_agents,), jnp.int32)
    out = []
    for _ in range(n_rounds):
        rng, rem, counts = one_round(rng, rem)
        out.append(counts)
    conn, steps, hit, cloud = (np.asarray(jnp.stack(c)) for c in zip(*out))
    return {"connected": conn, "live_steps": steps, "rsus_hit": hit,
            "cloud_rsus": cloud}


@functools.partial(jax.jit,
                   static_argnames=("t_items", "block", "mode", "loss"))
def _train_block(start, x, y, share, assign, rsu, cloud, steps, *,
                 t_items, block: int, mode: str, loss: Callable):
    """Train agents [start, start + block) from their RSU's model on
    ``loss``; return their part of each RSU's weighted mean, where
    ``share`` is each agent's weight in its RSU's mean (0 for an agent
    that did not reach it)."""
    t = dict(t_items)
    dot = make_dot(mode)
    n_rsus = jax.tree.leaves(rsu)[0].shape[0]

    def take(a):
        return jax.lax.dynamic_slice_in_dim(a, start, block, 0)

    xb, yb, wb, ab, sb = map(take, (x, y, share, assign, steps))
    n = xb.shape[1]
    batch = t["batch"]
    n_steps = t["local_epochs"] * max(n // batch, 1)

    def one_agent(xa, ya, r, active):
        w0 = jax.tree.map(lambda l: l[r], rsu)

        def step(w, s):
            idx = (s * batch % n + jnp.arange(batch)) % n
            g = jax.grad(loss)(w, xa[idx], ya[idx], dot)
            live = (s < active).astype(jnp.float32)
            w = jax.tree.map(
                lambda wl, gl, rl, cl: wl - t["lr"] * live * (
                    gl + t["mu1"] * (wl - rl) + t["mu2"] * (wl - cl)),
                w, g, w0, cloud)
            return w, None

        w, _ = jax.lax.scan(step, w0, jnp.arange(n_steps))
        return w

    trained = jax.vmap(one_agent)(xb, yb, ab, sb)
    # each RSU's weighted mean is a product with the (R, block) matrix of
    # the agents' shares
    w = jnp.where(ab[None, :] == jnp.arange(n_rsus)[:, None], wb[None, :],
                  0.0)
    return jax.tree.map(
        lambda l: dot(w, l.reshape(block, -1)).reshape((n_rsus,) + l.shape[1:]),
        trained)


@functools.partial(jax.jit, static_argnames=("mode",))
def _cloud(rsu, total, cloud, mode: str):
    """The cloud's mean of the RSU models weighted by the mass each
    received, as a (1, R) product; the cloud keeps its model when no RSU
    received anything."""
    dot = make_dot(mode)
    tot = jnp.sum(total)
    wn = (total / jnp.where(tot > 0, tot, 1.0))[None, :]
    return jax.tree.map(
        lambda rl, cl: jnp.where(
            tot > 0, dot(wn, rl.reshape(rl.shape[0], -1)).reshape(cl.shape),
            cl), rsu, cloud)


def simulate(params: Params, x, y, n_per_agent, rsu_assign, t: Dict,
             sim_seed: int, n_rounds: int, *, loss: Callable,
             mode: str = "fp32") -> List[Params]:
    """The cloud model after each of ``n_rounds`` global rounds from
    ``params``, training on the model's plain ``loss`` with matrix
    products in ``mode``."""
    n_agents, n = int(x.shape[0]), int(x.shape[1])
    n_rsus = t["n_rsus"]
    spe = max(n // t["batch"], 1)
    n_par = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    most = max(1, BLOCK_BYTES // (4 * n_par))
    n_blocks = -(-n_agents // most)
    block = -(-n_agents // n_blocks)
    pad = n_blocks * block - n_agents

    def padded(a, fill=0):
        a = jnp.asarray(a)
        if not pad:
            return a
        tail = jnp.full((pad,) + a.shape[1:], fill, a.dtype)
        return jnp.concatenate([a, tail])

    xs, ys = padded(x), padded(y)
    weight = padded(jnp.asarray(n_per_agent, jnp.float32))
    assign = padded(jnp.asarray(rsu_assign, jnp.int32))
    t_items = tuple(sorted((k, v) for k, v in t.items()
                           if isinstance(v, (int, float))))
    rng = jax.random.key(sim_seed)
    rem = jnp.zeros((n_agents,), jnp.int32)
    cloud = jax.tree.map(lambda l: l.astype(jnp.float32), params)
    out = []
    for _ in range(n_rounds):
        rsu = jax.tree.map(lambda l: jnp.broadcast_to(l, (n_rsus,) + l.shape),
                           cloud)
        rng, keys = round_keys(rng, t["lar"])
        total = jnp.zeros((n_rsus,), jnp.float32)
        for key in keys:
            rem, mask, steps = draws(key, rem, t, n_agents, spe)
            w = padded(mask, False) * weight
            mass = jax.ops.segment_sum(w, assign, num_segments=n_rsus)
            share = w / jnp.where(mass > 0, mass, 1.0)[assign]
            mean = jax.tree.map(jnp.zeros_like, rsu)
            for b in range(n_blocks):
                part = _train_block(
                    b * block, xs, ys, share, assign, rsu, cloud,
                    padded(steps), t_items=t_items, block=block, mode=mode,
                    loss=loss)
                mean = jax.tree.map(jnp.add, mean, part)
            rsu = jax.tree.map(
                lambda ml, ol: jnp.where(
                    (mass > 0).reshape((-1,) + (1,) * (ml.ndim - 1)), ml, ol),
                mean, rsu)
            total = total + mass
        cloud = _cloud(rsu, total, cloud, mode)
        out.append(jax.device_get(cloud))
    return out
