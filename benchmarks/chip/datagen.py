"""A cell's data: the paper's synthetic MNIST-class task, the OEM pretrain
split and the scenario-II label-shard partition, made from the traffic's
data seed, and the biased pretrained model, made from the run's seed.

This is the benchmark's own copy of ``repro.data.synthetic.mnist_class_task``,
``repro.data.partition.pretrain_split`` / ``scenario_two`` and
``repro.fedsim.pretrain.pretrain_to_target``, kept here so that no later
change to the program can change the yardstick.  The recipe is the same;
the work is laid out for a fleet:

* labels, the pretrain split and every agent's shard come from the same
  numpy draws as the program's generator, so at one seed both give the same
  per-agent label shards and counts (``tests/bench_chip`` checks it at the
  paper's size);
* pixels are drawn on the device, only for the samples a run uses, each
  from a key folded from its index in the training set: the class prototype
  scaled by a brightness in [0.7, 1.3], plus Gaussian pixel noise, clipped
  to [0, 1.5], as in the program's generator;
* the pretrained model is trained on the device in one jitted call: plain
  SGD over the label-excluded pool, stopping at the first epoch whose test
  accuracy reaches the target (the program's stopping rule), with each
  epoch's order drawn from the seed.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

IMG_SIDE = 28
INPUT_DIM = IMG_SIDE * IMG_SIDE
N_CLASSES = 10


@dataclasses.dataclass(frozen=True)
class Shards:
    """Host-side index plan of one cell (numbers only, no pixels)."""
    protos: np.ndarray       # (10, 784) class prototypes
    y_train: np.ndarray      # (n_train,) labels of the whole training set
    y_test: np.ndarray       # (n_test,)
    pre_idx: np.ndarray      # (n_pre_kept,) train indices of the OEM pool
    agent_idx: np.ndarray    # (A, n) train indices of each agent's shard
    n_per_agent: np.ndarray  # (A,)
    rsu_assign: np.ndarray   # (A,)


def class_prototypes(rng: np.random.Generator) -> np.ndarray:
    """(10, 784) smooth prototype images, one per class (the program's
    generator, draw for draw)."""
    yy, xx = np.mgrid[0:IMG_SIDE, 0:IMG_SIDE].astype(np.float32)
    protos = []
    for c in range(N_CLASSES):
        img = np.zeros((IMG_SIDE, IMG_SIDE), np.float32)
        for _ in range(3 + c % 4):
            cx, cy = rng.uniform(4, IMG_SIDE - 4, size=2)
            sx, sy = rng.uniform(2.0, 5.0, size=2)
            amp = rng.uniform(0.6, 1.0)
            img += amp * np.exp(-(((xx - cx) / sx) ** 2
                                  + ((yy - cy) / sy) ** 2))
        img /= max(img.max(), 1e-6)
        protos.append(img)
    return np.stack(protos).reshape(N_CLASSES, INPUT_DIM)


def scenario_two_idx(y_fed: np.ndarray, n_agents: int, n_rsus: int,
                     labels_per_agent: int, seed: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Scenario II (label shards per agent) as an index plan into the
    federated pool: (A, n) indices and the RSU of each agent.

    Agent a holds ``labels_per_agent`` consecutive labels from
    ``(a * labels_per_agent + a // n_rsus) % 10``; each label's pool is a
    seeded permutation, consumed in order of agent and label, ``per_label``
    samples at a time.  A pool that would run dry is refused: the program
    then recycles its last chunk, which no cell's traffic should need."""
    rng = np.random.default_rng(seed)
    pools = [rng.permutation(np.where(y_fed == c)[0])
             for c in range(N_CLASSES)]
    per_label = max(len(y_fed) // (n_agents * labels_per_agent * 2), 8)
    a = np.arange(n_agents)
    start = (a * labels_per_agent + a // n_rsus) % N_CLASSES
    labs = (start[:, None] + np.arange(labels_per_agent)) % N_CLASSES
    flat = labs.ravel()                               # consumers, in order
    rank = np.zeros_like(flat)
    for c in range(N_CLASSES):
        sel = flat == c
        rank[sel] = np.arange(int(sel.sum()))
        need = int(sel.sum()) * per_label
        if need > len(pools[c]):
            raise ValueError(f"label {c}: {need} samples asked of a pool "
                             f"of {len(pools[c])}")
    cols = rank[:, None] * per_label + np.arange(per_label)
    take = np.stack([pools[c][k] for c, k in zip(flat, cols)])
    idx = take.reshape(n_agents, labels_per_agent * per_label)
    return idx, (a % n_rsus).astype(np.int32)


def plan(t: Dict, seed: int) -> Shards:
    """The cell's index plan from its traffic parameters and data seed."""
    rng = np.random.default_rng(seed)
    protos = class_prototypes(rng)
    y_train = rng.integers(0, N_CLASSES, size=t["n_train"]).astype(np.int32)
    y_test = np.random.default_rng(seed + 1).integers(
        0, N_CLASSES, size=t["n_test"]).astype(np.int32)
    # pretrain_split: the first frac of a seeded permutation, with the
    # excluded labels dropped, is the OEM pool; the rest is the fleet's
    perm = np.random.default_rng(seed).permutation(t["n_train"])
    n_pre = int(t["n_train"] * t["pretrain_frac"])
    pre, fed = perm[:n_pre], perm[n_pre:]
    pre = pre[~np.isin(y_train[pre], np.asarray(t["excluded_labels"]))]
    local, rsu_assign = scenario_two_idx(
        y_train[fed], t["n_agents"], t["n_rsus"], t["labels_per_agent"],
        seed)
    agent_idx = fed[local]
    n = agent_idx.shape[1]
    if n_pre != t["oem_pool"] or n != t["samples_per_agent"]:
        raise ValueError(f"traffic gives an OEM pool of {n_pre} and {n} "
                         f"samples per agent, not the {t['oem_pool']} and "
                         f"{t['samples_per_agent']} it states")
    return Shards(protos=protos, y_train=y_train, y_test=y_test,
                  pre_idx=pre, agent_idx=agent_idx,
                  n_per_agent=np.full((t["n_agents"],), n, np.int32),
                  rsu_assign=rsu_assign)


@functools.partial(jax.jit, static_argnames=("noise",))
def pixels(key, protos, idx, y, noise: float):
    """Pixels of the samples ``idx`` (any shape) with labels ``y``: sample
    i's brightness and noise come from ``fold_in(key, i)`` alone."""
    def one(i, c):
        kb, kn = jax.random.split(jax.random.fold_in(key, i))
        bright = jax.random.uniform(kb, (), jnp.float32, 0.7, 1.3)
        x = protos[c] * bright + noise * jax.random.normal(
            kn, (INPUT_DIM,), jnp.float32)
        return jnp.clip(x, 0.0, 1.5)
    flat = jax.vmap(one)(idx.reshape(-1), y.reshape(-1))
    return flat.reshape(idx.shape + (INPUT_DIM,))


def init_mlp(key, dims: Sequence[int]) -> Dict[str, jax.Array]:
    """He-normal weights, zero biases, keys split layer by layer (the
    program's initializer)."""
    params = {}
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        key, k = jax.random.split(key)
        params[f"w{i}"] = jax.random.normal(k, (d_in, d_out), jnp.float32) \
            * jnp.sqrt(2.0 / d_in)
        params[f"b{i}"] = jnp.zeros((d_out,), jnp.float32)
    return params


def _forward(params, x):
    n = len(params) // 2
    h = x
    for i in range(n):
        h = jnp.dot(h, params[f"w{i}"], precision="highest") \
            + params[f"b{i}"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def _loss(params, x, y):
    logp = jax.nn.log_softmax(_forward(params, x), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=(
    "dims", "lr", "batch", "target", "max_epochs"))
def pretrain(key, x_pre, y_pre, x_test, y_test, *, dims: Tuple[int, ...],
             lr: float, batch: int, target: float, max_epochs: int):
    """The biased OEM model: returns (params, test accuracy, epochs)."""
    k_init, k_order = jax.random.split(key)
    params = init_mlp(k_init, dims)
    n_batches = x_pre.shape[0] // batch

    def acc(p):
        return jnp.mean(jnp.argmax(_forward(p, x_test), -1) == y_test)

    def epoch(carry):
        p, _, e = carry
        order = jax.random.permutation(jax.random.fold_in(k_order, e),
                                       x_pre.shape[0])
        order = order[:n_batches * batch].reshape(n_batches, batch)

        def step(p, take):
            g = jax.grad(_loss)(p, x_pre[take], y_pre[take])
            return jax.tree.map(lambda w, gw: w - lr * gw, p, g), None

        p, _ = jax.lax.scan(step, p, order)
        return p, acc(p), e + 1

    def more(carry):
        _, a, e = carry
        return (e < max_epochs) & (a < target)

    return jax.lax.while_loop(more, epoch, (params, acc(params),
                                            jnp.int32(0)))


@dataclasses.dataclass
class CellData:
    """Device arrays of one cell."""
    x: jax.Array             # (A, n, 784) agents' samples
    y: jax.Array             # (A, n)
    n_per_agent: np.ndarray  # (A,)
    rsu_assign: np.ndarray   # (A,)
    x_test: jax.Array
    y_test: jax.Array
    params: Dict[str, jax.Array]   # the pretrained model
    pre_acc: float
    pre_epochs: int


def make(t: Dict, dims: Tuple[int, ...], run_seed: int) -> CellData:
    """Everything a cell's run needs.  The dataset and its partition come
    from the traffic's ``data_seed``; the pretrained model from the run's
    seed."""
    s = plan(t, int(t["data_seed"]))
    key = jax.random.key(int(t["data_seed"]))
    k_train, k_test = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
    k_pre = jax.random.key(run_seed)
    protos = jnp.asarray(s.protos)
    noise = float(t["noise"])
    y_agents = s.y_train[s.agent_idx]
    x = pixels(k_train, protos, jnp.asarray(s.agent_idx, jnp.int32),
               jnp.asarray(y_agents), noise)
    x_pre = pixels(k_train, protos, jnp.asarray(s.pre_idx, jnp.int32),
                   jnp.asarray(s.y_train[s.pre_idx]), noise)
    x_test = pixels(k_test, protos, jnp.arange(len(s.y_test)),
                    jnp.asarray(s.y_test), noise)
    y_test = jnp.asarray(s.y_test)
    params, acc, epochs = pretrain(
        k_pre, x_pre, jnp.asarray(s.y_train[s.pre_idx]), x_test, y_test,
        dims=tuple(dims), lr=float(t["pretrain_lr"]), batch=int(t["batch"]),
        target=float(t["pretrain_target"]),
        max_epochs=int(t["pretrain_max_epochs"]))
    return CellData(x=x, y=jnp.asarray(y_agents), n_per_agent=s.n_per_agent,
                    rsu_assign=s.rsu_assign, x_test=x_test, y_test=y_test,
                    params=params, pre_acc=float(acc),
                    pre_epochs=int(epochs))
