"""Model kind ``mlp``: the paper's classification MLP (input, ReLU hidden
layers, softmax cross-entropy) on its synthetic MNIST-class task.

A config names this module with ``"model": "mlp"`` and gives
``input_dim``, ``hidden_dims`` and ``n_classes``.  What the harness takes
from it:

* the data recipe (``DATA_KEYS``, ``PARTITIONS``, ``make``): the
  benchmark's own copy of ``repro.data.synthetic.mnist_class_task``,
  ``repro.data.partition.pretrain_split`` / ``scenario_two`` and
  ``repro.fedsim.pretrain.pretrain_to_target``, kept here so that no later
  change to the program can change the yardstick.  The recipe is the
  same; the work is laid out for a fleet:

  - labels, the pretrain split and every agent's shard come from the same
    numpy draws as the program's generator, so at one seed both give the
    same per-agent label shards and counts (``tests/bench_chip`` checks it
    at the paper's size);
  - pixels are drawn on the device, only for the samples a run uses, each
    from a key folded from its index in the training set: the class
    prototype scaled by a brightness in [0.7, 1.3], plus Gaussian pixel
    noise, clipped to [0, 1.5], as in the program's generator;
  - the pretrained model is trained on the device in one jitted call:
    plain SGD over the label-excluded pool, stopping at the first epoch
    whose test accuracy reaches the target (the program's stopping rule),
    with each epoch's order drawn from the seed;

* the program's side (``spec_fields``, ``program_loss``): the
  ``ScenarioSpec`` fields of the recipe and the loss the system under
  test trains with;
* the plain reference model (``loss``, ``evaluate``), which imports
  nothing of the program;
* the work counts (``n_params``, ``flops_per_sample``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import datagen
from benchmarks.chip.reference import make_dot

IMG_SIDE = 28
INPUT_DIM = IMG_SIDE * IMG_SIDE
N_CLASSES = 10

# the traffic keys this recipe reads, besides the harness's own
DATA_KEYS = frozenset((
    "labels_per_agent", "n_train", "n_test", "noise", "excluded_labels",
    "pretrain_frac", "oem_pool", "pretrain_target", "pretrain_lr",
    "pretrain_max_epochs"))
PARTITIONS = ("scenario_two",)


# -- data -----------------------------------------------------------------

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
    local, rsu_assign = datagen.scenario_two_idx(
        y_train[fed], t["n_agents"], t["n_rsus"], t["labels_per_agent"],
        seed, N_CLASSES)
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


def make(t: Dict, config: Dict, run_seed: int) -> datagen.CellData:
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
        dims=tuple(layer_dims(config)), lr=float(t["pretrain_lr"]),
        batch=int(t["batch"]), target=float(t["pretrain_target"]),
        max_epochs=int(t["pretrain_max_epochs"]))
    return datagen.CellData(
        x=x, y=jnp.asarray(y_agents), n_per_agent=s.n_per_agent,
        rsu_assign=s.rsu_assign, x_test=x_test, y_test=y_test,
        params=params, pre_acc=float(acc), pre_epochs=int(epochs))


# -- the program's side -----------------------------------------------------

def spec_fields(config: Dict, t: Dict) -> Dict:
    """The ``ScenarioSpec`` fields of this recipe and model."""
    return dict(n_train=t["n_train"], n_test=t["n_test"], noise=t["noise"],
                excluded_labels=tuple(t["excluded_labels"]),
                pretrain_frac=t["pretrain_frac"],
                pretrain_target=t["pretrain_target"],
                hidden_dims=tuple(config["hidden_dims"]))


def program_loss(config: Dict) -> Callable:
    """The loss ``run_scenario`` trains with: the program's MLP loss."""
    from repro.models import mlp
    return mlp.loss_fn


# -- the plain reference ----------------------------------------------------

def forward(params, x, dot):
    n = len(params) // 2
    h = x
    for i in range(n):
        h = dot(h, params[f"w{i}"]) + params[f"b{i}"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def loss(params, x, y, dot):
    """Mean cross-entropy, with every matrix product taken by ``dot``."""
    logp = jax.nn.log_softmax(forward(params, x, dot), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


@jax.jit
def evaluate(params, x, y):
    """(test loss, test accuracy) in fp32."""
    dot = make_dot("fp32")
    logits = forward(params, x, dot)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))
    return nll, jnp.mean(jnp.argmax(logits, -1) == y)


# -- work counts --------------------------------------------------------------

def layer_dims(config: Dict) -> Sequence[int]:
    return ([config["input_dim"]] + list(config["hidden_dims"])
            + [config["n_classes"]])


def n_params(config: Dict) -> int:
    d = layer_dims(config)
    return sum(a * b + b for a, b in zip(d[:-1], d[1:]))


def flops_per_sample(config: Dict, t: Dict) -> int:
    """Matrix-product operations of one training sample: the forward
    product, the weight gradient, and the input gradient of every layer
    but the first (the data needs no gradient).  Bias adds, activations
    and the update are left out: they are under 2% of the products at
    these widths."""
    d = layer_dims(config)
    prods = [a * b for a, b in zip(d[:-1], d[1:])]
    return 2 * sum(prods) + 2 * sum(prods) + 2 * sum(prods[1:])
