"""Model kind ``softreg``: softmax regression on Gaussian class blobs.

A model kind that the harness was not written for, kept as files only:
``tests/bench_chip/test_bench_chip_kinds.py`` copies it, with its config,
mix and limits, beside the benchmark's own files and runs it as a cell.
It trains on its own loss (``program_loss``), not the program's MLP.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import datagen
from benchmarks.chip.reference import make_dot

DATA_KEYS = frozenset(("labels_per_agent", "n_train", "n_test", "spread"))
PARTITIONS = ("scenario_two",)


def _blobs(rng, means, n, spread):
    y = rng.integers(0, means.shape[0], size=n).astype(np.int32)
    x = means[y] + spread * rng.standard_normal((n, means.shape[1]))
    return x.astype(np.float32), y


def make(t: Dict, config: Dict, run_seed: int) -> datagen.CellData:
    """Blobs around one mean per class from the data seed, split into
    scenario-II label shards; the starting model from the run's seed."""
    d, c = config["dim"], config["n_classes"]
    rng = np.random.default_rng(t["data_seed"])
    means = rng.standard_normal((c, d))
    x, y = _blobs(rng, means, t["n_train"], t["spread"])
    x_test, y_test = _blobs(rng, means, t["n_test"], t["spread"])
    idx, rsu_assign = datagen.scenario_two_idx(
        y, t["n_agents"], t["n_rsus"], t["labels_per_agent"],
        t["data_seed"], c)
    params = {"w": 0.1 * jax.random.normal(jax.random.key(run_seed), (d, c)),
              "b": jnp.zeros((c,), jnp.float32)}
    _, acc = evaluate(params, x_test, y_test)
    return datagen.CellData(
        x=jnp.asarray(x[idx]), y=jnp.asarray(y[idx]),
        n_per_agent=np.full((t["n_agents"],), idx.shape[1], np.int32),
        rsu_assign=rsu_assign, x_test=jnp.asarray(x_test),
        y_test=jnp.asarray(y_test), params=params, pre_acc=float(acc),
        pre_epochs=0)


def spec_fields(config: Dict, t: Dict) -> Dict:
    return {}


def _program_loss(params, x, y):
    logp = jax.nn.log_softmax(x @ params["w"] + params["b"], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


def program_loss(config: Dict) -> Callable:
    return _program_loss


def loss(params, x, y, dot):
    logp = jax.nn.log_softmax(dot(x, params["w"]) + params["b"], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


@jax.jit
def evaluate(params, x, y):
    logits = make_dot("fp32")(x, params["w"]) + params["b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))
    return nll, jnp.mean(jnp.argmax(logits, -1) == y)


def n_params(config: Dict) -> int:
    return config["dim"] * config["n_classes"] + config["n_classes"]


def flops_per_sample(config: Dict, t: Dict) -> int:
    """The forward product and the weight gradient; the data needs no
    input gradient."""
    return 4 * config["dim"] * config["n_classes"]
