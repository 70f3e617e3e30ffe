"""What a cell's data is, whatever the model: the device arrays a run
hands to the program and to the reference, and the scenario-II index
split that a model kind's recipe may use.

Each model kind makes its own data (``models/<kind>.py``, ``make``); the
split here is the benchmark's copy of the index plan of
``repro.data.partition.scenario_two``, kept here so that no later change
to the program can change the yardstick.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import numpy as np


@dataclasses.dataclass
class CellData:
    """Device arrays of one cell."""
    x: jax.Array             # (A, n, ...) agents' samples
    y: jax.Array             # (A, n, ...)
    n_per_agent: np.ndarray  # (A,)
    rsu_assign: np.ndarray   # (A,)
    x_test: jax.Array
    y_test: jax.Array
    params: Dict[str, jax.Array]   # the model the window starts from
    pre_acc: float
    pre_epochs: int


def scenario_two_idx(y_fed: np.ndarray, n_agents: int, n_rsus: int,
                     labels_per_agent: int, seed: int, n_labels: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Scenario II (label shards per agent) as an index plan into the
    federated pool, whose labels are ``y_fed`` in [0, n_labels): (A, n)
    indices and the RSU of each agent.

    Agent a holds ``labels_per_agent`` consecutive labels from
    ``(a * labels_per_agent + a // n_rsus) % n_labels``; each label's pool
    is a seeded permutation, consumed in order of agent and label,
    ``per_label`` samples at a time.  A pool that would run dry is refused:
    the program then recycles its last chunk, which no cell's traffic
    should need."""
    rng = np.random.default_rng(seed)
    pools = [rng.permutation(np.where(y_fed == c)[0])
             for c in range(n_labels)]
    per_label = max(len(y_fed) // (n_agents * labels_per_agent * 2), 8)
    a = np.arange(n_agents)
    start = (a * labels_per_agent + a // n_rsus) % n_labels
    labs = (start[:, None] + np.arange(labels_per_agent)) % n_labels
    flat = labs.ravel()                               # consumers, in order
    rank = np.zeros_like(flat)
    for c in range(n_labels):
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
