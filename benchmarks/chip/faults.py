"""Faults planted in the program under test, for the readings that set the
limits and for the tests that show the comparison catches them.

Each is a context manager that patches the program's flat round while it
is open; the round must be traced inside it.

* ``state_unchanged``: every global round returns its input state.
* ``half_agents``: the RSU aggregation leaves out every other agent and
  takes the weighted mean over the rest.
* ``half_batch``: every local step leaves out half of its minibatch and
  takes the mean loss over the rest.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def state_unchanged():
    from repro.fedsim import simulator
    return _patched(simulator, "_make_flat_round_body",
                    lambda *a, **k: (lambda state: state))


def half_agents():
    from repro.kernels import ops
    agg_blend = ops.agg_blend

    def agg(stacked, weights, mask, rsu_assign, n_rsus, prev):
        keep = (jnp.arange(mask.shape[0]) % 2 == 0).astype(mask.dtype)
        return agg_blend(stacked, weights, mask * keep, rsu_assign, n_rsus,
                         prev)
    return _patched(ops, "agg_blend", agg)


def half_batch():
    from repro.fedsim import simulator
    minibatch = simulator.agent_minibatch

    def take(x, y, step, batch):
        xb, yb = minibatch(x, y, step, batch)
        return xb[:batch // 2], yb[:batch // 2]
    return _patched(simulator, "agent_minibatch", take)


FAULTS = {"state_unchanged": state_unchanged, "half_agents": half_agents,
          "half_batch": half_batch}
