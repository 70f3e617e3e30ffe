"""Shared fixtures.  NOTE: device count is NOT forced here — smoke tests and
benches see the single real CPU device; anything needing >1 device runs
through ``run_forced_devices`` below, which forces
``XLA_FLAGS=--xla_force_host_platform_device_count`` in a SUBPROCESS before
its jax initializes (the launch/dryrun mechanism) so the main pytest process
keeps the single real CPU device."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)
# the suite compiles hundreds of small programs across parallel workers;
# keep them out of the persistent compilation cache (tests/
# test_program_cache.py exercises the cache in child processes)
jax.config.update("jax_enable_compilation_cache", False)

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_forced_devices(code: str, devices: int = 8,
                       timeout: int = 600) -> str:
    """Run ``code`` in a subprocess with ``devices`` forced host devices.

    The single shared implementation of the forced-device-count setup used
    by test_sharded.py, test_launch.py and test_async.py (multi-device
    cases); asserts a zero exit and returns stdout.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.fixture(scope="session")
def forced_devices_run():
    """Fixture handle on ``run_forced_devices`` for multi-device tests."""
    return run_forced_devices


@pytest.fixture(scope="session")
def tiny_task():
    """Small synthetic classification task shared across federated tests."""
    from repro.data.synthetic import mnist_class_task
    train, test = mnist_class_task(n_train=3000, n_test=600, seed=0)
    return train, test


@pytest.fixture(scope="session")
def mlp_params():
    from repro.configs.mnist_mlp import CONFIG
    from repro.models import mlp
    return mlp.init_params(CONFIG, jax.random.key(42))


@pytest.fixture(scope="session")
def fed_small(tiny_task):
    """Small federated split: 20 agents, 4 RSUs (scenario II)."""
    from repro.data.partition import scenario_two
    train, _ = tiny_task
    return scenario_two(train, n_agents=20, n_rsus=4, seed=0)


def rand(shape, dtype=np.float32, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape) * scale).astype(dtype)
