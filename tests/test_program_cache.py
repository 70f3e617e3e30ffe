"""core/program_cache: persistent XLA cache wiring, ProgramKey identity,
registry semantics and the per-spec opt-out (DESIGN.md §10)."""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.mnist_mlp import CONFIG as MLP_CFG
from repro.core import program_cache
from repro.core.h2fed import H2FedParams
from repro.core.heterogeneity import HeterogeneityModel
from repro.core.scenario import ScenarioSpec
from repro.fedsim import sweep
from repro.kernels import ops
from repro.models import mlp

BASE = ScenarioSpec(n_agents=8, n_rsus=4, batch=8, n_train=400, n_test=100,
                    hp=H2FedParams(mu1=0.01, mu2=0.005, lar=2,
                                   local_epochs=1, lr=0.1),
                    het=HeterogeneityModel(csr=0.8, scd=1), rounds=2)


@pytest.fixture(scope="module")
def params():
    return mlp.init_params(MLP_CFG, jax.random.key(42))


def _key(**overrides):
    base = dict(kind="sweep", static_key=("flat",), n_scenarios=2,
                dyn_names=("hp.mu1",), baked=(("hp.lr", 0.1),),
                cadence=None, data_axes=((("x", 0),), 0, 0),
                donation=(0,),
                devices=program_cache.device_fingerprint(),
                mesh=None, flags=program_cache.ops_flags(True))
    base.update(overrides)
    return program_cache.ProgramKey(**base)


class TestProgramKey:
    def test_key_is_hashable_and_stable(self):
        assert _key() == _key()
        assert hash(_key()) == hash(_key())

    def test_key_changes_with_interpret_flag(self):
        """An interpret flip MUST miss the registry: the traced program
        routes through different kernel lowerings."""
        prev = ops._FORCE_INTERPRET
        try:
            ops.set_interpret(True)
            k_interp = _key(flags=program_cache.ops_flags(True))
            ops.set_interpret(False)
            k_pallas = _key(flags=program_cache.ops_flags(True))
        finally:
            ops.set_interpret(prev)
        assert k_interp != k_pallas

    def test_key_changes_with_fused_flag(self):
        assert _key(flags=program_cache.ops_flags(True)) != \
            _key(flags=program_cache.ops_flags(False))

    def test_key_changes_with_mesh_fingerprint(self):
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("sweep",))
        assert _key(mesh=program_cache.mesh_fingerprint(mesh)) != \
            _key(mesh=None)

    def test_mesh_fingerprint_carries_axes_and_devices(self):
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("sweep",))
        axes, devs = program_cache.mesh_fingerprint(mesh)
        assert axes == (("sweep", 1),)
        assert devs == program_cache.device_fingerprint(jax.devices()[:1])
        assert program_cache.mesh_fingerprint(None) is None


class TestRegistry:
    def test_get_or_build_memoizes(self):
        program_cache.clear()
        calls = []
        k = _key()
        a = program_cache.get_or_build(k, lambda: calls.append(1) or "A")
        b = program_cache.get_or_build(k, lambda: calls.append(1) or "B")
        assert (a, b) == ("A", "A") and len(calls) == 1
        s = program_cache.stats()
        assert (s["misses"], s["hits"], s["entries"]) == (1, 1, 1)

    def test_disabled_never_touches_registry(self):
        program_cache.clear()
        k = _key()
        out = program_cache.get_or_build(k, lambda: "fresh", enabled=False)
        assert out == "fresh"
        assert program_cache.stats()["entries"] == 0

    def test_build_sweep_registry_hit_returns_same_program(self, params):
        program_cache.clear()
        specs = [BASE.replace(
            hp=dataclasses.replace(BASE.hp, mu1=m)) for m in (0.0, 0.02)]
        resolved = [s.resolve() for s in specs]
        p1 = sweep.build_sweep(resolved, params)
        p2 = sweep.build_sweep(resolved, params)
        # the jitted round program is the registry entry; eval_fn is a
        # thin per-build closure over the test set around a cached jit
        assert p2.round_fn is p1.round_fn
        assert program_cache.stats()["hits"] >= 1

    def test_program_cache_opt_out_builds_fresh(self, params):
        program_cache.clear()
        specs = [BASE.replace(
            hp=dataclasses.replace(BASE.hp, mu1=m),
            program_cache=False) for m in (0.0, 0.02)]
        resolved = [s.resolve() for s in specs]
        p1 = sweep.build_sweep(resolved, params)
        p2 = sweep.build_sweep(resolved, params)
        assert p2.round_fn is not p1.round_fn
        assert program_cache.stats()["entries"] == 0

    def test_trace_counters(self):
        program_cache.reset_stats()
        program_cache.note_trace("x")
        program_cache.note_trace("x")
        assert program_cache.trace_count("x") == 2
        assert program_cache.stats()["traces/x"] == 2
        program_cache.reset_stats()
        assert program_cache.trace_count("x") == 0


ROOT = Path(__file__).resolve().parents[1]

CACHE_CODE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.core import program_cache
    d = program_cache.enable_persistent_cache()
    x = jax.jit(lambda v: (v * 2.0 + 1.0).sum())(jnp.ones((8, 8)))
    x.block_until_ready()
    print("PERSIST", d, jax.config.jax_compilation_cache_dir)
""")


def _cache_child(env_dir=None):
    env = dict(os.environ,
               PYTHONPATH=str(ROOT / "src") + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env.pop(program_cache.ENV_CACHE_DIR, None)
    if env_dir is not None:
        env[program_cache.ENV_CACHE_DIR] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", CACHE_CODE], cwd=ROOT,
                         env=env, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("PERSIST")]
    return line[-1].split()[1:]


class TestPersistentCache:
    def test_enable_persistent_cache_writes_entries(self, tmp_path):
        """Fresh processes (config flags are process-global): with
        ``JAX_COMPILATION_CACHE_DIR`` set, the cache lives there and only
        there — no directory is set over it — and a jitted program lands
        entries on disk that a second process loads (the cold/warm
        contract CI pins)."""
        cache = tmp_path / "xla-cache"
        for _ in range(2):      # cold run writes, warm run reads
            active, jax_dir = _cache_child(cache)
            assert active == jax_dir == str(cache)
            assert any(cache.iterdir()), "no cache entries written"

    def test_env_var_unset_is_noop(self):
        """Unset, the variable adds nothing: the cache goes to one fixed,
        git-ignored path inside the checkout, so every process of the
        checkout shares it."""
        active, jax_dir = _cache_child()
        assert active == jax_dir == program_cache.DEFAULT_CACHE_DIR
        default = Path(program_cache.DEFAULT_CACHE_DIR)
        assert default.parent == ROOT
        assert f"{default.name}/" in (ROOT / ".gitignore").read_text()
