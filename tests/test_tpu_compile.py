"""Ahead-of-time compiles of the aggregation kernels and the paper's flat
round for a described TPU v5e (no chip attached).

Every case compiles through the ``kernels.ops`` entry the engines call,
with the compiled Pallas route forced on, and asserts the kernel lowered
to a Mosaic ``tpu_custom_call``.  The compiler refuses here what the chip
would refuse (VMEM overflow, unaligned tiles), so these guard the tile
plan at the shapes the engines pass.  Nothing runs, so nothing here says
anything about results or times.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and a test-collection-time call would
make parallel workers collect different tests.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

A, R, N = 100, 10, 31_810              # the paper's Sec. VI fleet and MLP


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                         # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip, with the compiled Pallas route forced on
    and the persistent compile cache off (an entry compiled for a
    described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev_route = ops._FORCE_INTERPRET
    prev_cache = jax.config.jax_enable_compilation_cache
    ops.set_interpret(False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    ops.set_interpret(prev_route)
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    cc.reset_cache()


def _sds(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _entry(name: str, a: int, r: int, n: int):
    """(fn, arg shape builder) for one ``ops`` aggregation entry."""
    if name == "agg_blend":
        return (lambda x, w, m, asg, prev: ops.agg_blend(
                    x, w, m, asg, r, prev),
                lambda c, dt: (_sds(c, (a, n), dt), _sds(c, (a,)),
                               _sds(c, (a,)), _sds(c, (a,), jnp.int32),
                               _sds(c, (r, n), dt)))
    if name == "agg_absorb":              # the async tick's two cohorts
        return (lambda x1, w1, x2, w2, asg, buf, bm: ops.agg_absorb(
                    ((x1, w1), (x2, w2)), asg, r, buf, bm, keep=0.5),
                lambda c, dt: (_sds(c, (a, n), dt), _sds(c, (a,)),
                               _sds(c, (a, n), dt), _sds(c, (a,)),
                               _sds(c, (a,), jnp.int32),
                               _sds(c, (r, n), dt), _sds(c, (r,))))
    if name in ("chunk_agg", "block_local_agg"):
        op = getattr(ops, name)
        return (lambda x, w, asg: op(x, w, asg, r),
                lambda c, dt: (_sds(c, (a, n), dt), _sds(c, (a,)),
                               _sds(c, (a,), jnp.int32)))
    if name == "cloud_blend":
        return (ops.cloud_blend,
                lambda c, dt: (_sds(c, (r, n), dt), _sds(c, (r,)),
                               _sds(c, (n,))))
    raise KeyError(name)


ENTRIES = ("agg_blend", "agg_absorb", "chunk_agg", "block_local_agg",
           "cloud_blend")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", ENTRIES)
def test_paper_shapes_compile(chip, name, dtype):
    fn, shapes = _entry(name, A, R, N)
    assert "tpu_custom_call" in _compiled_text(fn, *shapes(chip, dtype))


@pytest.mark.parametrize("name,dtype", [
    ("agg_blend", jnp.float32), ("agg_absorb", jnp.float32),
    ("chunk_agg", jnp.float32), ("agg_absorb", jnp.bfloat16)],
    ids=["agg_blend-f32", "agg_absorb-f32", "chunk_agg-f32",
         "agg_absorb-bf16"])
def test_wide_fleet_compiles(chip, name, dtype):
    """A = 2,000 resident agents: the N tile narrows with A to fit VMEM
    (a fixed 2048-lane tile overflows it from A ~ 1,000 on in fp32)."""
    fn, shapes = _entry(name, 2_000, R, N)
    assert "tpu_custom_call" in _compiled_text(fn, *shapes(chip, dtype))


@pytest.mark.parametrize("a,n", [(4, 1 << 20), (16_384, 68)],
                         ids=["two_axis_tile", "streamed_fleet_chunk"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_streamed_chunk_compiles(chip, a, n, dtype):
    """The streamed engines' chunk shapes: a two-axis N-tile of 4 agents,
    and a 16,384-agent chunk of the tiny-linear fleet cell."""
    fn, shapes = _entry("chunk_agg", a, R, n)
    assert "tpu_custom_call" in _compiled_text(fn, *shapes(chip, dtype))


def test_wide_fleet_past_one_lane_tile_is_refused(chip):
    fn, shapes = _entry("agg_blend", 20_000, R, N)
    with pytest.raises(ValueError, match="A-blocked reduction grid"):
        _compiled_text(fn, *shapes(chip, jnp.float32))


def test_paper_flat_round_compiles(chip):
    """The whole flat-engine round at the paper's fleet width (A = 100,
    R = 10, the 784-40-10 MLP), both aggregation layers on the kernel."""
    from repro.configs.mnist_mlp import CONFIG
    from repro.core import flatten
    from repro.core.scenario import ScenarioSpec
    from repro.fedsim import simulator
    from repro.models import mlp

    ops.set_interpret(False)
    spec = ScenarioSpec(n_agents=A, n_rsus=R, n_train=3_000, n_test=100,
                        rounds=1, partition="scenario_two",
                        excluded_labels=(7, 8, 9))
    res = spec.resolve()
    params = mlp.init_params(CONFIG, jax.random.key(0))
    fspec = flatten.spec_of(params)
    assert fspec.n == N
    round_fn = simulator.make_flat_global_round(res.cfg, spec.hp, spec.het,
                                                res.fed, fspec)
    state = jax.eval_shape(lambda: simulator.init_flat_state(
        res.cfg, fspec, params, jax.random.key(0)))
    state = jax.tree.map(lambda s: _sds(chip, s.shape, s.dtype), state)
    text = round_fn.lower(state).compile().as_text()
    assert text.count("tpu_custom_call") >= 2      # RSU and cloud layers
