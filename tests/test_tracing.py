"""The program's instrumentation: named scopes in the flat round's HLO,
host spans around each round and eval, and the compile counters of
core/program_cache."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.configs.mnist_mlp import CONFIG as MLP_CFG
from repro.core import flatten, program_cache
from repro.core.h2fed import H2FedParams
from repro.core.heterogeneity import HeterogeneityModel
from repro.core.scenario import ScenarioSpec
from repro.fedsim import simulator, sweep
from repro.models import mlp

SPEC = ScenarioSpec(n_agents=8, n_rsus=4, batch=8, n_train=400, n_test=100,
                    hp=H2FedParams(mu1=0.01, mu2=0.005, lar=2,
                                   local_epochs=1, lr=0.1),
                    het=HeterogeneityModel(csr=0.8, scd=1), rounds=3)
SCOPES = ("h2fed.local_train", "h2fed.rsu_agg", "h2fed.cloud_blend",
          "h2fed.draws")


@pytest.fixture(scope="module")
def params():
    return mlp.init_params(MLP_CFG, jax.random.key(0))


@pytest.mark.parametrize("fused", [True, False])
def test_flat_round_names_its_layers(params, fused):
    """Every layer of the flat round carries its scope in the lowered
    program's op names (the unfused A/B program has no fused entry
    points: training and draws at least)."""
    res = SPEC.resolve()
    spec = flatten.spec_of(params)
    state = simulator.init_flat_state(res.cfg, spec, params,
                                      jax.random.key(1))
    round_fn = simulator.make_flat_global_round(
        res.cfg, SPEC.hp, SPEC.het, res.fed, spec, fused=fused)
    text = round_fn.lower(state).as_text(debug_info=True)
    named = {s for s in SCOPES if s in text}
    want = set(SCOPES) if fused else {"h2fed.local_train", "h2fed.draws"}
    assert want <= named


def test_a_fresh_jitted_call_raises_the_compile_counters():
    program_cache.watch_compiles()
    program_cache.reset_stats()
    before = program_cache.stats()
    jax.jit(lambda v: jnp.sin(v) * 3.0 + 1.0)(
        jnp.ones((5, 7))).block_until_ready()
    after = program_cache.stats()
    for name in ("trace", "lower", "compile"):
        assert after[f"{name}_s"] > before[f"{name}_s"] == 0
        assert after[f"{name}_events"] > before[f"{name}_events"] == 0
    program_cache.reset_stats()
    zeroed = program_cache.stats()
    for name in program_cache.COMPILE_EVENTS.values():
        assert zeroed[f"{name}_s"] == 0 and zeroed[f"{name}_events"] == 0


def test_watching_twice_registers_one_listener():
    seen = []

    def count(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    program_cache.watch_compiles()
    program_cache.watch_compiles()
    program_cache.reset_stats()
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        jax.jit(lambda v: v - 2.5)(jnp.ones((3, 11))).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    s = program_cache.stats()
    assert seen and s["compile_events"] == len(seen)
    assert s["compile_s"] == pytest.approx(sum(seen))


def test_run_scenario_spans_each_round_and_eval(params, tmp_path):
    """Under the profiler, ``run_scenario`` writes one ``h2fed.round`` and
    one ``h2fed.eval`` span per round on the caller's thread, tagged with
    the round index, after one ``h2fed.build``."""
    from jax.profiler import ProfileData
    res = SPEC.replace(program_cache=False).resolve()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.caller"):
            sweep.run_scenario(res, params)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    lines = [list(line.events)
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    caller = [ev for ev in lines
              if any(e.name == "test.caller" for e in ev)]
    assert len(caller) == 1
    spans = [(e.name, dict(e.stats).get("round")) for e in caller[0]
             if e.name.startswith("h2fed.")]
    rounds = range(SPEC.rounds)
    assert spans == [("h2fed.build", None)] + [
        (name, r) for r in rounds for name in ("h2fed.round", "h2fed.eval")]
    others = [e.name for ev in lines if ev is not caller[0] for e in ev
              if e.name.startswith("h2fed.")]
    assert others == []
