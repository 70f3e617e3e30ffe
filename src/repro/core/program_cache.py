"""Spec-keyed compiled-program caching (DESIGN.md §10).

Two layers kill redundant compilation:

  1. **Persistent XLA compilation cache** (cross-process):
     ``enable_persistent_cache`` turns on JAX's persistent compilation
     cache with the thresholds dropped to zero, so every jitted program —
     sweep rounds, figure grids, benchmarks, CI re-runs — compiles once
     per machine and loads from disk afterwards.  Where
     ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and no directory
     is set in code; otherwise the cache lives at one fixed, git-ignored
     path in the checkout, ``.jax_cache``.  The XLA cache keys on the
     serialized HLO + compile options + backend, so it is safe across
     unrelated programs by construction.

  2. **In-process program registry** (cross-call): ``get_or_build`` memoizes
     built program bundles (the jitted round fn + eval core of a sweep
     group) under an explicit :class:`ProgramKey`.  The key carries
     everything that changes the traced program but is NOT visible in the
     jit signature: the widened ``ResolvedScenario.static_key``, the sweep
     width S and which scalars are batched, the baked (non-batched)
     hp/het/cadence values, the donation signature, the device + mesh
     fingerprint, and the ``kernels.ops`` interpret/fused flags — the last
     three MUST enter the key or a backend/mesh/interpret flip would serve
     a stale program.  A registry hit skips Python tracing entirely; the
     persistent cache below it skips XLA compilation.

Trace accounting: round bodies call :func:`note_trace` from inside their
Python trace, so ``trace_count(label)`` counts actual (re)traces — the
number benchmarks/CI pin to 1 for a mixed-cadence group (BENCH_PR9.json).

Compile accounting: from the first ``run_scenario`` or
``enable_persistent_cache`` call on, JAX's monitoring events feed
process-wide seconds and event counts of every jitted call's Python trace
(``trace_s``), its lowering to MLIR (``lower_s``), its backend compile
(``compile_s``; on a persistent-cache hit, the load) and the cache read
inside it (``cache_load_s``).  A jit traced inside another's trace
records its own event within the outer one, so ``trace_s`` counts nested
traces twice.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
from jax.experimental.compilation_cache.compilation_cache import reset_cache

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")

_persistent_dir: Optional[str] = None
_REGISTRY: Dict[Any, Any] = {}
_TRACES: Dict[str, int] = {}
_stats = {"hits": 0, "misses": 0}
# JAX monitoring event -> the counter it feeds
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_compile: Dict[str, float] = {}
_watching = False


# --------------------------------------------------------------------------
# layer 1: the persistent XLA compilation cache
# --------------------------------------------------------------------------

def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` where set (JAX reads it itself), else
    ``DEFAULT_CACHE_DIR``.  Idempotent.  Thresholds are dropped to zero so
    even the small CI/test programs persist — the default min-compile-time
    gate would skip exactly the programs the warm-start asserts measure.
    """
    global _persistent_dir
    watch_compiles()
    target = os.environ.get(ENV_CACHE_DIR) or DEFAULT_CACHE_DIR
    if _persistent_dir == target:
        return target
    if not os.environ.get(ENV_CACHE_DIR):
        os.makedirs(target, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", target)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax materializes its cache object once, at the first compile — if
    # anything compiled before this call (data gen, init_params), the
    # settings above would be ignored for the rest of the process
    reset_cache()
    _persistent_dir = target
    return target


def persistent_cache_dir() -> Optional[str]:
    """The active persistent-cache dir (None = disabled)."""
    return _persistent_dir


def _zero_compile_counters() -> None:
    for name in COMPILE_EVENTS.values():
        _compile[f"{name}_s"] = 0.0
        _compile[f"{name}_events"] = 0


def _on_duration(event: str, duration: float, **_) -> None:
    name = COMPILE_EVENTS.get(event)
    if name is not None:
        _compile[f"{name}_s"] += duration
        _compile[f"{name}_events"] += 1


def watch_compiles() -> None:
    """Register the listener that feeds the compile counters (once per
    process; ``run_scenario`` and ``enable_persistent_cache`` call it)."""
    global _watching
    if not _watching:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _watching = True


_zero_compile_counters()


# --------------------------------------------------------------------------
# layer 2: the in-process program registry
# --------------------------------------------------------------------------

def device_fingerprint(devices=None) -> Tuple:
    """Hashable identity of the devices a program was built against."""
    devices = jax.devices() if devices is None else list(devices)
    return tuple((d.platform, d.device_kind, d.id) for d in devices)


def mesh_fingerprint(mesh) -> Optional[Tuple]:
    """Hashable identity of a jax.sharding.Mesh (None passes through):
    axis names/sizes plus the flat device list."""
    if mesh is None:
        return None
    return (tuple(mesh.shape.items()),
            device_fingerprint(mesh.devices.flat))


def ops_flags(fused: bool) -> Tuple:
    """The kernels.ops lowering flags a traced program bakes in."""
    from repro.kernels import ops
    return ("interpret", ops.interpret_mode(), "fused", bool(fused))


class ProgramKey(NamedTuple):
    """The full identity of a built program bundle (DESIGN.md §10)."""
    kind: str                    # e.g. "sweep"
    static_key: Tuple            # widened ResolvedScenario.static_key
    n_scenarios: int             # sweep width S (a shape)
    dyn_names: Tuple[str, ...]   # which scalars are batched (S,) data
    baked: Tuple                 # non-batched hp/het/cadence scalar values
    cadence: Any                 # simulator.Cadence bounds or None
    data_axes: Tuple             # vmap in_axes of the stacked fed arrays
    donation: Tuple[int, ...]    # donate_argnums signature
    devices: Tuple               # device_fingerprint()
    mesh: Optional[Tuple]        # mesh_fingerprint()
    flags: Tuple                 # ops_flags(): interpret + fused


def get_or_build(key, builder: Callable[[], Any], *, enabled: bool = True):
    """Return the program bundle registered under ``key``, building (and
    registering) it on first use.  ``enabled=False`` (the ScenarioSpec
    ``program_cache=False`` opt-out) always builds fresh and never touches
    the registry."""
    if not enabled:
        return builder()
    try:
        bundle = _REGISTRY[key]
    except KeyError:
        _stats["misses"] += 1
        bundle = _REGISTRY[key] = builder()
        return bundle
    _stats["hits"] += 1
    return bundle


def note_trace(label: str) -> None:
    """Called from inside a round body's Python trace: one call == one
    actual (re)trace of that program family."""
    _TRACES[label] = _TRACES.get(label, 0) + 1


def trace_count(label: str) -> int:
    return _TRACES.get(label, 0)


def stats() -> Dict[str, float]:
    return dict(_stats, entries=len(_REGISTRY), **_compile, **{
        f"traces/{k}": v for k, v in _TRACES.items()})


def reset_stats() -> None:
    """Zero the hit/miss/trace and compile counters (the registry itself
    survives)."""
    _stats["hits"] = _stats["misses"] = 0
    _TRACES.clear()
    _zero_compile_counters()


def clear() -> None:
    """Drop the registry + counters (tests; frees held jitted callables)."""
    _REGISTRY.clear()
    reset_stats()
