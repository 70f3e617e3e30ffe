"""Flat-buffer parameter representation (DESIGN.md §3).

The simulator's hot path treats the fleet as matrices, not pytrees: every
agent's parameters are raveled into one contiguous fp32 row of an ``(A, N)``
buffer (RSUs: ``(R, N)``; cloud: ``(N,)``), so hierarchical aggregation is a
single ``(R, A) @ (A, N)`` Pallas matmul (kernels/masked_hier_agg) instead of
O(leaves) tree-mapped reductions.  Structure round-trips losslessly:
ravel/unravel are pure reshape+concatenate/slice, bit-exact for matching
dtypes, and differentiable — ``jax.grad`` of a loss composed with
``unravel`` yields the raveled gradient directly.

A ``FlatSpec`` is static metadata (treedef + leaf shapes/dtypes/offsets)
derived once per simulation from the parameter template; it never crosses a
jit boundary as a traced value.

Dtype policy (DESIGN.md §3): the spec carries a ``storage_dtype`` knob for
the FLEET buffers — ``bfloat16`` storage halves the HBM bytes (and any
collective bytes) of the dominant (A, N)/(R, N) traffic and doubles the
agent count that fits a device.  ``ravel``/``unravel`` stay fp32 masters
(the cloud buffer and all eval/checkpoint boundaries), kernels accumulate
fp32 regardless of storage, and ``to_storage`` is the single cast point
engines use when writing into fleet buffers.  The default keeps everything
fp32 — bit-compatible with the pre-knob behavior.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

BUFFER_DTYPE = jnp.float32

# accepted --fleet-dtype spellings -> storage dtype
STORAGE_DTYPES = {
    "float32": jnp.float32, "f32": jnp.float32, "fp32": jnp.float32,
    "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
}


def resolve_storage_dtype(name) -> Any:
    """Fleet-buffer storage dtype from a CLI/config spelling (or a dtype).

    Only the dtypes the policy covers (fp32, bf16) are admitted — dtype
    OBJECTS are held to the same allowlist as strings, so an fp16 fleet
    (whose ±65k range can overflow weighted numerators) fails at
    configuration time rather than producing inf buffers mid-run."""
    if name is None:
        return jnp.dtype(BUFFER_DTYPE)
    if isinstance(name, str):
        if name not in STORAGE_DTYPES:
            raise ValueError(f"unknown fleet dtype {name!r} "
                             f"(want one of {sorted(STORAGE_DTYPES)})")
        return jnp.dtype(STORAGE_DTYPES[name])
    dt = jnp.dtype(name)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(f"unsupported fleet dtype {dt} "
                         f"(the dtype policy covers float32 | bfloat16)")
    return dt


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static ravel plan for one parameter pytree (no leading fleet axis)."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    n: int                       # total flat length Σ sizes
    storage_dtype: Any = BUFFER_DTYPE   # fleet-buffer dtype (DESIGN.md §3)

    def to_storage(self, x: jax.Array) -> jax.Array:
        """Cast into the fleet-buffer storage dtype (the ONE cast point for
        writes into (A, N)/(R, N) buffers; no-op under the fp32 default)."""
        return x.astype(self.storage_dtype)

    # -- single model: (N,) ------------------------------------------------
    def ravel(self, tree: PyTree) -> jax.Array:
        leaves = self.treedef.flatten_up_to(tree)
        return jnp.concatenate(
            [l.astype(BUFFER_DTYPE).reshape(-1) for l in leaves])

    def unravel(self, vec: jax.Array) -> PyTree:
        leaves = [
            vec[off:off + size].reshape(shape).astype(dtype)
            for off, size, shape, dtype in zip(
                self.offsets, self.sizes, self.shapes, self.dtypes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # -- stacked fleet: (A, N) ---------------------------------------------
    def ravel_stacked(self, stacked: PyTree) -> jax.Array:
        leaves = self.treedef.flatten_up_to(stacked)
        a = leaves[0].shape[0]
        return jnp.concatenate(
            [l.astype(BUFFER_DTYPE).reshape(a, -1) for l in leaves], axis=1)

    def unravel_stacked(self, mat: jax.Array) -> PyTree:
        a = mat.shape[0]
        leaves = [
            mat[:, off:off + size].reshape((a,) + shape).astype(dtype)
            for off, size, shape, dtype in zip(
                self.offsets, self.sizes, self.shapes, self.dtypes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


def spec_of(tree: PyTree, *, storage_dtype=BUFFER_DTYPE) -> FlatSpec:
    """Build the ravel plan from a parameter template (arrays or tracers —
    only static shape/dtype metadata is read)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    offsets = tuple(int(o) for o in np.cumsum((0,) + sizes)[:-1])
    return FlatSpec(treedef=treedef, shapes=shapes, dtypes=dtypes,
                    offsets=offsets, sizes=sizes, n=int(sum(sizes)),
                    storage_dtype=resolve_storage_dtype(storage_dtype))


def spec_of_stacked(stacked: PyTree, *,
                    storage_dtype=BUFFER_DTYPE) -> FlatSpec:
    """Ravel plan from a fleet-stacked template (leading axis dropped)."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    shapes = tuple(tuple(l.shape[1:]) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    offsets = tuple(int(o) for o in np.cumsum((0,) + sizes)[:-1])
    return FlatSpec(treedef=treedef, shapes=shapes, dtypes=dtypes,
                    offsets=offsets, sizes=sizes, n=int(sum(sizes)),
                    storage_dtype=resolve_storage_dtype(storage_dtype))
