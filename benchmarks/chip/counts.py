"""Work and bytes of one cell, computed from shapes, and the chip's peaks.

* The model's parameter count and the operations of one training sample
  come from its kind's module (``models/<kind>.py``: ``n_params``,
  ``flops_per_sample``), which the cell carries as ``cell.model``.
* ``agg_blend_bytes`` / ``cloud_blend_bytes``: the fewest bytes an RSU or
  cloud aggregation call must move: the rows that carry weight are read
  once, and the rows that change are written once.  An agent that did not
  reach its RSU and an RSU that received nothing need not be touched.
* ``least_seconds``: the larger of operations over the peak rate and
  bytes over the peak bandwidth.
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict, Optional

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def agg_blend_bytes(n_connected: int, n_rsus_hit: int, n: int,
                    itemsize: int) -> int:
    """RSU aggregation: read the connected agents' rows, write the rows of
    the RSUs that received data."""
    return (n_connected + n_rsus_hit) * n * itemsize


def agg_blend_flops(n_connected: int, n: int) -> int:
    return 2 * n_connected * n


def cloud_blend_bytes(n_rsus_with_mass: int, n: int, itemsize: int) -> int:
    """Cloud aggregation: read the RSU rows that carry mass, write the fp32
    cloud model (nothing moves when no RSU carries mass)."""
    if n_rsus_with_mass == 0:
        return 0
    return n_rsus_with_mass * n * itemsize + n * 4


def cloud_blend_flops(n_rsus_with_mass: int, n: int) -> int:
    return 2 * n_rsus_with_mass * n


def least_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])


# The trace names the aggregation kernel's operations (RSU aggregation and
# cloud blend launch the same Pallas kernel) by this pattern.
AGG_KERNEL = re.compile(r"fused_agg_blend")


def agg_kernel_seconds(trace: Optional[Dict]) -> float:
    """Device seconds of the aggregation kernel in a trace summary."""
    if trace is None:
        return 0.0
    return sum(s for name, s in trace["ops"].items()
               if AGG_KERNEL.search(name))


def _itemsize(config: Dict) -> int:
    return {"float32": 4, "bfloat16": 2}[config["fleet_dtype"]]


def agg_least_seconds(ctx) -> float:
    """Least time of the window's aggregation calls: one RSU call per local
    round and one cloud call per global round."""
    n = ctx.cell.model.n_params(ctx.cell.config)
    size = _itemsize(ctx.cell.config)
    peak = peaks(ctx.device["kind"])
    d = ctx.draws
    rsu = sum(least_seconds(agg_blend_flops(int(c), n),
                            agg_blend_bytes(int(c), int(h), n, size), peak)
              for c, h in zip(d["connected"].ravel(), d["rsus_hit"].ravel()))
    cloud = sum(least_seconds(cloud_blend_flops(int(m), n),
                              cloud_blend_bytes(int(m), n, size), peak)
                for m in d["cloud_rsus"].ravel())
    return rsu + cloud


def step_flops(ctx) -> float:
    """Operations of the window's local steps that reach an RSU."""
    cell = ctx.cell
    return (float(ctx.draws["live_steps"].sum()) * cell.traffic["batch"]
            * cell.model.flops_per_sample(cell.config, cell.traffic))
