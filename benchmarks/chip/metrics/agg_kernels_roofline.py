"""The aggregation kernel's share of its roofline over the traced rounds:
the least time their RSU and cloud aggregation calls need on this chip,
from the rows that carry weight (``counts``), over the kernel's device
time, in percent."""
from benchmarks.chip import counts


def read(ctx):
    s = counts.agg_kernel_seconds(ctx.trace)
    if not s:
        return None
    return counts.agg_least_seconds(ctx) / s * 100.0
