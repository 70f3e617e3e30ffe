"""Device time per global round of the aggregation kernel, the Pallas
``_fused_agg_blend`` that RSU aggregation and the cloud blend launch, over
the traced rounds (device trace)."""
from benchmarks.chip import counts


def read(ctx):
    s = counts.agg_kernel_seconds(ctx.trace)
    if not s:
        return None
    return s / ctx.traced_rounds * 1e3
