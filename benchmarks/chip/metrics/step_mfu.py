"""The whole round's share of the chip's peak over the traced rounds:
matrix-product operations of the local steps that reach an RSU (connected
agents, completed steps) over the traced window and the peak rate, in
percent."""
from benchmarks.chip import counts


def read(ctx):
    if ctx.trace is None:
        return None
    return (counts.step_flops(ctx) / ctx.trace["window_s"]
            / counts.peaks(ctx.device["kind"])["flops_per_s"] * 100.0)
