"""Seconds of the warm-up calls of ``run_scenario``: compile or cache
load, and a few rounds (host clock)."""


def read(ctx):
    return ctx.setup["warmup_s"]
