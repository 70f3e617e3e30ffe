"""Seconds to make the cell's data and pretrained model (host clock)."""


def read(ctx):
    return ctx.setup["data_setup_s"]
