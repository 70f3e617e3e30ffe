"""Process start to the start of the window: imports, the device, data,
the pretrained model and the warm-up calls (host clock)."""


def read(ctx):
    return ctx.setup["setup_s"]
