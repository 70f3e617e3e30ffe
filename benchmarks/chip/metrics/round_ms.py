"""Wall time of the window over the rounds it completed (host clock)."""


def read(ctx):
    return ctx.window["seconds"] / ctx.window["rounds"] * 1e3
