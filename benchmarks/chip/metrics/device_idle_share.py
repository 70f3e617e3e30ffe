"""Share of the traced rounds' window in which no operation ran on the
device, averaged over the devices (device trace), in percent."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace["idle_share"] * 100.0
