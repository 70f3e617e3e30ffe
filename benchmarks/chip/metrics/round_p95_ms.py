"""95th percentile of every round's time in the window, each round from
the previous round's accuracy on the host to its own, evaluation included;
the first round also carries the call's own set-up (host clock)."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.window["per_round_s"], 95)) * 1e3
