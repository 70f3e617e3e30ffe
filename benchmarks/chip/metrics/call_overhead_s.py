"""Seconds the timed ``run_scenario`` call spends before its rounds run at
their steady pace: the first round's time less the median of the others
(host clock).  The flat engine builds its round program afresh in every
call, so this is its re-trace, lowering and compile-cache load."""
import statistics


def read(ctx):
    per_round = ctx.window["per_round_s"]
    return per_round[0] - statistics.median(per_round[1:])
