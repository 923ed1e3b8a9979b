"""Device busy milliseconds per engine tick: busy time over the ticks,
the largest run count among the ops that hold at least 1% of the busy
time (the tick loop's own passes run once per tick; ``bench/reduce.py``)."""


def read(ctx):
    red = ctx["reduction"]
    return red["busy_ns"] / red["ticks"] * 1e-6
