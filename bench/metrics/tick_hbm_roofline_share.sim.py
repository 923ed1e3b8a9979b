"""The least time one tick's pool pass needs at the HBM peak (the bytes
it must read and write, from the cell's shapes: ``work.tick_bytes``),
over the measured device milliseconds per tick."""

import work


def read(ctx):
    red = ctx["reduction"]
    tick_s = red["busy_ns"] / red["ticks"] * 1e-9
    least_s = work.least_tick_s(ctx["shapes"], ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / tick_s
