"""Share of the traced window in which no op ran on the device."""


def read(ctx):
    return 100.0 * ctx["reduction"]["idle_share"]
