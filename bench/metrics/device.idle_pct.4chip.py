"""Share of the traced window in which the chips ran no operation, mean
over the four chips."""


def read(ctx):
    r = ctx["reduced"]
    return 100.0 * (1.0 - r.mean_busy_s / r.window_s) if r.busy_s else None
