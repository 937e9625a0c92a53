"""Share of the traced window in which the chip ran no operation."""


def read(ctx):
    r = ctx["reduced"]
    return 100.0 * (1.0 - r.mean_busy_s / r.window_s) if r.busy_s else None
