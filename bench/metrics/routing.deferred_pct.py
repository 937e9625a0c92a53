"""Write lanes deferred (parked by routing overflow) over the lanes offered
to the sharded filter's entry point in the window, in %: the pump's
``deferred`` and ``offered`` lane counts, every kind."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("offered"):
        return None
    return 100.0 * c["deferred"] / c["offered"]
