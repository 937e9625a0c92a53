"""Grid steps the routed delete's shard-local kernel runs per call, on the
busiest shard: the ``delete_grid_step`` markers the sharded filter's entry
point (``DeferredWritePump``) records at each delete's harvest, one a step,
over the routed deletes (one ``distributed.delete`` span each), in the
traced window.  A program that records no marker reads nothing."""


def read(ctx):
    spans = ctx["reduced"].spans
    calls = sum(1 for name, _s, _e in spans if name == "distributed.delete")
    steps = sum(1 for name, _s, _e in spans if name == "delete_grid_step")
    if not calls or not steps:
        return None
    return steps / calls
