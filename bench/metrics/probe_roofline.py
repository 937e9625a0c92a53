"""The probe's share of the HBM roofline: the least bytes of every lookup
of the traced window (``bench.costmodel.probe_bytes`` over the keys the
waves probed after dedupe, and the stash per wave) over the device time of
the probe program, ``probe_emulated`` (the XLA form of
``kernels/probe.py``)."""
from bench import costmodel

PROGRAM = r"probe_emulated"


def read(ctx):
    c, t = ctx["counters"], ctx["reduced"].program_s(PROGRAM)
    if t <= 0:
        return None
    nbytes = costmodel.probe_bytes(c["probe_keys"], c["lookup_waves"],
                                   bucket_size=c["bucket_size"],
                                   stash_slots=c["stash_slots"])
    return costmodel.roofline_pct(nbytes, t, ctx["peaks"]["hbm_bytes_per_s"])
