"""Device time of the all-to-all operations per routed call, mean over the
chips: every op of the traced window whose opcode is ``all-to-all``,
summed over the chips and divided by their number and by the routed calls
(one ``distributed.<kind>`` span each, resubmissions among them).  On a
TPU an op's name is its HLO text, ``%all_to_all.30 = pred[4,1,513]
all-to-all(...)``: the opcode is spelled with hyphens, and an op that
only takes one as an operand names it with underscores."""
import re

_A2A = re.compile(r"all-to-all")


def read(ctx):
    r = ctx["reduced"]
    calls = sum(1 for name, _s, _e in r.spans
                if name.startswith("distributed."))
    a2a_s = sum(s for name, s in r.ops.items() if _A2A.search(name))
    if not calls or a2a_s <= 0:
        return None
    return 1e3 * a2a_s / r.n_devices / calls
