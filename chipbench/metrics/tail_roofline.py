"""tail_roofline (layer: tail stage, ``runtime/partition.py``'s jitted tail):
the tail program's share of its roofline, in %.  Its device time is that
of the tail program's runs in the traced span; the least time of
one run, over the full pool of ``n_slots`` images it computes, is the
larger of its operations over the bf16 peak and its bytes (weights once,
input, logits) over HBM bandwidth (``flops.tail_cost``).

Matches: the stage jits are lambdas, so their programs are named
``jit__lambda``; the head never runs in the window, so the tail is the
only such program there."""
from chipbench import flops

MODULE = "jit__lambda"


def read(rec):
    if rec.trace is None:
        return None
    runs, secs = rec.trace.module_seconds(lambda name: name == MODULE)
    if not runs or secs <= 0:
        return None
    images = rec.cfg["n_slots"] * rec.cfg["client_batch"]
    least = flops.least_seconds(*flops.tail_cost(rec.cfg, images), rec.peaks)
    return 100.0 * runs * least / secs
