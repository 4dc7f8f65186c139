"""step_mfu (layer: the whole server step): operations of the requests
served (each one's ae8 decode and its image's share of the tail; padding
slots not counted) over the host-clock time of the steps that served
them times the chip's bf16 peak, in %.  Reads the steps of the traced
span (``Record.host_steps``)."""
import numpy as np

from chipbench import flops


def read(rec):
    w = rec.window
    m = rec.host_steps()
    wall = float((w.step_end - w.step_begin)[m].sum())
    if wall <= 0:
        return None
    ops = flops.request_ops(rec.cfg) * float(w.step_served[m].sum())
    return 100.0 * ops / (wall * rec.peaks["bf16_flops"])
