"""step_wall_ms_p50 (layer: serving loop, ``TailServer.step``): median
host-clock time of one ``step()`` call, admission to logits on the host.
Reads the steps of the traced span (``Record.host_steps``)."""
import numpy as np


def read(rec):
    w = rec.window
    m = rec.host_steps()
    if not m.any():
        return None
    return 1e3 * float(np.median((w.step_end - w.step_begin)[m]))
