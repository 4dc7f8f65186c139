"""queue_wait_ms_p50 (layer: serving loop, admission): median of the start
of the step that served a request minus its due time (host clock), over
the requests served by the steps of the traced span
(``Record.host_steps``)."""
import numpy as np


def read(rec):
    w = rec.window
    m = rec.host_steps()
    ok = w.served_by >= 0
    ok[ok] = m[w.served_by[ok]]
    if not ok.any():
        return None
    wait = w.step_begin[w.served_by[ok]] - rec.schedule.due[ok]
    return 1e3 * float(np.median(wait))
