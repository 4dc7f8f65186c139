"""device_idle_share (layer: device): 1 - the union of the device's
operation intervals over the traced span, in %."""


def read(rec):
    if rec.trace is None:
        return None
    return 100.0 * rec.trace.idle_share
