"""setup_s: seconds from the process's start to the window's start (host
clock): imports, the chip's start, weights and frames, every compile and
the warm-up steps."""


def read(rec):
    return rec.setup_s
