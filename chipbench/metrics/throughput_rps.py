"""throughput_rps: requests answered inside the window per second of
window (host clock)."""
from chipbench import stats


def read(rec):
    return stats.throughput(rec.window.done, rec.seconds)
