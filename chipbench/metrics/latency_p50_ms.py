"""latency_p50_ms: median latency of all requests due in the window, from
the due time to the return of the step that hands the logits to the host
(host clock).  Requests answered in the drain after the window count with
their whole wait."""
from chipbench import stats


def read(rec):
    return 1e3 * stats.percentile(
        stats.latencies_s(rec.schedule.due, rec.window.done), 50)
