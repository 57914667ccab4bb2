"""90th percentile of due time to the start of the tick that admitted the
request into a slot, over every request due in the window (host clock).
One never admitted counts with the time it had waited when the run
ended."""
from bench.lib.measure import in_window, percentile


def read(r):
    reqs = in_window(r)
    return percentile([((q.admitted if q.admitted == q.admitted else r.end)
                        - q.due) * 1e3 for q in reqs], 90)
