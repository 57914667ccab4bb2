"""90th percentile of due time to first output token, over every request
due in the window.  One without a first token when the run ends counts
with the time it had waited by then."""
from bench.lib.measure import in_window, percentile


def read(r):
    return percentile([((q.token_times[0] if q.token_times else r.end)
                        - q.due) * 1e3 for q in in_window(r)], 90)
