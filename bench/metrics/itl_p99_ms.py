"""99th percentile of the gap between consecutive output tokens of one
request, over every gap that ends in the window."""
from bench.lib.measure import percentile


def read(r):
    w0, w1 = r.window
    return percentile([(b - a) * 1e3 for q in r.reqs
                       for a, b in zip(q.token_times, q.token_times[1:])
                       if w0 <= b <= w1], 99)
