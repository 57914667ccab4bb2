"""99th percentile of the time a request waits for a slot: its
``serve.queued`` span (``repro.runtime.telemetry``), from ``submit`` to
its placement, over every request submitted in the window.  One never
placed counts with the time it had waited when the run ended.  None for
a program without the span ring, or where the ring overwrote spans of
the window."""
from bench.lib.measure import percentile


def read(r):
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    w0, w1 = r.window
    s = telemetry.spans(w0, w1)
    if not s["complete"]:
        return None
    q = s["name"] == "serve.queued"
    waited = dict(zip(s["rid"][q].tolist(), (s["t1"] - s["t0"])[q].tolist()))
    reqs = [x.request for x in r.reqs
            if w0 <= x.request.submitted_at <= w1]
    return percentile([waited.get(q.rid, r.end - q.submitted_at) * 1e3
                       for q in reqs], 99)
