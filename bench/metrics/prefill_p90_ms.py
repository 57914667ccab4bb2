"""90th percentile of the time a request's prompt takes once it holds a
slot: its ``serve.prefill`` span (``repro.runtime.telemetry``), from
placement to the tick that yields its first output token, over every
request submitted in the window.  One placed but without a first token
counts up to the end of the run; one never placed, with none.  None for
a program without the span ring, or where the ring overwrote spans from
the window's start on."""
from bench.lib.measure import percentile


def read(r):
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    w0, w1 = r.window
    s = telemetry.spans(w0)
    if not s["complete"]:
        return None
    p = s["name"] == "serve.prefill"
    took = dict(zip(s["rid"][p].tolist(), (s["t1"] - s["t0"])[p].tolist()))
    reqs = [x.request for x in r.reqs
            if w0 <= x.request.submitted_at <= w1]
    return percentile([_prefill_s(q, took, r.end) * 1e3 for q in reqs], 90)


def _prefill_s(q, took: dict, end: float) -> float:
    if q.rid in took:
        return took[q.rid]
    if q.admitted_at != q.admitted_at:            # nan: never placed
        return 0.0
    return end - q.admitted_at
