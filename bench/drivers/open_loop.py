"""Open-loop arrivals: independent users who send on a schedule.

Mix parameters: ``rate_per_s`` (Poisson arrivals), ``block``,
``ramp_blocks`` (untimed load at the same rate before the window, as a
whole number of blocks: every block of arrivals spans the same time, so
the window starts where a block does and holds whole blocks, the same
sizes for every seed), ``drain_cap_s``.  Requests are
submitted at the tick boundary after they fall due; each is timed from
its due time, so a stall counts against every request that waits behind
it.  After the window the run goes on, with arrivals still coming, only
until every request due in the window has its first token, or for
``drain_cap_s`` at most; then a traced run profiles a few more seconds
of the same load.  Attempted: the requests due in the window; failed:
those of them with no first token when the run ends.
"""
from __future__ import annotations

import time

import jax

from bench.lib import traffic


def drive(rec, mix: dict, seed: int, vocab: int, seconds: float,
          tracer) -> dict:
    clock = rec.clock
    it = traffic.stream(mix, seed, vocab)
    nxt = next(it)
    span = float(traffic.exponential_gaps(mix["rate_per_s"],
                                          mix["block"]).sum())
    origin = clock()
    w0 = origin + mix["ramp_blocks"] * span
    w1 = end = None
    lateness = []
    while True:
        now = clock()
        while origin + nxt.due_s <= now:
            due = origin + nxt.due_s
            rec.submit(nxt, due)
            if w0 <= due < w0 + seconds:
                lateness.append(now - due)
            nxt = next(it)
        if w1 is None and now >= w0 + seconds:
            w1 = now
        if w1 is not None and end is None:
            waiting = any(w0 <= r.due < w1 and not r.token_times
                          for r in rec.reqs.values())
            if not waiting or now >= w1 + mix["drain_cap_s"]:
                end = now
        if end is not None:
            if not tracer.pending:
                due = [r for r in rec.reqs.values() if w0 <= r.due < w1]
                return {"window": (w0, w1), "end": end, "lateness": lateness,
                        "attempted": len(due),
                        "failed": sum(not r.token_times for r in due)}
            tracer.at(now)
        if rec.idle():
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, origin + nxt.due_s - clock()))
            continue
        rec.tick()
