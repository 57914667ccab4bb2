"""A backlog that keeps every slot full: offline batch generation.

Mix parameters: ``ramp_s`` (untimed ticks before the window, so that the
slots no longer start in step).  Before every tick the engine's queue is
topped up to one request per slot, so a slot that frees is refilled at
once.  The run ends with the window; a traced run then profiles a few
more seconds of the same load.  Attempted: the requests that ran in the
window.  None can fail: every admitted request runs until it ends.
"""
from __future__ import annotations

from bench.lib import traffic


def drive(rec, mix: dict, seed: int, vocab: int, seconds: float,
          tracer) -> dict:
    clock = rec.clock
    it = traffic.stream(mix, seed, vocab)
    eng = rec.engine
    origin = clock()
    w0 = origin + mix["ramp_s"]
    w1 = None
    while True:
        while len(eng.queue) < eng.B:
            rec.submit(next(it), clock())
        now = clock()
        if w1 is None and now >= w0 + seconds:
            w1 = now
        if w1 is not None:
            if not tracer.pending:
                ran = [r for r in rec.reqs.values() if r.admitted < w1 and (
                    not r.token_times or r.token_times[-1] >= w0)]
                return {"window": (w0, w1), "end": w1, "lateness": [],
                        "attempted": len(ran), "failed": 0}
            tracer.at(now)
        rec.tick()
