"""Mean host time of a tick that the device waits for: each
``serve.tick`` span of the program (``repro.runtime.telemetry``) that
starts in the window, less its ``serve.sync`` child, in which the host
waits for the device.  None for a program without the span ring, or
where the ring overwrote spans of the window."""
import numpy as np


def read(r):
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    s = telemetry.spans(*r.window)
    tick = s["name"] == "serve.tick"
    if not s["complete"] or not tick.any():
        return None
    dur = s["t1"] - s["t0"]
    host = dict(zip(s["seq"][tick].tolist(), dur[tick].tolist()))
    sync = s["name"] == "serve.sync"
    for parent, d in zip(s["parent"][sync].tolist(), dur[sync].tolist()):
        if parent in host:
            host[parent] -= d
    return float(np.mean(list(host.values())) * 1e3)
