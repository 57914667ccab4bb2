"""Shared arithmetic of the metric readers (``bench/metrics/*.py``).

A reader gets the run's record (``run.py`` builds it): ``setup_s``,
``trace`` (``lib/trace.py``'s reduction, or None), ``trace_span``, the
config ``c``, the chip's ``peak``, ``step`` (the name of the program
whose runs are the cell's step, from its kind), and what the kind's
``drive`` returned: for serving ``window`` (host clock), ``end``,
``ticks`` (rows of start, end, live slots, tokens served, keys seen),
``reqs`` and the ``counts`` module of the configuration.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, q)) if v.size else None


def in_window(r) -> list:
    """Requests due in the window."""
    w0, w1 = r.window
    return [q for q in r.reqs if w0 <= q.due < w1]


def window_ticks(r) -> np.ndarray:
    w0, w1 = r.window
    t = r.ticks
    return t[(t[:, 0] >= w0) & (t[:, 1] <= w1)]


def traced_ticks(r) -> np.ndarray:
    a, b = r.trace_span
    t = r.ticks
    return t[(t[:, 0] >= a) & (t[:, 1] <= b)]


def program_runs(r, name: str) -> List[float]:
    """Device seconds of each run of ``name`` in the traced window, over
    all devices."""
    if not r.trace:
        return []
    return [s for d in r.trace["devices"].values()
            for s in d["programs"].get(name, [])]


def other_program_seconds(r, name: str) -> float:
    return sum(s for d in r.trace["devices"].values()
               for p, runs in d["programs"].items() if p != name
               for s in runs) / len(r.trace["devices"])


def _bounds(r):
    """Per traced tick: (FLOPs, seconds at peak FLOP/s, seconds at HBM
    bandwidth) of its decode step."""
    pf, pb = r.peak["bf16_flops_per_s"], r.peak["hbm_bytes_per_s"]
    out = []
    for _, _, live, _, keys in traced_ticks(r):
        f = r.counts.decode_flops(r.c, int(live), int(keys))
        out.append((f, f / pf,
                    r.counts.decode_bytes(r.c, int(live), int(keys)) / pb))
    return out


def step_work(r):
    """(FLOPs, least seconds) of the traced ticks, summed, scaled to the
    number of step runs the trace holds (a tick whose step ran on the
    device astride the window's edge is in one count and not the
    other)."""
    b = _bounds(r)
    runs = program_runs(r, r.step)
    if not b or not runs:
        return None
    scale = len(runs) / len(b)
    return (sum(f for f, _, _ in b) * scale,
            sum(max(tf, tb) for _, tf, tb in b) * scale)


def which_bound(r) -> str:
    """Which bound of the roofline wins for the traced decode steps."""
    b = _bounds(r)
    if not b:
        return "no traced steps"
    tf = sum(x[1] for x in b) / len(b)
    tb = sum(x[2] for x in b) / len(b)
    return (f"{'memory' if tb >= tf else 'compute'} (FLOPs at peak "
            f"{tf * 1e3} ms, bytes at HBM bandwidth {tb * 1e3} ms per step, "
            f"{len(b)} steps)")


def step_ms(r):
    runs = program_runs(r, r.step)
    return sum(runs) / len(runs) * 1e3 if runs else None


def eager_ms_per_tick(r):
    if not r.trace:
        return None
    ticks = len(program_runs(r, r.step)) / len(r.trace["devices"])
    return other_program_seconds(r, r.step) / ticks * 1e3 if ticks else None


def step_mfu(r):
    work = step_work(r)
    if work is None:
        return None
    return work[0] / (sum(program_runs(r, r.step))
                      * r.peak["bf16_flops_per_s"]) * 100


def step_roofline(r):
    work = step_work(r)
    return work[1] / sum(program_runs(r, r.step)) * 100 if work else None


def idle_share(r):
    if not r.trace:
        return None
    return (1 - r.trace["busy_s"] / r.trace["window_s"]) * 100


def tick_line(r) -> str:
    """Durations of the window's ticks, for the record of a run."""
    t = window_ticks(r)
    if not len(t):
        return "no ticks in the window"
    d = np.diff(t[:, 1]) * 1e3
    return (f"window ticks: {len(t)}, gap between tick ends median "
            f"{np.median(d)} ms, p99 {np.percentile(d, 99)} ms, max "
            f"{d.max()} ms")
