"""Reduction of a JAX profiler trace to what the metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU
its device planes are named ``/device:TPU:<n>`` and hold, among others,
the lines ``XLA Modules`` (one event per program run, named
``<program>(<fingerprint>)``) and ``XLA Ops`` (one event per operation).
The host plane ``/host:CPU`` holds the benchmark's own annotations
(``bench.*``), on the same clock.

``reduce_trace`` keeps, inside the window that the ``bench.window``
annotation spans: per device the busy time (union of operation
intervals) and every program run; the operations that took most time;
and the idle gaps, each named by the innermost ``bench.*`` annotation
around its midpoint, which says what the host was doing.

``Tracer`` records such a trace: a driver calls it between ticks or
steps once the window's numbers are final.
"""
from __future__ import annotations

import collections
import re
import time
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[32,1,2048]{...} fusion(...)`` -> ``fusion.3
    bf16[32,1,2048]``: the op and its result, without layouts."""
    lhs, _, rhs = event_name.partition(" = ")
    shape = ("tuple" if rhs.startswith("(") else
             re.split(r"[{ ]", rhs, maxsplit=1)[0])
    return (lhs.lstrip("%") + (" " + shape if shape else "")).strip()


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def self_times(events: List[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Time of each named op less the ops nested inside it (a ``while``
    op spans its whole body)."""
    out: Dict[str, float] = collections.Counter()
    stack: list = []                       # [name, end]
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= min(e, stack[-1][1]) - s
        out[n] += e - s
        stack.append([n, e])
    return out


def gaps(intervals: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def events_from_xplane(path: str) -> Dict[str, list]:
    """Flatten a trace into plain lists of (name, start_ns, end_ns):
    ``devices`` {plane: {"modules": [...], "ops": [...]}} and ``host``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    d[key].extend((e.name, e.start_ns, e.end_ns)
                                  for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def reduce_events(ev: Dict[str, list], top: int = 10) -> dict:
    """The window's busy time per device, program runs, top operations and
    idle gaps by host activity, from ``events_from_xplane``'s lists."""
    wins = [(s, e) for n, s, e in ev["host"] if n == WINDOW]
    if not wins or not ev["devices"]:
        return {}
    lo, hi = wins[0]
    spans = [(n, s, e) for n, s, e in ev["host"] if n != WINDOW]
    devices, op_time = {}, collections.Counter()
    idle_by = collections.Counter()
    for plane, d in sorted(ev["devices"].items()):
        ops = [(max(s, lo), min(e, hi)) for _, s, e in d["ops"]
               if e > lo and s < hi]
        inside = [(op_name(n), max(s, lo), min(e, hi))
                  for n, s, e in d["ops"] if e > lo and s < hi]
        for n, t in self_times(inside).items():
            op_time[n] += t / len(ev["devices"])
        runs = collections.defaultdict(list)
        for n, s, e in d["modules"]:
            if s >= lo and e <= hi:
                runs[program_name(n)].append((e - s) / 1e9)
        devices[plane] = {"busy_s": union_length(ops) / 1e9,
                          "programs": dict(runs)}
        for gs, ge in gaps(ops, lo, hi):
            mid = (gs + ge) / 2
            around = [(e - s, n) for n, s, e in spans if s <= mid <= e]
            idle_by[min(around)[1] if around else "outside bench spans"] += (
                (ge - gs) / 1e9 / len(ev["devices"]))
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices.values()) / len(devices),
        "device_ops": [[n, t / 1e9] for n, t in op_time.most_common(top)],
        "idle_gaps": [[n, t] for n, t in idle_by.most_common(top)],
    }


def reduce_trace(path: str, top: int = 10) -> dict:
    return reduce_events(events_from_xplane(path), top)


class Tracer:
    """Profiles ``length_s`` seconds from its first ``at`` call, between
    ticks.  The drivers call it once the window's numbers are final
    (``stop_trace`` stalls the host for seconds), with the load still
    on.  Off unless ``enabled``."""

    def __init__(self, enabled: bool, directory: Optional[str],
                 length_s: float):
        self.dir, self.length_s = directory, length_s
        self.state = "armed" if enabled else "done"
        self._ann = None
        self.span = (float("nan"), float("nan"))

    @property
    def pending(self) -> bool:
        return self.state != "done"

    def at(self, now: float) -> None:
        import jax
        if self.state == "armed":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.span = (time.perf_counter(), float("nan"))
            self.state = "on"
        elif self.state == "on" and now >= self.span[0] + self.length_s:
            self._ann.__exit__(None, None, None)
            self.span = (self.span[0], time.perf_counter())
            jax.profiler.stop_trace()
            self.state = "done"
