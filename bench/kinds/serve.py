"""Serving cells: the program's ``ServeEngine`` driven tick by tick.

The mix names its driver (``bench/drivers/<driver>.py``: open loop,
backlog), which submits requests and ticks the engine through its ramp
and window.  The step metrics read the runs of ``STEP`` in the trace;
the FLOPs and bytes of a decode step come from
``bench/counts/<arch_kind>.py``.  ``correct``: a sample of the finished
requests against the float32 reference (``bench/lib/check.py``), held to
the cell's limits.
"""
from __future__ import annotations

import importlib

from bench.lib import check, measure, serving

STEP = "jit_serve_step"      # the program whose device runs are the step


def build(c: dict, mix: dict, seed: int, devices, phases: dict):
    layout = importlib.import_module(
        f"bench.weights.{c['arch_kind']}").layout(c)
    return serving.build(c, layout, seed, phases)


def drive(engine, c: dict, mix: dict, seed: int, seconds: float,
          tracer) -> dict:
    rec = serving.Recorder(engine)
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    out = driver.drive(rec, mix, seed, c["vocab_size"], seconds, tracer)
    return {**out, "ticks": rec.tick_array(), "reqs": list(rec.reqs.values()),
            "finished": rec.finished,
            "counts": importlib.import_module(
                f"bench.counts.{c['arch_kind']}")}


def notes(r) -> list:
    lines = [measure.tick_line(r)]
    if r.trace:
        lines.append(f"decode step bound: {measure.which_bound(r)}")
    return lines


def verify(r, mix: dict, limits: dict, seed: int):
    """(checks, correct, lines): the widest and mean logit gap of the
    sampled requests' served tokens, and how many were compared."""
    picked = check.sample(r.finished, seed, mix["check_requests"])
    served = sum(len(q.request.output) for q in picked)
    gaps = check.logit_gaps(r.c, seed, check.sequences(picked))["f32"]
    checks = {f"{k}_logit_gap": {"value": gaps[f"{k}_gap"],
                                 "limit": limits[f"{k}_logit_gap"]}
              for k in ("max", "mean") if f"{k}_logit_gap" in limits}
    checks["tokens_compared"] = {"value": gaps["positions"],
                                 "limit": limits["min_tokens_compared"]}
    correct = (all(v["value"] <= v["limit"] for k, v in checks.items()
                   if k != "tokens_compared")
               and gaps["positions"] >= limits["min_tokens_compared"]
               and gaps["positions"] == served)
    lines = [f"requests checked: {len(picked)}, served tokens {served}, "
             f"positions where the served token is not the reference's "
             f"argmax: {gaps['disagree']}"]
    return checks, correct, lines
