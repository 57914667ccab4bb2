#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate that the
engine sustains without a growing queue (not run by a benchmark run).

    python3 bench/sweep.py --workload <cell> --rates 1.6,2.0,2.4 --seconds 40 --seed 1

One set-up, then for each rate the cell's driver with that rate, its ramp
and a window of ``--seconds``; no drain.  Between rates the engine's
queue and slots are emptied (requests in flight are abandoned; the slots
are wiped on their next admission as in any run).  One JSON line per
rate: offered and admitted requests per second in the window, the queue
at the window's end and at its middle, and the time to first token of
the requests due in each half of the window.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402
from bench.control import _Off  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = run.load(ROOT / "BENCHMARK.json")
    wl, c, mix, _ = run.cell(bench, args.workload)
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        run.err("sweep: no TPU")
        return 2
    run.place_cache(jax)
    from bench.lib import serving
    layout = importlib.import_module(
        f"bench.weights.{c['arch_kind']}").layout(c)
    engine = serving.build(c, layout, args.seed)
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    for rate in (float(r) for r in args.rates.split(",")):
        rec = serving.Recorder(engine)
        queue = []
        tick = rec.tick

        def tick_and_log():
            tick()
            queue.append((rec.ticks[-1][1], len(engine.queue)))
        rec.tick = tick_and_log
        out = driver.drive(rec, {**mix, "rate_per_s": rate,
                                 "drain_cap_s": 0.0},
                           args.seed, c["vocab_size"], args.seconds, _Off())
        w0, w1 = out["window"]
        mid = (w0 + w1) / 2
        due = [q for q in rec.reqs.values() if w0 <= q.due < w1]

        def ttft(reqs):
            v = [(q.token_times[0] if q.token_times else w1) - q.due
                 for q in reqs]
            return ([float(np.percentile(v, p)) for p in (50, 90)]
                    if v else None)
        q_at = lambda t: next((n for s, n in queue if s >= t), None)  # noqa
        print(json.dumps({
            "rate_per_s": rate, "offered_per_s": len(due) / (w1 - w0),
            "admitted_per_s": sum(w0 <= q.admitted < w1
                                  for q in rec.reqs.values()) / (w1 - w0),
            "queue_mid": q_at(mid), "queue_end": len(engine.queue),
            "ttft_s_p50_p90_first_half": ttft([q for q in due if q.due < mid]),
            "ttft_s_p50_p90_second_half": ttft([q for q in due
                                                if q.due >= mid]),
            "tick_ms_median": float(np.median(np.diff(
                rec.tick_array()[:, 1]))) * 1e3}), flush=True)
        engine.queue.clear()
        engine.active = [None] * engine.B
        engine.remaining_prompt = [[] for _ in range(engine.B)]
    return 0


if __name__ == "__main__":
    sys.exit(main())
