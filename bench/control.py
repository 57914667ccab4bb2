#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not run by a
benchmark run).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51

For each seed, in one process: the cell's own run (weights from the
seed, the engine, the driver's ramp and window at the cell's load), then
the same sample of served requests that a run checks, read three ways
against the float32 reference: the program's served tokens (``f32``: the
lower reading), and the tokens that the reference puts first when its
bfloat16 weights are rounded to float8 e4m3 (``fp8w``) or its weights
and matmul inputs are (``fp8``): the controls, whose widest gaps give
the upper reading.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402


class _Off:
    pending = False
    span = (float("nan"), float("nan"))


def readings(c: dict, mix: dict, seed: int, seconds: float,
             modes=("f32", "fp8w", "fp8")) -> dict:
    from bench.kinds import serve
    from bench.lib import check
    engine = serve.build(c, mix, seed, None, {})
    finished = serve.drive(engine, c, mix, seed, seconds, _Off())["finished"]
    del engine
    gc.collect()
    picked = check.sample(finished, seed, mix["check_requests"])
    out = check.logit_gaps(c, seed, check.sequences(picked), modes)
    out["served"] = sum(len(q.request.output) for q in picked)
    out["longest"] = max((len(q.request.output) for q in picked), default=0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--modes", default="f32,fp8w,fp8")
    args = ap.parse_args(argv)
    bench = run.load(ROOT / "BENCHMARK.json")
    wl, c, mix, _ = run.cell(bench, args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        run.err("control: no TPU; no readings")
        return 2
    run.place_cache(jax)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(c, mix, seed, args.seconds, tuple(args.modes.split(",")))
        print(json.dumps({"workload": wl["name"], "seed": seed, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
