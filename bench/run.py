#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  The mix names its kind
(``bench/kinds/<kind>.py``: serving, and any other a later cell brings),
which builds the system under test, drives it through the ramp and the
window, names the program whose runs are the step, and decides
``correct`` against the limits in ``bench/limits/<cell>.json``.  Each
metric is read by ``bench/metrics/<metric>.py``; a metric split by cell
as ``<metric>.<suffix>`` is read by the first of ``<metric>.<suffix>.py``
and ``<metric>.py`` that exists.  Adding a cell, a mix, a kind or a
metric adds files; nothing here names one.

A served configuration adds ``bench/configs/<config>.json`` (the
source's keys; ``arch`` names the program's config, ``arch_kind`` the
files below) and, for an ``arch_kind`` the benchmark has not got:
``bench/weights/<arch_kind>.py`` (``layout(c)``, every leaf of the
program's tree; optionally ``stacks(c)``, layer stacks of their own
lengths in the order they run, and ``program_keys(c)``, program-config
fields the file fixes beyond the GQA ones ``lib/serving.py`` compares),
``bench/reference/<arch_kind>.py`` (the plain float32 reference:
``prepare``, ``matmul``, ``rms_norm`` and ``layer``, which may take the
layer's ``index`` in the whole model; optionally ``order(c)``; see
``lib/check.py``) and ``bench/counts/<arch_kind>.py`` (a decode step's
FLOPs and bytes); each of its cells adds ``bench/limits/<cell>.json``,
and a new mix ``bench/traffic/<mix>.json``.  An untied output head is
an ``unembed`` leaf in the layout.

A kind module has ``STEP`` and four functions:
``build(c, mix, seed, devices, phases)`` returns the system, with every
program it runs compiled and warmed up (seconds of each phase go into
``phases``); ``drive(system, c, mix, seed, seconds, tracer)`` returns a
dict with ``window``, ``attempted`` and ``failed`` and whatever its
metrics read (a ``lateness`` list is printed); ``notes(record)`` returns
lines for standard error; ``verify(record, mix, limits, seed)`` returns
``(checks, correct, lines)``, each check a value beside its limit, and
runs once the system is freed.

Refuses any platform but ``tpu`` and fewer chips than the cell asks for
(exit 2, no result).  Set-up (the kind's ``build``) is timed as
``setup_s``; then the kind drives its ramp and a window of ``--seconds``.
With ``--trace 1`` a few seconds of load after the window are profiled
and the per-layer metrics are reported instead of the end-to-end ones.
After the window: peak memory is read, the system is freed, and the
kind checks what the window produced.  The last line of standard output
is the result as JSON; the numbers compared, each beside its limit, are
the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRACE_S = 1.5   # seconds of load after the window that a traced run profiles


def load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(bench: dict, name: str):
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return (wl, load(ROOT / entry["file"]),
            load(ROOT / "bench" / "traffic" / f"{wl['traffic']}.json"),
            load(ROOT / "bench" / "limits" / f"{name}.json"))


def metrics_for(bench: dict, wl: dict, trace: bool) -> list:
    ms = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in ms if wl["name"] in m.get("workloads", [wl["name"]])]


def reader_path(name: str) -> Path:
    """``bench/metrics/<name>.py``, or, for a metric split by cell
    (``<metric>.<suffix>``), the first file of the name with suffixes
    dropped one by one."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = ROOT / "bench" / "metrics" / (".".join(parts[:n]) + ".py")
        if path.is_file():
            return path
    raise SystemExit(f"no reader for metric {name!r} in bench/metrics")


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", reader_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def place_cache(jax) -> None:
    """JAX's compile cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else a
    fixed directory in the checkout (the path is part of the key)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def err(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load(ROOT / "BENCHMARK.json")
    wl, c, mix, limits = cell(bench, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        err(f"bench: no TPU (JAX found {devices[0].platform}); no result")
        return 2
    if len(devices) < wl["chips"]:
        err(f"bench: {len(devices)} chip(s), the cell needs {wl['chips']}")
        return 2
    place_cache(jax)
    peaks = load(ROOT / "bench" / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        err(f"bench: no peaks for device kind {kind!r} in bench/peaks.json")
        return 2
    result, lines = run_cell(bench, wl, c, mix, limits, args.seed,
                             args.seconds, bool(args.trace), peaks[kind])
    for line in lines:
        err(line)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(bench, wl, c, mix, limits, seed, seconds, traced, peak):
    """Everything after the look for a chip: (result, lines for stderr)."""
    import jax
    devices = jax.devices()
    used = devices[:wl["chips"]]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name.endswith(("backend_compile_duration",
                          "jaxpr_trace_duration")) else None)
    from bench.lib import trace
    kind = importlib.import_module(f"bench.kinds.{mix['kind']}")
    phases = {"start": time.perf_counter() - T_START}
    system = kind.build(c, mix, seed, used, phases)
    # set-up's objects live as long as the run: keep them out of the
    # collector's full passes inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    tracer = trace.Tracer(traced, tdir, TRACE_S)
    before = len(compiles)
    pauses = collections.defaultdict(float)
    started = {}

    def on_gc(phase, info):
        if phase == "start":
            started["t"] = time.perf_counter()
        else:
            pauses[info["generation"]] += time.perf_counter() - started["t"]
    gc.callbacks.append(on_gc)
    out = kind.drive(system, c, mix, seed, seconds, tracer)
    gc.callbacks.remove(on_gc)
    gc.unfreeze()
    in_window = len(compiles) - before
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
    reduced = None
    if tdir:
        paths = sorted(Path(tdir).rglob("*.xplane.pb"))
        reduced = trace.reduce_trace(str(paths[-1])) if paths else None
        shutil.rmtree(tdir, ignore_errors=True)
    lines = [f"set-up phases, s: {phases}"]
    lat = sorted(out.get("lateness", []))
    if lat:
        lines.append(f"generator lateness: median {lat[len(lat) // 2] * 1e3}"
                     f" ms, max {lat[-1] * 1e3} ms over {len(lat)} submissions")
    lines.append(f"compiles in the run: {in_window}; garbage collection "
                 f"by generation, ms: "
                 f"{ {g: t * 1e3 for g, t in sorted(pauses.items())} }")

    record = types.SimpleNamespace(
        **out, setup_s=setup_s, trace=reduced, trace_span=tracer.span, c=c,
        peak=peak, step=kind.STEP)
    lines += kind.notes(record)

    # the program's state leaves the chip before the reference runs
    del system
    gc.collect()
    checks, correct, more = kind.verify(record, mix, limits, seed)
    lines += more

    metrics = {}
    for m in metrics_for(bench, wl, traced):
        v = reader(m["name"])(record)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak_mem}
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": dev}
    if reduced:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    lines += [f"check {k}: {v['value']} limit {v['limit']}"
              for k, v in checks.items()]
    return result, lines


if __name__ == "__main__":
    sys.exit(main())
