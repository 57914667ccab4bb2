"""The trace reduction, on hand-made events and on a small trace recorded
on a TPU v5e (tiny dense model, 4 slots, about 0.1 s of serving)."""
import gzip
import shutil
import types
from pathlib import Path

import numpy as np
import pytest

from bench.lib import trace
from bench import run
from bench.kinds.serve import STEP

DATA = Path(__file__).resolve().parent / "data"


def test_union_gaps_and_self_time_by_hand():
    iv = [(0, 10), (5, 12), (20, 30)]
    assert trace.union_length(iv) == 22
    assert trace.gaps(iv, -5, 40) == [(-5, 0), (12, 20), (30, 40)]
    # a while op spanning two body ops keeps only its own time
    ev = [("while", 0, 100), ("a", 10, 40), ("b", 50, 90), ("a", 200, 210)]
    assert dict(trace.self_times(ev)) == {"while": 30, "a": 40, "b": 40}


def test_names():
    assert trace.program_name("jit_serve_step(12648702877592038660)") == \
        "jit_serve_step"
    assert trace.op_name("%fusion.3 = bf16[32,1,2048]{2,1,0:T(8,128)} "
                         "fusion(%p), kind=kLoop") == "fusion.3 bf16[32,1,2048]"
    assert trace.op_name("%while.13 = (s32[], bf16[2]) while(%t)") == \
        "while.13 tuple"


def test_reduce_hand_made_events():
    ev = {"host": [("bench.window", 0, 100), ("bench.tick", 0, 60),
                   ("bench.wait", 62, 100)],
          "devices": {"/device:TPU:0": {
              "modules": [("jit_serve_step(1)", 10, 50),
                          ("jit_scatter(2)", 70, 80),
                          ("jit_serve_step(1)", 95, 130)],
              "ops": [("%f = f32[2]{0} fusion()", 10, 50),
                      ("%s = f32[2]{0} scatter()", 70, 80),
                      ("%f = f32[2]{0} fusion()", 95, 130)]}}}
    r = trace.reduce_events(ev)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(55e-9)       # 40 + 10 + 5 inside
    progs = r["devices"]["/device:TPU:0"]["programs"]
    assert progs == {"jit_serve_step": [pytest.approx(40e-9)],
                     "jit_scatter": [pytest.approx(10e-9)]}
    gaps = dict(r["idle_gaps"])
    # gaps 0-10 and 50-70 (midpoint 60, inside the tick), 80-95
    assert gaps["bench.tick"] == pytest.approx(30e-9)
    assert gaps["bench.wait"] == pytest.approx(15e-9)
    assert r["device_ops"][0] == ["f f32[2]", pytest.approx(45e-9)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(DATA / "tiny_serve.xplane.pb.gz") as f, \
            open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    return str(path)


def test_recorded_trace(recorded):
    ev = trace.events_from_xplane(recorded)
    assert list(ev["devices"]) == ["/device:TPU:0"]
    assert {n for n, _, _ in ev["host"]} == {"bench.window", "bench.tick"}
    r = trace.reduce_events(ev)
    assert r["window_s"] == pytest.approx(0.10488303)
    assert r["busy_s"] == pytest.approx(0.00182778)
    runs = r["devices"]["/device:TPU:0"]["programs"]
    assert len(runs[STEP]) == 33
    assert sum(runs[STEP]) == pytest.approx(0.001786504)
    assert len(runs["jit_scatter"]) == 14            # 7 wipes of k and v
    # the whole window is either busy or an idle gap named by the host
    assert sum(t for _, t in r["idle_gaps"]) + r["busy_s"] == \
        pytest.approx(r["window_s"])
    assert r["idle_gaps"][0][0] == "bench.tick"
    assert len(r["device_ops"]) == 10


def test_trace_metrics_on_the_recorded_trace(recorded):
    """The per-layer readers on the recorded trace: shares stay within
    (0, 100], and the step's time is the programs' own."""
    import importlib.util
    import json
    root = Path(__file__).resolve().parents[2]
    c = json.loads((root / "tests/bench/fixtures/tiny-dense.json")
                   .read_text())
    from bench.counts import dense
    r = trace.reduce_trace(recorded)
    n = len(r["devices"]["/device:TPU:0"]["programs"][STEP])
    rec = types.SimpleNamespace(
        trace=r, trace_span=(0.0, 1.0), c=c, counts=dense, step=STEP,
        peak=json.loads((root / "bench/peaks.json").read_text())
        ["TPU v5 lite"],
        ticks=np.array([[0.1, 0.2, 4, 4, 4 * 40]] * n, np.float64))

    def read(name):
        spec = importlib.util.spec_from_file_location(
            name, run.reader_path(name))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(rec)

    assert read("serve_step_ms.chat") == pytest.approx(0.001786504 / 33 * 1e3)
    assert 0 < read("serve_step_roofline.chat") <= 100
    assert 0 < read("serve_step_mfu.chat") <= read("serve_step_roofline.chat")
    assert read("device_idle_share.chat") == pytest.approx(
        (1 - 0.00182778 / 0.10488303) * 100)
    eager = sum(sum(v) for k, v in r["devices"]["/device:TPU:0"]
                ["programs"].items() if k != STEP)
    assert read("eager_device_ms_per_tick.chat") == pytest.approx(eager / n * 1e3)
