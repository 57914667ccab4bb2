"""The readers of the program's spans (``host_serial_ms_per_tick``,
``slot_wait_p99_ms``, ``prefill_p90_ms``) on a hand-made record and span
ring, with answers computed by hand."""
import math
import sys
import types

import pytest

from bench import run
from repro.runtime import telemetry
from repro.runtime.serve_loop import Request

W0, W1, END = 100.0, 200.0, 250.0
READERS = ["host_serial_ms_per_tick.chat", "host_serial_ms_per_tick.batch",
           "slot_wait_p99_ms.chat", "prefill_p90_ms.chat"]


def _clock(monkeypatch, times):
    monkeypatch.setattr(telemetry, "perf_counter", iter(times).__next__)


def _tick(ring, monkeypatch, t0, sync0, sync1, t1):
    _clock(monkeypatch, [t0, sync0, sync1, t1])
    with ring.span("serve.tick"):
        with ring.span("serve.sync"):
            pass


def _req(rid, submitted, admitted=math.nan):
    return types.SimpleNamespace(request=Request(
        rid=rid, prompt=[1], max_new_tokens=1, submitted_at=submitted,
        admitted_at=admitted))


@pytest.fixture
def ring(monkeypatch):
    r = telemetry.Ring(64)
    monkeypatch.setattr(telemetry, "RING", r)
    return r


@pytest.fixture
def record():
    reqs = [_req(1, 110.0, 110.5),      # placed, first token at 112.5
            _req(2, 120.0),             # never placed
            _req(3, 90.0, 95.0),        # submitted before the window
            _req(4, 150.0, 151.0),      # placed, no first token
            _req(5, 190.0, 195.0),      # first token at 205
            _req(6, 199.0, 210.0)]      # placed after the window
    return types.SimpleNamespace(window=(W0, W1), end=END, reqs=reqs)


def _requests(ring):
    ring.record("serve.queued", 90.0, 95.0, rid=3)
    ring.record("serve.prefill", 95.0, 96.0, rid=3)
    ring.record("serve.queued", 110.0, 110.5, rid=1)
    ring.record("serve.prefill", 110.5, 112.5, rid=1)
    ring.record("serve.queued", 150.0, 151.0, rid=4)
    ring.record("serve.queued", 190.0, 195.0, rid=5)
    ring.record("serve.prefill", 195.0, 205.0, rid=5)
    ring.record("serve.queued", 199.0, 210.0, rid=6)
    ring.record("serve.prefill", 210.0, 211.0, rid=6)


def test_host_serial_ms_per_tick(ring, record, monkeypatch):
    _tick(ring, monkeypatch, 50.0, 50.001, 50.1, 50.2)      # before
    _tick(ring, monkeypatch, 150.0, 150.010, 150.025, 150.030)
    _tick(ring, monkeypatch, 150.030, 150.035, 150.045, 150.050)
    # (30 - 15) and (20 - 10) ms of host time
    assert run.reader("host_serial_ms_per_tick.chat")(record) == \
        pytest.approx(12.5)


def test_slot_wait_p99_ms(ring, record):
    _requests(ring)
    # 500 ms, 130,000 ms (never placed: 250 - 120 s), 1,000, 5,000 and
    # 11,000 ms; p99 of five: 11,000 + 0.96 x 119,000
    assert run.reader("slot_wait_p99_ms.chat")(record) == \
        pytest.approx(125240.0)


def test_prefill_p90_ms(ring, record):
    _requests(ring)
    # 2,000 ms; 0 (never placed); 99,000 (no first token: 250 - 151 s);
    # 10,000; 1,000 (placed after the window); p90 of five:
    # 10,000 + 0.6 x 89,000
    assert run.reader("prefill_p90_ms.chat")(record) == \
        pytest.approx(63400.0)


@pytest.mark.parametrize("name", READERS)
def test_a_ring_that_dropped_spans_of_the_window_reads_none(
        name, ring, record, monkeypatch):
    small = telemetry.Ring(4)
    monkeypatch.setattr(telemetry, "RING", small)
    _requests(small)
    for k in range(3):
        _tick(small, monkeypatch, 150.0 + k, 150.2 + k, 150.5 + k,
              150.6 + k)
    assert small.dropped() > 0
    assert run.reader(name)(record) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_ring_reads_none(name, record, monkeypatch):
    import repro.runtime
    monkeypatch.delattr(repro.runtime, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.runtime.telemetry", None)
    assert run.reader(name)(record) is None


@pytest.mark.parametrize("name", READERS)
def test_a_window_without_spans_reads_none_or_a_wait(name, ring, record):
    value = run.reader(name)(record)
    if name.startswith("host_serial"):
        assert value is None
    else:
        assert value is not None and value >= 0
