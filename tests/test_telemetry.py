"""The span ring (``runtime/telemetry.py``), the spans and counters of
``ServeEngine``, and the named scopes of the compiled serve step."""
import contextlib
import math
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import COUNTERS, counter_line
from repro.models import init_model
from repro.models.transformer import init_cache
from repro.runtime import telemetry
from repro.runtime.serve_loop import Request, ServeEngine
from repro.runtime.steps import make_serve_step

TICK_CHILDREN = ["serve.refill", "serve.feed", "serve.dispatch",
                 "serve.sync", "serve.retire"]
SCOPES = ["attn.qkv", "attn.kv_cache", "attn.core", "attn.out", "ffn",
          "lm_head", "sample"]
ARCHS = ["qwen2.5-3b", "granite-moe-1b-a400m", "moonlight-16b-a3b"]
#: the expert layer's scopes inside ``ffn``, by configuration
MOE_SCOPES = {"granite-moe-1b-a400m": ["moe.route", "moe.experts"],
              "moonlight-16b-a3b": ["moe.route", "moe.experts",
                                    "moe.shared"]}


# -- the ring ---------------------------------------------------------------

def test_ring_records_nesting_and_parents():
    ring = telemetry.Ring(16)
    with ring.span("a"):
        with ring.span("b", rid=3):
            pass
        with ring.span("c"):
            with ring.span("c"):          # a name nested in itself
                pass
    s = ring.spans()
    assert list(s["name"]) == ["a", "b", "c", "c"]
    assert list(s["seq"]) == [0, 1, 2, 3]
    assert list(s["parent"]) == [-1, 0, 0, 2]
    assert list(s["rid"]) == [-1, 3, -1, -1]
    for i, p in enumerate(s["parent"]):
        if p >= 0:
            assert s["t0"][p] <= s["t0"][i] <= s["t1"][i] <= s["t1"][p]
    assert s["complete"] and ring.dropped() == 0


def test_ring_closes_a_span_left_by_an_exception():
    ring = telemetry.Ring(8)
    with pytest.raises(ValueError):
        with ring.span("outer"):
            with ring.span("inner"):
                raise ValueError
    with ring.span("after"):
        pass
    s = ring.spans()
    assert list(s["name"]) == ["outer", "inner", "after"]
    assert list(s["parent"]) == [-1, 0, -1]
    assert not np.isnan(s["t1"]).any()


@pytest.mark.parametrize("n", [5, 6, 11])
def test_ring_wraps_and_counts_what_it_drops(n):
    ring = telemetry.Ring(4)
    for k in range(n):
        ring.record("r", float(k), float(k) + 0.5, rid=k)
    assert ring.dropped() == n - 4
    s = ring.spans()
    assert list(s["rid"]) == list(range(n - 4, n))
    assert list(s["seq"]) == list(range(n - 4, n))
    # the newest overwritten span started at n - 5
    assert ring.spans(n - 4.5)["complete"]
    assert not ring.spans(n - 5.0)["complete"]


def test_a_span_overwritten_while_open_leaves_the_newer_one():
    ring = telemetry.Ring(2)
    with ring.span("long"):
        for k in range(3):
            ring.record("r", 10.0 + k, 11.0 + k, rid=k)
    s = ring.spans()
    assert list(s["rid"]) == [1, 2] and list(s["t1"]) == [12.0, 13.0]


def test_spans_filters_by_start_and_leaves_out_open_spans():
    ring = telemetry.Ring(16)
    for k in (1.0, 2.0, 3.0):
        ring.record("r", k, 10.0, rid=int(k))
    assert list(ring.spans(1.5, 2.5)["rid"]) == [2]
    assert list(ring.spans(2.0, 3.0)["rid"]) == [2, 3]
    assert list(ring.spans(0.0)["rid"]) == [1, 2, 3]
    with ring.span("open"):
        assert "open" not in list(ring.spans()["name"])
    assert "open" in list(ring.spans()["name"])


def test_a_span_is_an_annotation_in_a_profile(tmp_path):
    ring = telemetry.Ring(8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("outer.test"):
            with ring.span("serve.test"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = next(tmp_path.rglob("*.xplane.pb"))
    ev = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
          for p in ProfileData.from_file(str(path)).planes
          for line in p.lines for e in line.events
          if e.name in ("outer.test", "serve.test")}
    assert set(ev) == {"outer.test", "serve.test"}
    (o0, o1), (s0, s1) = ev["outer.test"], ev["serve.test"]
    assert o0 <= s0 < s1 <= o1 and s1 - s0 >= 2e6


# -- the engine's spans and counters ----------------------------------------

@pytest.fixture(scope="module")
def dense():
    cfg = get_config("qwen2.5-3b", smoke=True)
    return cfg, init_model(jax.random.PRNGKey(0), cfg)


def _engine(dense, slots=2):
    cfg, params = dense
    return ServeEngine(params, cfg, batch_slots=slots, max_len=32)


def _tick_spans(t0):
    s = telemetry.spans(t0)
    assert s["complete"]
    return s


def test_a_step_emits_a_tick_with_its_phases_in_order(dense):
    eng = _engine(dense)
    eng.submit(Request(rid=1, prompt=[3, 4], max_new_tokens=2))
    t0 = time.perf_counter()
    eng.step()
    s = _tick_spans(t0)
    host = [i for i, n in enumerate(s["name"]) if n.startswith("serve.")
            and n not in ("serve.queued", "serve.prefill", "serve.decode")]
    names = [s["name"][i] for i in host]
    assert names == ["serve.tick"] + TICK_CHILDREN
    tick = s["seq"][host[0]]
    assert all(s["parent"][i] == tick for i in host[1:])
    assert all(s["t1"][a] <= s["t0"][b] for a, b in zip(host[1:], host[2:]))


def test_a_refill_into_a_used_slot_emits_a_wipe_under_refill(dense):
    eng = _engine(dense)
    for rid in range(3):                   # the third waits for a slot
        eng.submit(Request(rid=rid, prompt=[5], max_new_tokens=1))
    eng.step()
    t0 = time.perf_counter()
    eng.step()
    s = _tick_spans(t0)
    names = list(s["name"])
    assert names.count("serve.wipe") == 1
    wipe = names.index("serve.wipe")
    refill = names.index("serve.refill")
    assert s["parent"][wipe] == s["seq"][refill]
    assert s["parent"][refill] == s["seq"][names.index("serve.tick")]
    assert s["rid"][wipe] == 2
    assert eng.stats()["wipes"] == 1.0


TRAFFIC = {
    "one_slot_many": (1, [(2, 3), (1, 1), (4, 2)]),
    "two_slots_queue": (2, [(3, 2), (1, 4), (5, 1), (2, 2), (1, 1)]),
    "empty_prompt": (2, [(0, 3), (2, 2), (3, 1)]),
}


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_counters_agree_with_the_run(dense, name):
    slots, shapes = TRAFFIC[name]
    eng = _engine(dense, slots)
    reqs = [Request(rid=100 + i, prompt=list(range(1, p + 1)),
                    max_new_tokens=n) for i, (p, n) in enumerate(shapes)]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    live = 0                     # slots that ran in a tick, summed
    while eng.queue or any(r is not None for r in eng.active):
        done = eng.step()
        live += sum(r is not None for r in eng.active) + len(done)
    st = eng.stats()
    assert st["admitted"] == len(reqs)
    # every slot's first occupant finds it clean; each later one wipes it
    assert st["wipes"] == len(reqs) - min(slots, len(reqs))
    # the tokens written into the cache: all of each prompt but its last
    # token (an empty one feeds a BOS), and every output but the last
    written = sum(max(len(r.prompt), 1) - 1 + len(r.output) for r in reqs)
    assert st["prompt_tokens"] + st["decode_tokens"] == written
    assert st["decode_tokens"] == sum(len(r.output) for r in reqs)
    # a prompt token goes in a chunk or in its slot's own row
    assert 0 < st["chunk_tokens"] <= sum(len(r.prompt) for r in reqs)
    # a live slot-tick fed one token or carried the tick's chunk
    assert live == written - st["chunk_tokens"] + st["prefill_chunks"]
    assert st["queue_peak"] == len(reqs)
    assert st["spans_dropped"] == telemetry.dropped()
    s = _tick_spans(t0)
    assert list(s["name"]).count("serve.wipe") == st["wipes"]
    assert list(s["name"]).count("serve.tick") == st["ticks"]
    line = counter_line(eng)
    assert all(k in line for k in COUNTERS)


def test_chunk_counters_give_the_fill_share(dense):
    """``prefill_chunks`` counts the ticks that carried a chunk and
    ``chunk_tokens`` the prompt tokens in them."""
    cfg, params = dense
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=32, chunk=4)
    for rid, n in enumerate([0, 1, 4, 9]):
        eng.submit(Request(rid=rid, prompt=list(range(1, n + 1)),
                           max_new_tokens=2))
    eng.run()
    st = eng.stats()
    # the 1-token prompt: one chunk.  The 4- and 9-token prompts are
    # placed on a tick that wipes, which carries no chunk: one token each
    # in its own row; then the 4-token prompt's chunk of 3, beside the
    # 9-token prompt's second token; then its chunks of 4 and 3
    assert (st["prefill_chunks"], st["chunk_tokens"]) == (4, 11)
    assert st["chunk_tokens"] / (st["prefill_chunks"] * eng.chunk) == 11 / 16
    line = counter_line(eng)
    assert "prefill_chunks 4" in line and "chunk_tokens 11" in line


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_request_times_are_ordered_and_match_their_spans(dense, name):
    slots, shapes = TRAFFIC[name]
    eng = _engine(dense, slots)
    reqs = [Request(rid=200 + i, prompt=list(range(1, p + 1)),
                    max_new_tokens=n) for i, (p, n) in enumerate(shapes)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run()
    s = telemetry.spans(t0)
    for r in reqs:
        assert (r.submitted_at <= r.admitted_at <= r.first_token_at
                <= r.finished_at)
        for span, a, b in (("serve.queued", r.submitted_at, r.admitted_at),
                           ("serve.prefill", r.admitted_at,
                            r.first_token_at),
                           ("serve.decode", r.first_token_at,
                            r.finished_at)):
            k = np.flatnonzero((s["name"] == span) & (s["rid"] == r.rid))
            assert len(k) == 1, (span, r.rid)
            assert (s["t0"][k[0]], s["t1"][k[0]]) == (a, b)


def test_request_times_start_as_nan():
    r = Request(rid=0, prompt=[1], max_new_tokens=1)
    assert all(math.isnan(t) for t in (r.submitted_at, r.admitted_at,
                                       r.first_token_at, r.finished_at))


# -- named scopes in the compiled step --------------------------------------

def _step_hlo(arch):
    cfg = get_config(arch, smoke=True)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_cache(cfg, 4, 32))
    tokens = jax.ShapeDtypeStruct((4, 1), jnp.int32)
    index = jax.ShapeDtypeStruct((4,), jnp.int32)
    return jax.jit(make_serve_step(cfg)).lower(
        params, tokens, cache, index).compile().as_text()


def _strip_metadata(hlo: str) -> str:
    """The HLO without op metadata and the source-location tables."""
    lines = [line for line in hlo.splitlines() if not re.match(
        r"(FileNames|FunctionNames|FileLocations|StackFrames)$|\d+ ", line)]
    return re.sub(r",?\s*metadata=\{[^}]*\}", "", "\n".join(lines))


@pytest.fixture(scope="module")
def step_hlo():
    return {arch: _step_hlo(arch) for arch in ARCHS}


@pytest.mark.parametrize("scope", SCOPES)
@pytest.mark.parametrize("arch", ARCHS)
def test_the_step_carries_each_scope(step_hlo, arch, scope):
    names = re.findall(r'op_name="([^"]*)"', step_hlo[arch])
    assert any(f"/{scope}/" in n for n in names), scope


@pytest.mark.parametrize("arch,scope", [(a, s) for a, ss in MOE_SCOPES.items()
                                        for s in ss])
def test_the_expert_layer_carries_its_scopes(step_hlo, arch, scope):
    names = re.findall(r'op_name="([^"]*)"', step_hlo[arch])
    assert any(f"/ffn/{scope}/" in n for n in names), scope


@pytest.mark.parametrize("arch", ARCHS)
def test_the_scopes_change_only_metadata(step_hlo, arch, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _step_hlo(arch)
    assert not any(f"/{s}/" in bare for s in SCOPES + MOE_SCOPES.get(arch, []))
    assert _strip_metadata(bare) == _strip_metadata(step_hlo[arch])
