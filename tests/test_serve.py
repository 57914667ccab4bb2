"""Continuous-batching serve engine."""
import jax
import numpy as np

from repro.configs import get_config
from repro.models import init_model
from repro.runtime.serve_loop import Request, ServeEngine


def _engine(slots=2, max_len=64):
    cfg = get_config("qwen2.5-3b", smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    return ServeEngine(params, cfg, batch_slots=slots, max_len=max_len), cfg


def test_engine_completes_all_requests():
    eng, cfg = _engine(slots=2)
    reqs = [Request(rid=i, prompt=[3 + i, 7, 11], max_new_tokens=5)
            for i in range(5)]       # more requests than slots
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 5
    for r in done:
        assert r.done
        assert len(r.output) == 5
        assert all(0 <= t < cfg.vocab for t in r.output)


def test_engine_respects_budgets():
    eng, _ = _engine(slots=1)
    a = Request(rid=0, prompt=[1], max_new_tokens=3)
    b = Request(rid=1, prompt=[2, 3], max_new_tokens=7)
    eng.submit(a)
    eng.submit(b)
    done = eng.run()
    assert [len(r.output) for r in sorted(done, key=lambda r: r.rid)] \
        == [3, 7]


def test_engine_eos_stops_early():
    eng, cfg = _engine(slots=1)
    # discover what the model emits first, then use it as EOS
    probe = Request(rid=0, prompt=[5, 9], max_new_tokens=1)
    eng.submit(probe)
    first = eng.run()[0].output[0]

    eng2, _ = _engine(slots=1)
    req = Request(rid=1, prompt=[5, 9], max_new_tokens=50, eos_id=first)
    eng2.submit(req)
    done = eng2.run()
    assert done[0].output[-1] == first
    assert len(done[0].output) < 50


# ---------------------------------------------------------------------------
# slot-reuse regression suite (the continuous-batching KV-cache bug)
# ---------------------------------------------------------------------------


def _shared_params():
    cfg = get_config("qwen2.5-3b", smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _solo_output(params, cfg, req, max_len=64):
    eng = ServeEngine(params, cfg, batch_slots=1, max_len=max_len)
    eng.submit(Request(rid=req.rid, prompt=list(req.prompt),
                       max_new_tokens=req.max_new_tokens))
    return eng.run()[0].output


def test_refilled_slot_output_bit_equal_to_solo():
    """Staggered arrivals through a 2-slot pool: every request — in
    particular every request *refilled* into a previously-used slot —
    must produce exactly the tokens it produces when served alone.

    Before the per-slot KV index fix this failed: refilled slots wrote
    their keys/values at the pool-wide ``max(pos)`` cursor and attended
    to the previous occupant's cache rows."""
    params, cfg = _shared_params()
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=64)
    reqs = [Request(rid=i, prompt=[3 + 2 * i, 7, 11 + i][: 1 + i % 3],
                    max_new_tokens=4 + i % 3) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    done = {r.rid: r for r in eng.run()}
    assert len(done) == 6
    for r in reqs:
        solo = _solo_output(params, cfg, r)
        assert done[r.rid].output == solo, (
            f"rid={r.rid}: batched {done[r.rid].output} != solo {solo}")


def test_single_request_path_unchanged():
    """One request in a 1-slot pool exercises the scalar-index decode
    path end to end (the pre-fix behavior for B=1 was correct and must
    stay bit-identical)."""
    params, cfg = _shared_params()
    out1 = _solo_output(params, cfg,
                        Request(rid=0, prompt=[5, 9, 2], max_new_tokens=6))
    out2 = _solo_output(params, cfg,
                        Request(rid=0, prompt=[5, 9, 2], max_new_tokens=6))
    assert out1 == out2
    assert len(out1) == 6


def test_empty_prompt_request():
    """An empty prompt starts generation from the BOS convention (token
    0) instead of crashing or reading stale slot state."""
    params, cfg = _shared_params()
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=32)
    eng.submit(Request(rid=0, prompt=[], max_new_tokens=4))
    # a second, normal request shares the pool to make sure the empty
    # prompt does not disturb a neighbor slot
    eng.submit(Request(rid=1, prompt=[4, 8], max_new_tokens=4))
    done = {r.rid: r for r in eng.run()}
    assert len(done[0].output) == 4
    assert done[1].output == _solo_output(params, cfg,
                                          Request(1, [4, 8], 4), max_len=32)


def test_max_len_boundary_truncates_generation():
    """A request whose prompt + budget exceeds the cache length stops at
    the max_len boundary instead of writing past the cache."""
    params, cfg = _shared_params()
    max_len = 16
    eng = ServeEngine(params, cfg, batch_slots=1, max_len=max_len)
    prompt = list(range(1, 11))          # 10 prompt tokens
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=50))
    done = eng.run()
    assert done[0].done
    # pos advances once per tick; the engine stops at max_len - 1
    assert len(prompt) + len(done[0].output) <= max_len
    assert len(done[0].output) < 50


def test_slot_reuse_after_max_len_boundary():
    """A slot freed by the max_len cut must serve its next occupant
    correctly (the refill zeroes the full cache row)."""
    params, cfg = _shared_params()
    eng = ServeEngine(params, cfg, batch_slots=1, max_len=16)
    eng.submit(Request(rid=0, prompt=list(range(1, 11)),
                       max_new_tokens=50))
    follow = Request(rid=1, prompt=[6, 2], max_new_tokens=5)
    eng.submit(follow)
    done = {r.rid: r for r in eng.run()}
    assert done[1].output == _solo_output(params, cfg, follow, max_len=16)


def test_run_truncation_signal():
    """Hitting max_ticks with work left must warn and set the stats
    flag; a drained run must not."""
    import warnings

    params, cfg = _shared_params()
    eng = ServeEngine(params, cfg, batch_slots=1, max_len=32)
    eng.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=10))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        eng.run(max_ticks=3)
    assert eng.stats()["truncated"] == 1.0
    assert any("truncated" in str(w.message) for w in caught)
    # drain the rest: the flag resets and no warning fires
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        done = eng.run()
    assert eng.stats()["truncated"] == 0.0
    assert not caught
    assert len(done) == 1


# ---------------------------------------------------------------------------
# admission scheduler (multi-tenant serving lanes)
# ---------------------------------------------------------------------------


def _two_lane_sched(mode, slots=2):
    from repro.runtime.serve_loop import AdmissionScheduler, Lane

    params, cfg = _shared_params()
    mk = lambda: ServeEngine(params, cfg, batch_slots=slots, max_len=32)
    return AdmissionScheduler(
        [Lane("hi", mk(), share=2.0, priority=1),
         Lane("lo", mk(), share=1.0)], mode=mode), params, cfg


def _burst(sched, lane, rids, max_new=4):
    for rid in rids:
        sched.submit(lane, Request(rid=rid, prompt=[1 + rid % 5, 3],
                                   max_new_tokens=max_new))


def test_scheduler_drains_all_lanes_every_mode():
    for mode in ("spatial", "time", "serialized"):
        sched, _, _ = _two_lane_sched(mode)
        _burst(sched, "hi", range(3))
        _burst(sched, "lo", range(10, 13))
        done = sched.run(max_ticks=2000)
        assert {k: len(v) for k, v in done.items()} == {"hi": 3, "lo": 3}
        assert sched.stats()["truncated"] == 0.0


def test_scheduler_spatial_lanes_progress_concurrently():
    sched, _, _ = _two_lane_sched("spatial")
    _burst(sched, "hi", range(2))
    _burst(sched, "lo", range(10, 12))
    sched.run(max_ticks=2000)
    # disjoint bands: both engines ticked the same rounds
    assert sched.lanes["hi"].engine.ticks == sched.lanes["lo"].engine.ticks


def test_scheduler_serialized_respects_priority():
    sched, _, _ = _two_lane_sched("serialized")
    _burst(sched, "hi", range(2))
    _burst(sched, "lo", range(10, 12))
    sched.run(max_ticks=2000)
    st = sched.stats()
    assert st["hi.mean_finish_tick"] < st["lo.mean_finish_tick"]


def test_scheduler_time_slices_by_share():
    sched, _, _ = _two_lane_sched("time")
    _burst(sched, "hi", range(4), max_new=6)
    _burst(sched, "lo", range(10, 14), max_new=6)
    sched.run(max_ticks=4000)
    st = sched.stats()
    # 2:1 share: while both lanes are backlogged the high-share lane
    # ticks about twice as often, so its requests finish earlier even
    # though both lanes need the same total engine work
    assert st["hi.mean_finish_tick"] < st["lo.mean_finish_tick"]


def test_scheduler_bursty_admission_bit_equal_to_solo():
    """A burst far larger than the slot pool, admitted over many rounds:
    every request still decodes exactly as it does alone."""
    sched, params, cfg = _two_lane_sched("spatial", slots=2)
    reqs = [Request(rid=i, prompt=[2 + i % 4, 9], max_new_tokens=3 + i % 2)
            for i in range(6)]
    for r in reqs:
        sched.submit("hi", Request(rid=r.rid, prompt=list(r.prompt),
                                   max_new_tokens=r.max_new_tokens))
    done = {r.rid: r for r in sched.run(max_ticks=2000)["hi"]}
    assert len(done) == 6
    for r in reqs:
        assert done[r.rid].output == _solo_output(params, cfg, r,
                                                  max_len=32)


def test_scheduler_truncation_signal():
    import warnings

    sched, _, _ = _two_lane_sched("time")
    _burst(sched, "hi", range(2), max_new=20)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sched.run(max_ticks=2)
    assert sched.stats()["truncated"] == 1.0
    assert any("truncated" in str(w.message) for w in caught)


def test_serve_cli_builds_published_config_without_smoke():
    from repro.launch.serve import parser

    args = parser().parse_args(["--arch", "qwen2.5-3b"])
    assert not args.smoke
    cfg = get_config(args.arch, smoke=args.smoke)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (36, 2048, 151936)
    assert parser().parse_args(["--smoke"]).smoke


def test_serve_tenants_completes_every_request():
    """The --tenants path: two smoke tenants resolved by the planner, then
    served through the admission scheduler to the last request."""
    from repro.launch.serve import parser, serve_tenants

    args = parser().parse_args(["--smoke", "--tenants",
                                "qwen2.5-3b:2:1,qwen2.5-3b:1",
                                "--requests", "2", "--max-new", "4"])
    done = serve_tenants(args)
    assert sorted(done) == ["qwen2.5-3b#0", "qwen2.5-3b#1"]
    for reqs in done.values():
        assert len(reqs) == 2
        assert all(r.done and len(r.output) == 4 for r in reqs)
