"""Tentpole tests: vectorized NoC engine equivalence, cut-point DP
dominance over the uniform-depth enumeration, and the Planner facade."""
import dataclasses

import numpy as np
import pytest

from repro.configs.xrbench import all_tasks
from repro.core import (PAPER_HW, CacheInfo, FlowBatch, PlanRequest, Planner,
                        Topology, analyze, analyze_reference, get_planner,
                        graph_fingerprint, multicast_flow_batch,
                        pair_flow_batch, plan_pipeorgan,
                        plan_pipeorgan_reference, plan_pipeorgan_uniform)
from repro.core.graph import chain, conv
from repro.core.noc import Flow, multicast_flows, pair_flows
from repro.core.spatial import SpatialOrg, place

HW = PAPER_HW
ALL_TOPOLOGIES = list(Topology)


def _random_flows(rng, n, same_words=False):
    src = rng.integers(0, 32, (n, 2))
    dst = rng.integers(0, 32, (n, 2))
    words = (np.full(n, 3.25) if same_words
             else rng.uniform(0.0, 5.0, n))
    if not same_words:
        words[rng.random(n) < 0.1] = 0.0        # dropped by both engines
    self_mask = rng.random(n) < 0.05            # src == dst corner case
    dst[self_mask] = src[self_mask]
    return [Flow((int(a), int(b)), (int(c), int(d)), float(w))
            for (a, b), (c, d), w in zip(src, dst, words)]


def _assert_stats_equal(a, b):
    # per-link loads accumulate in the identical (flow, hop) order in both
    # engines, so the order-sensitive fields must agree exactly
    assert a.worst_channel_load == b.worst_channel_load
    assert a.max_path_hops == b.max_path_hops
    assert a.num_links_used == b.num_links_used
    assert a.link_count == b.link_count
    # totals are reduced in a different association order -> tolerance
    np.testing.assert_allclose(a.total_hop_words, b.total_hop_words,
                               rtol=1e-12)
    np.testing.assert_allclose(a.total_wire_words, b.total_wire_words,
                               rtol=1e-12)


# ---------------------------------------------------------------------------
# vectorized analyze == scalar reference walk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topology", ALL_TOPOLOGIES)
def test_analyze_matches_reference_on_random_flows(topology):
    rng = np.random.default_rng(hash(topology.value) % (2 ** 32))
    for n in (0, 1, 7, 500, 3000):
        for same_words in (False, True):
            flows = _random_flows(rng, n, same_words)
            _assert_stats_equal(analyze(flows, HW, topology),
                                analyze_reference(flows, HW, topology))


@pytest.mark.parametrize("topology", ALL_TOPOLOGIES)
def test_analyze_matches_reference_on_placement_traffic(topology):
    """Real planner traffic: multicast chains and nearest-pair unicasts."""
    for org, list_fn, batch_fn in [
            (SpatialOrg.BLOCKED_1D, multicast_flows, multicast_flow_batch),
            (SpatialOrg.FINE_STRIPED_1D, pair_flows, pair_flow_batch),
            (SpatialOrg.BLOCKED_2D, multicast_flows, multicast_flow_batch),
            (SpatialOrg.CHECKERBOARD_2D, pair_flows, pair_flow_batch)]:
        for alloc in ([1.0, 1.0], [3.0, 1.0], [1.0, 2.0, 1.0, 4.0]):
            pl = place(org, alloc, HW)
            flows = list_fn(pl, 0, 1, 512.0)
            _assert_stats_equal(analyze(flows, HW, topology),
                                analyze_reference(flows, HW, topology))


def test_flow_batches_match_list_generators():
    """Batch generators emit the same flows in the same order (the order
    feeds the reference engine's port arbitration, so it must match)."""
    for org, list_fn, batch_fn in [
            (SpatialOrg.BLOCKED_1D, multicast_flows, multicast_flow_batch),
            (SpatialOrg.BLOCKED_2D, multicast_flows, multicast_flow_batch),
            (SpatialOrg.FINE_STRIPED_1D, pair_flows, pair_flow_batch),
            (SpatialOrg.CHECKERBOARD_2D, pair_flows, pair_flow_batch)]:
        for alloc in ([1.0, 1.0], [3.0, 1.0], [1.0, 2.0, 1.0, 4.0]):
            pl = place(org, alloc, HW)
            for i, j in ((0, 1), (1, 0)):
                listed = list_fn(pl, i, j, 257.0)
                batch = batch_fn(pl, i, j, 257.0)
                assert batch.to_flows() == listed


def test_flow_batch_roundtrip_and_concat():
    rng = np.random.default_rng(0)
    flows = _random_flows(rng, 100)
    fb = FlowBatch.from_flows(flows)
    assert fb.to_flows() == flows
    both = FlowBatch.concat([fb, FlowBatch.empty(), fb])
    assert len(both) == 200
    assert both.to_flows() == flows + flows


# ---------------------------------------------------------------------------
# cut-point DP: never worse than the uniform-depth enumeration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("task", sorted(all_tasks()))
def test_dp_never_worse_than_uniform_enumeration(task):
    g = all_tasks()[task]
    dp = plan_pipeorgan(g, HW, Topology.AMP)
    uni = plan_pipeorgan_uniform(g, HW, Topology.AMP)
    assert dp.latency_cycles <= uni.latency_cycles * (1 + 1e-9)
    assert dp.dram_bytes <= uni.dram_bytes * (1 + 1e-9)
    # both cover every op exactly once
    for plan in (dp, uni):
        assert sum(s.segment.depth for s in plan.segments) == len(g.ops)


def test_dp_finds_strictly_better_plans_somewhere():
    """The whole point of the DP: mixed-depth segmentations must win on at
    least some workloads (else the refactor would be a no-op)."""
    improved = 0
    for task, g in all_tasks().items():
        dp = plan_pipeorgan(g, HW, Topology.AMP)
        uni = plan_pipeorgan_uniform(g, HW, Topology.AMP)
        if (dp.latency_cycles < uni.latency_cycles * (1 - 1e-9)
                or dp.dram_bytes < uni.dram_bytes * (1 - 1e-9)):
            improved += 1
    assert improved >= 1


def test_uniform_enumeration_matches_scalar_reference():
    """Same algorithm on the two NoC engines -> same plans (numerically)."""
    g = all_tasks()["gaze_estimation"]
    uni = plan_pipeorgan_uniform(g, HW, Topology.AMP)
    ref = plan_pipeorgan_reference(g, HW, Topology.AMP)
    np.testing.assert_allclose(uni.latency_cycles, ref.latency_cycles,
                               rtol=1e-9)
    np.testing.assert_allclose(uni.dram_bytes, ref.dram_bytes, rtol=1e-9)
    assert [s.segment.depth for s in uni.segments] == \
        [s.segment.depth for s in ref.segments]


def test_dp_plans_reference_correct_ops():
    """Content-cached span plans must be re-bound to this span's ops."""
    g = all_tasks()["eye_segmentation"]
    plan = plan_pipeorgan(g, HW, Topology.AMP)
    for s in plan.segments:
        expect = g.ops[s.segment.start:s.segment.stop]
        assert [op.name for op in s.ops] == [op.name for op in expect]
        assert [df.op_name for df in s.dataflows] == \
            [op.name for op in expect]


# ---------------------------------------------------------------------------
# Planner facade
# ---------------------------------------------------------------------------

def _tiny_graph(name="tiny"):
    return chain(name, [conv(f"c{i}", 1, 32, 32, 8, 8, r=3)
                        for i in range(4)])


def test_planner_facade_caches_plans():
    planner = Planner(maxsize=8)
    g = _tiny_graph()
    first = planner.plan(PlanRequest(g, hw=HW, topology=Topology.AMP))
    second = planner.plan(PlanRequest(g, hw=HW, topology=Topology.AMP))
    assert second is first                      # cache hit returns same plan
    info = planner.cache_info()
    assert info == CacheInfo(hits=1, misses=1, maxsize=8, currsize=1)
    # a different topology / strategy is a different key
    planner.plan(PlanRequest(g, hw=HW, topology=Topology.MESH))
    planner.plan(PlanRequest(g, hw=HW, strategy="tangram"))
    planner.plan(PlanRequest(g, hw=HW, strategy="layerbylayer"))
    assert planner.cache_info().misses == 4
    planner.clear_cache()
    assert planner.cache_info() == CacheInfo(0, 0, 8, 0)


def test_planner_facade_evicts_lru():
    planner = Planner(maxsize=2)
    for i in range(3):
        planner.plan(PlanRequest(_tiny_graph(f"g{i}"), hw=HW,
                                 topology=Topology.AMP))
    assert planner.cache_info().currsize == 2
    planner.plan(PlanRequest(_tiny_graph("g0"), hw=HW,
                             topology=Topology.AMP))    # evicted -> miss
    assert planner.cache_info().misses == 4


def test_planner_facade_rejects_unknown_strategy():
    with pytest.raises(ValueError):
        PlanRequest(_tiny_graph(), hw=HW, strategy="nope")


def test_graph_fingerprint_tracks_structure():
    a, b = _tiny_graph(), _tiny_graph()
    assert graph_fingerprint(a) == graph_fingerprint(b)
    c = _tiny_graph()
    c.ops[1] = dataclasses.replace(c.ops[1], dims=dict(c.ops[1].dims, K=16))
    assert graph_fingerprint(a) != graph_fingerprint(c)


def test_get_planner_is_shared():
    assert get_planner() is get_planner()
