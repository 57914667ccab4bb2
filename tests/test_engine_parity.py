"""Engine parity: the jax pricing/simulation engines vs their numpy twins.

The engine-split idiom (docs/engines.md) keeps a numpy reference
implementation for every jax-accelerated path; this suite pins the two
sides together:

  1. segment pricing — ``_plan_segment(engine="jax")`` vs the host batch
     engine across 4 topologies x 4 spatial organizations x depths
     {1, 2, 4, 8}, plus branch-parallel (co-placed region) segments:
     latency within 1e-6 relative, DRAM bytes / congestion verdicts /
     burst counts bit-identical (they ride the host passthrough path),
  2. whole-plan identity — ``plan_pipeorgan(engine="jax")`` must select
     the exact plan the numpy engine selects on every XR-bench task, and
     both must match the committed golden snapshot (unregenerated),
  3. the max-plus simulator engine — ``simulate_segment(engine="jax")``
     (kernels/maxplus_scan.py) vs numpy and vs the scalar reference,
     including the Pallas kernel in interpret mode on CPU,
  4. the float64 guard — segments beyond 2^24 cycles, where a float32
     scan would quantize away unit-scale increments,
  5. a hypothesis property: both engines select the same plan under
     ``latency_first()`` and ``min_dram()`` objectives on random chains.

Everything here skips cleanly when jax is not importable (engine="numpy"
installs stay green).
"""
import json
import math
from pathlib import Path

import numpy as np
import pytest

from repro.configs.xrbench import all_tasks
from repro.core import (DEFAULT_MAX_BURSTS, PAPER_HW, PlanRequest, Planner,
                        Topology, latency_first, min_dram, plan_pipeorgan,
                        simulate_reference, simulate_segment)
from repro.core.depth import Segment
from repro.core.graph import Graph, add, branch_regions, chain, conv
from repro.core.hwconfig import HWConfig
from repro.core.plan_api import jax_engine_available
from repro.core.planner import (_pipeorgan_df_fn, _plan_branch_segment,
                                _plan_segment)
from repro.core.spatial import SpatialOrg

jax_ok = pytest.mark.skipif(not jax_engine_available(),
                            reason="jax pricing engine unavailable")

ALL_TOPOLOGIES = list(Topology)
ALL_ORGS = list(SpatialOrg)
DEPTHS = (1, 2, 4, 8)

#: small substrate keeps the sweep fast without losing any code path
SIM_HW = HWConfig(name="parity", pe_rows=8, pe_cols=8,
                  sram_bytes=1 << 16, rf_bytes_per_pe=256,
                  dram_bw_bytes_per_cycle=4096.0)

LAT_RTOL = 1e-6


def _chain(depth: int) -> Graph:
    return chain(f"parity-d{depth}",
                 [conv(f"c{i}", 1, 16, 16, 8, 8, r=3)
                  for i in range(depth)])


def _resnet_block(h=16, c=8) -> Graph:
    ops = [conv("stem", 1, h, h, c, c, r=3),
           conv("c1", 1, h, h, c, c, r=3, inputs=("stem",)),
           conv("c2", 1, h, h, c, c, r=3, inputs=("c1",)),
           conv("proj", 1, h, h, c, c, r=1, inputs=("stem",)),
           add("join", 1, h, h, c, inputs=("c2", "proj"))]
    return Graph("branchy", ops)


def _assert_cost_parity(cn, cj):
    """Numpy-priced vs jax-priced SegmentCost for the same prep."""
    assert cj.latency_cycles == pytest.approx(cn.latency_cycles,
                                              rel=LAT_RTOL)
    # host passthrough fields are bit-identical by construction — any
    # drift means the jax engine rebuilt something it should not have
    assert cj.dram_bytes == cn.dram_bytes
    assert cj.sram_bytes == cn.sram_bytes
    assert cj.congested == cn.congested
    assert cj.intervals == cn.intervals       # integer burst counts
    assert cj.noc_hop_energy == pytest.approx(cn.noc_hop_energy,
                                              rel=LAT_RTOL)


# ---------------------------------------------------------------------------
# 1. segment pricing parity: topology x org x depth, then branches
# ---------------------------------------------------------------------------


@jax_ok
@pytest.mark.parametrize("topology", ALL_TOPOLOGIES)
@pytest.mark.parametrize("org", ALL_ORGS)
@pytest.mark.parametrize("depth", DEPTHS)
def test_segment_pricing_parity(topology, org, depth):
    g = _chain(depth)
    seg = Segment(0, depth)
    pn = _plan_segment(g, seg, SIM_HW, topology, _pipeorgan_df_fn,
                       org, False, engine="batch")
    pj = _plan_segment(g, seg, SIM_HW, topology, _pipeorgan_df_fn,
                       org, False, engine="jax")
    assert pj.org == pn.org and pj.segment == pn.segment
    _assert_cost_parity(pn.cost, pj.cost)


@jax_ok
@pytest.mark.parametrize("topology", ALL_TOPOLOGIES)
@pytest.mark.parametrize("staged", [False, True])
def test_branch_segment_pricing_parity(topology, staged):
    g = _resnet_block()
    region = [r for r in branch_regions(g) if len(r.branches) >= 2][0]
    pn = _plan_branch_segment(g, region, SIM_HW, topology,
                              _pipeorgan_df_fn, force_gb=staged,
                              engine="batch")
    pj = _plan_branch_segment(g, region, SIM_HW, topology,
                              _pipeorgan_df_fn, force_gb=staged,
                              engine="jax")
    assert (pn is None) == (pj is None)
    if pn is None:
        return
    assert pj.edges == pn.edges and pj.branches == pn.branches
    _assert_cost_parity(pn.cost, pj.cost)


# ---------------------------------------------------------------------------
# 2. whole-plan identity on XR-bench, pinned to the committed golden
# ---------------------------------------------------------------------------


def _plan_key(plan):
    return [(s.segment.start, s.segment.stop,
             s.org.value if s.org is not None else None,
             bool(s.placement.via_global_buffer)
             if s.placement is not None else None,
             s.branches, s.edges)
            for s in plan.segments]


@jax_ok
@pytest.mark.parametrize("task", sorted(all_tasks()))
def test_xrbench_plan_identity(task):
    g = all_tasks()[task]
    pn = plan_pipeorgan(g, PAPER_HW, Topology.AMP, engine="numpy")
    pj = plan_pipeorgan(g, PAPER_HW, Topology.AMP, engine="jax")
    assert _plan_key(pj) == _plan_key(pn)
    assert pj.latency_cycles == pytest.approx(pn.latency_cycles,
                                              rel=LAT_RTOL)
    assert pj.dram_bytes == pn.dram_bytes
    # ... and both sit on the committed golden snapshot, unregenerated
    golden = json.loads((Path(__file__).parent / "golden"
                         / "xrbench_plans.json").read_text())[task]
    got = [(s["start"], s["stop"], s["org"], s["via_global_buffer"])
           for s in golden["segments"]]
    assert [(k[0], k[1], k[2], k[3]) for k in _plan_key(pj)] == got
    assert pj.latency_cycles == pytest.approx(golden["latency_cycles"],
                                              rel=LAT_RTOL)


# ---------------------------------------------------------------------------
# 3. max-plus simulator engine (incl. the Pallas kernel, interpret mode)
# ---------------------------------------------------------------------------


@jax_ok
@pytest.mark.parametrize("topology", [Topology.MESH, Topology.AMP])
@pytest.mark.parametrize("depth", (2, 4, 8))
def test_simulator_engine_parity(topology, depth):
    g = _chain(depth)
    plan = _plan_segment(g, Segment(0, depth), SIM_HW, topology,
                         _pipeorgan_df_fn, SpatialOrg.FINE_STRIPED_1D,
                         False)
    sn = simulate_segment(plan, SIM_HW, topology,
                          max_bursts=DEFAULT_MAX_BURSTS, engine="numpy")
    sj = simulate_segment(plan, SIM_HW, topology,
                          max_bursts=DEFAULT_MAX_BURSTS, engine="jax")
    sr = simulate_reference(plan, SIM_HW, topology,
                            max_bursts=DEFAULT_MAX_BURSTS)
    assert sj.latency_cycles == pytest.approx(sn.latency_cycles,
                                              rel=LAT_RTOL)
    assert sj.latency_cycles == pytest.approx(sr.latency_cycles,
                                              rel=LAT_RTOL)
    assert sj.link_loads == sn.link_loads     # bit-level: same host path
    assert sj.congested == sn.congested == sr.congested


@jax_ok
def test_pallas_maxplus_vs_simulate_reference(monkeypatch):
    """Force the Pallas kernel (interpret mode on CPU) under the jax
    simulator engine and pin it to the scalar reference event loop."""
    monkeypatch.setenv("REPRO_MAXPLUS_ENGINE", "pallas")
    g = _chain(4)
    plan = _plan_segment(g, Segment(0, 4), SIM_HW, Topology.AMP,
                         _pipeorgan_df_fn, SpatialOrg.CHECKERBOARD_2D,
                         False)
    sj = simulate_segment(plan, SIM_HW, Topology.AMP,
                          max_bursts=DEFAULT_MAX_BURSTS, engine="jax")
    sr = simulate_reference(plan, SIM_HW, Topology.AMP,
                            max_bursts=DEFAULT_MAX_BURSTS)
    assert sj.latency_cycles == pytest.approx(sr.latency_cycles,
                                              rel=LAT_RTOL)
    assert sj.congested == sr.congested


@jax_ok
def test_pallas_kernel_parity_direct():
    from repro.kernels.maxplus_scan import (maxplus_scan,
                                            maxplus_scan_reference)
    rng = np.random.default_rng(0)
    for T in (1, 7, 256, 1000):
        u = rng.uniform(0.0, 50.0, T).cumsum()
        s = rng.uniform(0.0, 3.0, T)
        ref = maxplus_scan_reference(u, s)
        got = np.asarray(maxplus_scan(u, s, engine="pallas",
                                      interpret=True))
        np.testing.assert_allclose(got, ref, rtol=1e-12)


# ---------------------------------------------------------------------------
# 4. float64 guard: >2^24-cycle segments
# ---------------------------------------------------------------------------


@jax_ok
def test_engine_import_enables_float64():
    import jax.numpy as jnp

    from repro.core import pipeline_model_jax
    assert pipeline_model_jax.is_available()
    # the import-time ensure_x64() guard: 2^53 + 1 must be representable,
    # which rules out both float32 and silently-disabled x64
    assert jnp.asarray(1.0).dtype == jnp.float64
    assert float(jnp.asarray(float(2**53 + 1))) == float(2**53 + 1)


@jax_ok
def test_maxplus_beyond_2pow24_cycles():
    """A scan whose running time passes 2^24 keeps unit-scale increments:
    float32 (eps ~ 6e-8) would quantize s_t=1.5 steps away entirely."""
    from repro.kernels.maxplus_scan import (maxplus_scan,
                                            maxplus_scan_reference)
    T = 4096
    u = np.full(T, -math.inf)
    u[0] = float(2 ** 26)                    # start beyond 2^24 already
    s = np.full(T, 1.5)
    ref = maxplus_scan_reference(u, s)
    assert ref[-1] > 2 ** 26 + 6000          # genuinely super-2^24 regime
    for engine in ("xla", "pallas", "numpy"):
        got = np.asarray(maxplus_scan(u, s, engine=engine, interpret=True))
        np.testing.assert_array_equal(got, ref, err_msg=engine)


@jax_ok
def test_simulator_beyond_2pow24_cycles():
    """Whole-segment regression: a DRAM-starved deep segment whose
    simulated latency exceeds 2^24 cycles must still match the scalar
    reference to 1e-9 — only possible with the float64 guard active."""
    hw = HWConfig(name="starved", pe_rows=4, pe_cols=4,
                  sram_bytes=1 << 14, rf_bytes_per_pe=128,
                  dram_bw_bytes_per_cycle=0.125)
    g = chain("big", [conv(f"c{i}", 1, 64, 64, 32, 32, r=3)
                      for i in range(4)])
    plan = _plan_segment(g, Segment(0, 4), hw, Topology.MESH,
                         _pipeorgan_df_fn, SpatialOrg.BLOCKED_1D, False)
    sr = simulate_reference(plan, hw, Topology.MESH,
                            max_bursts=DEFAULT_MAX_BURSTS)
    assert sr.latency_cycles > 2 ** 24
    sj = simulate_segment(plan, hw, Topology.MESH,
                          max_bursts=DEFAULT_MAX_BURSTS, engine="jax")
    assert sj.latency_cycles == pytest.approx(sr.latency_cycles, rel=1e-9)


# ---------------------------------------------------------------------------
# 5. hypothesis property: same plan selected under both objectives
# ---------------------------------------------------------------------------

@jax_ok
def test_engines_select_same_plan():
    """Property: for random conv chains and either objective, both
    engines drive the DP to the exact same plan (skips cleanly on
    minimal installs without hypothesis)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @st.composite
    def _chains(draw):
        depth = draw(st.integers(min_value=2, max_value=6))
        hw = draw(st.sampled_from([8, 16]))
        c = draw(st.sampled_from([4, 8]))
        r = draw(st.sampled_from([1, 3]))
        return chain(f"hyp-d{depth}-h{hw}-c{c}-r{r}",
                     [conv(f"c{i}", 1, hw, hw, c, c, r=r)
                      for i in range(depth)])

    @settings(max_examples=10, deadline=None)
    @given(g=_chains(), objective=st.sampled_from(["latency", "dram"]))
    def prop(g, objective):
        obj = latency_first() if objective == "latency" else min_dram()
        planner = Planner(maxsize=8)
        plans = {}
        for engine in ("numpy", "jax"):
            req = PlanRequest(g, hw=SIM_HW, topology=Topology.AMP,
                              objective=obj, engine=engine)
            plans[engine] = planner.plan(req)
        pn, pj = plans["numpy"], plans["jax"]
        assert _plan_key(pj) == _plan_key(pn)
        assert pj.latency_cycles == pytest.approx(pn.latency_cycles,
                                                  rel=LAT_RTOL)
        assert pj.dram_bytes == pn.dram_bytes

    prop()


def test_maxplus_engine_validated_before_empty_early_return():
    """An invalid engine name must raise even when T == 0 — the empty
    early return used to bypass engine resolution entirely."""
    from repro.kernels.maxplus_scan import maxplus_scan

    with pytest.raises(ValueError, match="unknown maxplus engine"):
        maxplus_scan(np.zeros((2, 0)), np.zeros((2, 0)), engine="bogus")
    with pytest.raises(ValueError, match="unknown maxplus engine"):
        maxplus_scan(np.zeros(0), np.zeros(0), engine="bogus")
    # valid engines still take the early return with the right shape
    out = maxplus_scan(np.zeros((3, 0)), np.zeros((3, 0)), engine="numpy")
    assert out.shape == (3, 0)
    assert maxplus_scan(np.zeros(0), np.zeros(0), engine="numpy").shape \
        == (0,)


def test_tpu_backend_auto_engines_stay_numpy(monkeypatch):
    """On a TPU backend "auto" prices and simulates with numpy and never
    probes the jax pricer, whose float64 guard would turn on
    jax_enable_x64 in the process serving the model."""
    import jax

    from repro.core import plan_api, planner
    from repro.kernels.maxplus_scan import _resolve_engine

    def probe():
        raise AssertionError("jax pricer probed on a TPU backend")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(plan_api, "jax_engine_available", probe)
    monkeypatch.delenv("REPRO_MAXPLUS_ENGINE", raising=False)
    assert PlanRequest(_chain(2), hw=SIM_HW).engine == "numpy"
    assert planner.resolve_engine("auto") == "batch"
    assert _resolve_engine("auto") == "numpy"


@jax_ok
def test_explicit_pallas_maxplus_on_tpu_compiles_not_interprets(monkeypatch):
    """An explicit engine="pallas" on a TPU backend hands the kernel to
    the chip's compiler (which refuses it); it never drops to interpret
    mode."""
    import importlib

    import jax

    mp = importlib.import_module("repro.kernels.maxplus_scan")
    seen = []

    def fake_chunked(u, s, h0, *, chunk, interpret):
        seen.append(interpret)
        raise ValueError("refused by the TPU compiler")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mp, "maxplus_chunked", fake_chunked)
    with pytest.raises(ValueError, match="refused"):
        mp.maxplus_scan(np.zeros((2, 8)), np.ones((2, 8)), engine="pallas")
    assert seen == [False]
