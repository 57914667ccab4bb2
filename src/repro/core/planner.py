"""End-to-end PipeOrgan planner (Fig. 7 flow) + baseline dataflows.

Stage 1 (HW-agnostic): segment the DAG by the depth heuristic, choose
intra-op dataflows from A/W ratios, derive the finest granularity (Alg. 1).

Stage 2 (HW mapping): allocate PEs per layer by MAC ratio, choose the
spatial organization from (depth, granularity, RF sizes), generate the
segment's NoC traffic (incl. skip connections and unequal allocations) and
evaluate latency/energy/DRAM via the Fig. 3 model on a chosen topology.

``plan_pipeorgan`` solves each stage-1 heuristic segment with a memoized
dynamic program over cut points — ``best(i) = min over j of cost(i, j) +
best(j)`` with a Pareto frontier over the (latency, DRAM) objective — so
it finds mixed-depth sub-segmentations (e.g. depth-3 followed by depth-2)
that the original uniform-depth enumeration cannot express.  The uniform
enumeration is kept as ``plan_pipeorgan_uniform`` (same vectorized NoC
engine) and ``plan_pipeorgan_reference`` (pre-refactor scalar engine) for
equivalence testing and benchmarking; the DP's selection is guarded to
never be worse than the uniform choice on either objective axis.

Baselines (Sec. V-C):
  * TANGRAM-like — fine-grained pipelining at fixed depth=2, alternating
    output-/input-stationary dataflows, blocked spatial allocation.
  * SIMBA-like   — parallelize C and K; pipeline (depth 2, blocked) only
    when C*K cannot utilize the substrate; otherwise layer-by-layer.
"""
from __future__ import annotations

import bisect
import collections
import collections.abc
import dataclasses
import functools
import math
import sys
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from .dataflow import Dataflow, choose_dataflow
from .depth import Segment, segment_graph
from .plan_api import (Constraint, DEFAULT_OBJECTIVE, Objective,
                       auto_engine, content_token, register_cache,
                       register_strategy, unregister_cache)
from .graph import (BranchRegion, COMPLEX_KINDS, Graph, Op, OpKind,
                    branch_regions, periodic_regions)
from .granularity import Granularity, finest_granularity
from .hwconfig import HWConfig
from .noc import (FlowBatch, LRUCache, Topology, TrafficStats,
                  analyze_batch, analyze_reference, cached_flow_batch,
                  join_flow_batch, multicast_flows, pair_flows,
                  route_incidence_cache_info)
from .pipeline_model import (SegmentCost, chain_edges, edge_burst_count,
                             op_work, segment_cost)
from .spatial import (Placement, SpatialOrg, allocate_pes, choose_spatial_org,
                      place, place_branches)

#: longest sub-segment span the cut-point DP evaluates exhaustively.  Spans
#: beyond it (one 32-deep segment) are still considered through the
#: uniform-depth candidates {1, 2, 4, 8, depth}, which the final selection
#: always includes; raising this widens the mixed-depth search at
#: quadratic planning cost.  Raised 6 -> 8 once the cross-segment
#: flow-batch cache amortized cut-point evaluation (PR 3): depth-8
#: sub-segments — the deepest uniform candidate — are now searched
#: exhaustively in mixed-depth combinations too.
DP_MAX_SPAN = 8


@dataclasses.dataclass
class SegmentPlan:
    segment: Segment
    ops: List[Op]
    dataflows: List[Dataflow]
    granularities: List[Granularity]
    pe_alloc: List[int]
    org: Optional[SpatialOrg]
    placement: Optional[Placement]
    noc: Optional[TrafficStats]
    cost: SegmentCost
    # replay metadata: everything the event-driven simulator needs to
    # re-execute this plan without the original Graph (slot-relative skip
    # edges in elements, boundary-crossing skip bytes, the baseline's
    # per-interval traffic multiplier, and the usable substrate size).
    intra_skips: Tuple[Tuple[int, int, int], ...] = ()
    skip_in_bytes: float = 0.0
    traffic_scale: float = 1.0
    array_pes: Optional[int] = None
    # branch-parallel segments: the explicit pipeline slot DAG (slot u
    # streams into slot v) and the slot-relative branch groups.  ``()``
    # means the implicit linear chain, everywhere.
    edges: Tuple[Tuple[int, int], ...] = ()
    branches: Tuple[Tuple[int, ...], ...] = ()

    @property
    def pipeline_edges(self) -> Tuple[Tuple[int, int], ...]:
        """The slot DAG this plan executes (explicit or implicit chain)."""
        return self.edges or chain_edges(len(self.ops))


@dataclasses.dataclass
class PlanResult:
    graph_name: str
    strategy: str
    topology: Topology
    segments: List[SegmentPlan]

    @property
    def latency_cycles(self) -> float:
        return sum(s.cost.latency_cycles for s in self.segments)

    @property
    def dram_bytes(self) -> float:
        return sum(s.cost.dram_bytes for s in self.segments)

    @property
    def energy(self) -> float:
        return sum(s.cost.total_energy for s in self.segments)

    @property
    def compute_lower_bound(self) -> float:
        return sum(s.cost.compute_cycles for s in self.segments)

    def metrics(self) -> Dict[str, float]:
        """The objective-facing totals (``plan_api.METRICS``)."""
        return {"latency_cycles": self.latency_cycles,
                "dram_bytes": self.dram_bytes, "energy": self.energy}

    def depth_labels(self) -> List[int]:
        labels: List[int] = []
        for s in self.segments:
            labels.extend([s.segment.depth] * s.segment.depth)
        return labels


# ---------------------------------------------------------------------------


#: identity-keyed span memos.  Graphs are unhashable (ops carry dims
#: dicts) but long-lived, and the cut-point DP revisits every span several
#: times per org/staging variant; values hold a strong ref to the graph so
#: id() cannot be recycled while the entry lives.
_SKIP_TRAFFIC_CACHE: Dict[Tuple[int, int, int], Tuple[Graph, Tuple]] = {}
_SPAN_SIG_CACHE: Dict[Tuple[int, int, int], Tuple[Graph, Tuple]] = {}
_SPAN_MEMO_MAX = 16384


def _segment_skip_traffic(g: Graph, seg: Segment
                          ) -> Tuple[List[Tuple[int, int, int]], float]:
    """(intra-segment skip slot pairs with volume), crossing bytes."""
    key = (id(g), seg.start, seg.stop)
    hit = _SKIP_TRAFFIC_CACHE.get(key)
    if hit is not None and hit[0] is g:
        return hit[1]
    intra: List[Tuple[int, int, int]] = []
    crossing = 0
    for p, c in g.skip_edges():
        vol = g.ops[p].output_volume()
        if p in seg and c in seg:
            intra.append((p - seg.start, c - seg.start, vol))
        elif (p in seg) != (c in seg):
            crossing += vol
    if len(_SKIP_TRAFFIC_CACHE) >= _SPAN_MEMO_MAX:
        _SKIP_TRAFFIC_CACHE.clear()
    _SKIP_TRAFFIC_CACHE[key] = (g, (intra, crossing))
    return intra, crossing


@functools.lru_cache(maxsize=1024)
def _cached_place(org: SpatialOrg, pe_alloc: Tuple[int, ...],
                  hw: HWConfig) -> Placement:
    return place(org, [float(p) for p in pe_alloc], hw)


_PAIR_TRAFFIC_CACHE = LRUCache(maxsize=65536)

#: one pair sweep request: (j, words, skips) — see ``_pair_traffic``
_PairReq = Tuple[int, float, Tuple[Tuple[int, int, float], ...]]


def _pair_traffic_sweep(org: SpatialOrg, pe_alloc: Tuple[int, ...],
                        hw: HWConfig, topology: Topology, fine: bool,
                        reqs: Sequence[_PairReq]) -> List[TrafficStats]:
    """A whole sweep of pipeline-pair traffic stats, cached per pair.

    The flows are a pure function of the key (the placement grid is itself
    a pure function of (org, pe_alloc)), and the DP re-encounters the same
    signatures constantly — overlapping spans of repeated same-shape
    layers, re-planned topologies — so the cache collapses the planner's
    dominant cost.  Every missing pair of the sweep is priced in ONE
    ``analyze_batch`` call over the shared route-incidence tables instead
    of one ``analyze`` per pair per candidate (the PR 8 tentpole).
    """
    keys = [(org, pe_alloc, j, words, skips, hw, topology, fine)
            for j, words, skips in reqs]
    stats: List[Optional[TrafficStats]] = [
        _PAIR_TRAFFIC_CACHE.get(k) for k in keys]
    missing = [i for i, st in enumerate(stats) if st is None]
    if missing:
        placement = _cached_place(org, pe_alloc, hw)
        fbs = []
        tokens = []
        for i in missing:
            j, words, skips = reqs[i]
            parts = [cached_flow_batch(placement, j, j + 1, words, fine)]
            for s, t, w in skips:
                parts.append(cached_flow_batch(placement, s, t, w, fine))
            fbs.append(FlowBatch.concat(parts))
            # the coordinate set is a pure function of this tuple, so it
            # serves as a route_incidence cache token: the incidence
            # lookup skips hashing the (src, dst) arrays — the dominant
            # per-pair cost once the tables are warm
            tokens.append((org, pe_alloc, hw, fine, j,
                           tuple((s, t) for s, t, _ in skips)))
        for i, st in zip(missing,
                         analyze_batch(fbs, hw, topology, tokens=tokens)):
            _PAIR_TRAFFIC_CACHE.put(keys[i], st)
            stats[i] = st
    return stats  # type: ignore[return-value]


def _pair_traffic(org: SpatialOrg, pe_alloc: Tuple[int, ...], j: int,
                  words: float, skips: Tuple[Tuple[int, int, float], ...],
                  hw: HWConfig, topology: Topology, fine: bool
                  ) -> TrafficStats:
    """One pipeline pair's traffic stats (single-key ``_pair_traffic_sweep``)."""
    return _pair_traffic_sweep(org, pe_alloc, hw, topology, fine,
                               [(j, words, skips)])[0]


# the benchmark harness and the cache registry address this cache through
# the functools-style accessors the old lru_cache decorator provided
_pair_traffic.cache_info = _PAIR_TRAFFIC_CACHE.info        # type: ignore[attr-defined]
_pair_traffic.cache_clear = _PAIR_TRAFFIC_CACHE.clear      # type: ignore[attr-defined]


@dataclasses.dataclass
class _SegPrep:
    """Host-side half of ``_plan_segment``: everything up to pricing.

    Splitting prep from pricing lets the jax engine materialize MANY
    spans' prep as struct-of-arrays rows and price them in one jitted
    vmap call (``_segment_planner(...).prime``) instead of once per
    ``segment_cost`` invocation."""
    seg: Segment
    ops: List[Op]
    dfs: List[Dataflow]
    grans: List[Granularity]
    pe_alloc: List[int]
    org: Optional[SpatialOrg]
    placement: Optional[Placement]
    worst: Optional[TrafficStats]
    stats: Optional[List[Optional[TrafficStats]]]
    via_gb: bool
    ext_in: float
    ext_out: float
    skip_in: float
    usable: int
    intra_skips: List[Tuple[int, int, int]]
    traffic_scale: float
    # branch-parallel candidates carry their explicit slot DAG
    edges: Tuple[Tuple[int, int], ...] = ()
    branches: Tuple[Tuple[int, ...], ...] = ()


def _finish_segment(prep: _SegPrep, cost: SegmentCost) -> SegmentPlan:
    return SegmentPlan(prep.seg, list(prep.ops), prep.dfs, prep.grans,
                       prep.pe_alloc, prep.org, prep.placement, prep.worst,
                       cost, intra_skips=tuple(prep.intra_skips),
                       skip_in_bytes=prep.skip_in,
                       traffic_scale=prep.traffic_scale,
                       array_pes=prep.usable, edges=prep.edges,
                       branches=prep.branches)


# --- the jax pricing engine is imported lazily: "numpy" planning must not
# pay (or require) the jax import --------------------------------------------


def _jax_model():
    from . import pipeline_model_jax
    pipeline_model_jax.require()
    return pipeline_model_jax


def resolve_engine(engine: str) -> str:
    """Public engine names -> internal engine ids.

    ``"numpy"`` is the vectorized host engine (internal id ``"batch"``,
    the historical default); ``"jax"`` requires the jax pricer and raises
    a clear error when it cannot run; ``"auto"`` follows
    ``plan_api.auto_engine()``.
    The internal ids ``"batch"``/``"reference"`` pass through for the
    benchmark harness.
    """
    if engine in ("batch", "reference"):
        return engine
    if engine == "numpy":
        return "batch"
    if engine == "jax":
        _jax_model()                # raises with the unavailability reason
        return "jax"
    if engine == "auto":
        return "jax" if auto_engine() == "jax" else "batch"
    raise ValueError(f"unknown engine {engine!r}; "
                     "one of ('auto', 'numpy', 'jax')")


def _price_row(prep: _SegPrep, hw: HWConfig):
    m = _jax_model()
    return m.build_row(prep.ops, prep.dfs, prep.grans, prep.pe_alloc, hw,
                       prep.stats, prep.via_gb, prep.ext_in, prep.ext_out,
                       prep.skip_in, array_pes=prep.usable,
                       edges=prep.edges or None)


def _host_cost(prep: _SegPrep, hw: HWConfig) -> SegmentCost:
    return segment_cost(prep.ops, prep.dfs, prep.grans, prep.pe_alloc, hw,
                        prep.stats, prep.via_gb, prep.ext_in, prep.ext_out,
                        prep.skip_in, array_pes=prep.usable,
                        edges=prep.edges or None)


def _prep_segment(g: Graph, seg: Segment, hw: HWConfig, topology: Topology,
                  dataflow_fn, force_org: Optional[SpatialOrg],
                  force_gb: Optional[bool],
                  util_fn=None, traffic_scale: float = 1.0,
                  engine: str = "batch") -> _SegPrep:
    ops = g.ops[seg.start:seg.stop]
    budget = hw.sram_bytes // max(1, seg.depth)
    dfs = [dataflow_fn(op, hw, i, budget) for i, op in enumerate(ops)]
    grans = [finest_granularity(ops[j], dfs[j], ops[j + 1], dfs[j + 1])
             for j in range(len(ops) - 1)]

    # Fine-grained pipelining needs a producer->consumer stream: an op
    # whose every input predates the span has nothing to stream from, so
    # the span can only execute staged through the global buffer (the
    # serialized-branch case — e.g. a ResNet projection whose input is the
    # block's fork, or a decoder layer consuming a long-distance encoder
    # tap).  Branch-parallel segments lift exactly this restriction by
    # co-placing the region instead.
    disconnected = any(
        op.inputs and not any(
            seg.start <= g.index(s) < seg.start + p for s in op.inputs)
        for p, op in enumerate(ops) if p > 0)

    # substrate under-utilization (e.g. SIMBA-like can only spread C and K):
    # an op that cannot fill its partition runs on fewer effective PEs
    usable = hw.num_pes
    if util_fn is not None:
        usable = max(1, int(hw.num_pes
                            * min(util_fn(op, hw) for op in ops)))
    pe_alloc = allocate_pes([max(1.0, op_work(op, hw)) for op in ops],
                            usable)

    intra_skips, crossing = _segment_skip_traffic(g, seg)
    ext_in = ops[0].input_volume() * hw.bytes_per_word
    ext_out = ops[-1].output_volume() * hw.bytes_per_word
    skip_in = crossing * hw.bytes_per_word

    if seg.depth == 1:
        return _SegPrep(seg, ops, dfs, grans, pe_alloc, None, None, None,
                        None, True, ext_in, ext_out, skip_in, usable,
                        intra_skips, traffic_scale)

    # organization choice
    gran_bytes = max(gr.elements for gr in grans) * hw.bytes_per_word
    mean_pes = max(1, hw.num_pes // seg.depth)
    if force_org is not None:
        org = force_org
        via_gb = force_gb if force_gb is not None else False
    else:
        org, via_gb = choose_spatial_org(seg.depth, gran_bytes,
                                         mean_pes, hw)
    if any(not gr.pipelinable for gr in grans) or disconnected:
        via_gb = True  # fall back to staging through the global buffer

    if engine != "reference":
        placement = dataclasses.replace(
            _cached_place(org, tuple(pe_alloc), hw),
            via_global_buffer=via_gb)
    else:
        placement = place(org, [float(p) for p in pe_alloc], hw, via_gb)

    # Blocked organizations keep flexible intra-op dataflows, so a produced
    # word is needed by many consumer PEs -> multicast chains (Figs. 8-9).
    # Fine interleavings constrain the consumer to its neighbour's output
    # -> unicast (Fig. 10).
    fine = org in (SpatialOrg.FINE_STRIPED_1D, SpatialOrg.CHECKERBOARD_2D)
    flow_fn: Callable = pair_flows if fine else multicast_flows

    # Per-pair traffic analysis at burst granularity: every interval each
    # producer PE emits one word (lockstep), so pair j's burst volume is its
    # producer's PE count.  Skip connections whose span covers the boundary
    # ride the same links at the pair's burst rate (Figs. 9a / 11).
    n_bursts = [max(1, math.ceil(ops[j].output_volume()
                                 / max(1, pe_alloc[j])))
                for j in range(len(grans))]
    if via_gb and engine != "reference":
        # coarse pipelining stages through the global buffer: the Fig. 3
        # cost model never consults NoC stats for it, so skip the traffic
        # analysis outright (a large share of planner time on deep spans)
        per_pair_stats = None
        worst = None
    elif engine != "reference":
        per_pair_stats = _pair_traffic_sweep(
            org, tuple(pe_alloc), hw, topology, fine,
            [(j, float(pe_alloc[j]) * traffic_scale,
              tuple((s, t, vol / max(1, n_bursts[j]))
                    for s, t, vol in intra_skips if s <= j < t))
             for j in range(len(grans))])
        worst = max(per_pair_stats, key=lambda st: st.worst_channel_load)
    else:
        per_pair_stats = []
        for j in range(len(grans)):
            flows = list(flow_fn(placement, j, j + 1,
                                 float(pe_alloc[j]) * traffic_scale))
            for s, t, vol in intra_skips:
                if s <= j < t:
                    flows.extend(flow_fn(placement, s, t,
                                         vol / max(1, n_bursts[j])))
            per_pair_stats.append(analyze_reference(flows, hw, topology))
        worst = max(per_pair_stats, key=lambda st: st.worst_channel_load)

    return _SegPrep(seg, ops, dfs, grans, pe_alloc, org, placement, worst,
                    per_pair_stats, via_gb, ext_in, ext_out, skip_in,
                    usable, intra_skips, traffic_scale)


def _plan_segment(g: Graph, seg: Segment, hw: HWConfig, topology: Topology,
                  dataflow_fn, force_org: Optional[SpatialOrg],
                  force_gb: Optional[bool],
                  util_fn=None, traffic_scale: float = 1.0,
                  engine: str = "batch") -> SegmentPlan:
    prep = _prep_segment(g, seg, hw, topology, dataflow_fn, force_org,
                         force_gb, util_fn=util_fn,
                         traffic_scale=traffic_scale, engine=engine)
    if engine == "jax":
        cost = _jax_model().price_rows([_price_row(prep, hw)])[0]
    else:
        cost = _host_cost(prep, hw)
    return _finish_segment(prep, cost)


# ---------------------------------------------------------------------------
# Branch-parallel segments: co-placed fork/branches/join regions
# ---------------------------------------------------------------------------


def edges_on_path(edges: Sequence[Tuple[int, int]], s: int, t: int
                  ) -> Tuple[Tuple[int, int], ...]:
    """Edges of the pipeline slot DAG lying on some path from s to t.

    The linear-chain special case reduces to the classic rule "skip (s, t)
    rides every pair j with s <= j < t"; for a branch DAG an intra-region
    skip rides only its own branch's stream.  Falls back to the edges into
    ``t`` when the DAG carries no s->t path (the skip then only loads the
    join's ingress, the closest physical approximation).
    """
    fwd: Dict[int, List[int]] = {}
    back: Dict[int, List[int]] = {}
    for u, v in edges:
        fwd.setdefault(u, []).append(v)
        back.setdefault(v, []).append(u)

    def reach(start: int, adj: Dict[int, List[int]]) -> set:
        seen = {start}
        stack = [start]
        while stack:
            for nxt in adj.get(stack.pop(), ()):
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append(nxt)
        return seen

    from_s = reach(s, fwd)
    to_t = reach(t, back)
    on = tuple((u, v) for u, v in edges if u in from_s and v in to_t)
    if not on:
        on = tuple((u, v) for u, v in edges if v == t)
    return on


def _region_streamable(g: Graph, region: BranchRegion) -> bool:
    """Every fabricated pipeline edge must carry real data flow.

    The region's slot DAG wires fork→head and consecutive branch members;
    that is only an honest pipeline when each branch op actually consumes
    something upstream *in its own stream* — the fork for a head (when
    the fork is inside the segment), an earlier member of the same branch
    (or the fork) otherwise.  The join must likewise consume every branch
    *tail*, or the fabricated tail→join edge would stream data the join
    never reads.  A parallel block of merely *interleaved* independent
    chains (or one with a dead-end branch) fails this and is not offered
    for co-placement (mirroring the linear rule that a sub-span with no
    in-span producer cannot fine-pipeline).
    """
    fork = region.fork
    join_srcs = {g.index(s) for s in g.ops[region.join].inputs}
    for br in region.branches:
        if br[-1] not in join_srcs:
            return False
        for pos, i in enumerate(br):
            feeds = set(br[:pos])
            if fork is not None:
                feeds.add(fork)
            srcs = {g.index(s) for s in g.ops[i].inputs}
            if pos == 0 and fork is None:
                continue       # forkless head streams its external input
            if not srcs & feeds:
                return False
    return True


def _region_edges(region: BranchRegion) -> Tuple[Tuple[int, int], ...]:
    """Slot-relative pipeline DAG of a fork/branches/join region.

    A direct fork→join data edge (``fork_to_join``) is deliberately NOT a
    pipeline edge: the join re-reads the fork's output at its own pace, so
    the tensor rides the branch streams as skip traffic (exactly how the
    linear model treats reuse-distance > 1 edges) rather than forcing a
    dedicated burst schedule through the fork's small partition.
    """
    base = region.start
    join = region.stop - 1 - base
    edges: List[Tuple[int, int]] = []
    fork = 0 if region.has_fork else None
    for br in region.branches:
        rel = [i - base for i in br]
        if fork is not None:
            edges.append((fork, rel[0]))
        edges.extend(zip(rel, rel[1:]))
        edges.append((rel[-1], join))
    return tuple(sorted(set(edges)))


def edge_flow_parts(edges: Tuple[Tuple[int, int], ...], k: int,
                    pe_alloc: Sequence[int], out_volumes: Sequence[int],
                    intra_skips: Sequence[Tuple[int, int, int]],
                    traffic_scale: float
                    ) -> Tuple[List[Tuple[int, int, float]],
                               List[Tuple[int, float]]]:
    """Flow generators of pipeline edge k, as ``(main, siblings)``.

    ``main`` holds (src_slot, dst_slot, words/interval) for the edge's own
    stream (one word per producer PE per interval) followed by every
    intra-segment skip tensor whose path rides this edge, diluted to the
    edge's burst schedule (``vol / n_k`` — the linear model's convention
    for reuse-distance > 1 traffic).  ``siblings`` holds (src_slot,
    words/interval) for the other streams converging on the same consumer
    (the join-aware part): while edge k moves one burst, each other edge
    into the same slot moves ``n_d / n_k`` of its own — over a full
    interval of k the join's ingress also absorbs ``vol_d / n_k`` of
    stream d, and those words contend for the same ingress ports and
    links.  Order is deterministic end to end: the ingress-port
    arbitration is flow-order dependent, so the planner and both
    simulator engines must derive the identical lists.
    """
    u, v = edges[k]
    n_k = edge_burst_count(out_volumes[u], pe_alloc[u])
    main: List[Tuple[int, int, float]] = [
        (u, v, float(pe_alloc[u]) * traffic_scale)]
    for s, t, vol in intra_skips:
        if (u, v) in edges_on_path(edges, s, t):
            main.append((s, t, vol / n_k))
    siblings = [(w, out_volumes[w] * traffic_scale / n_k)
                for w, x in edges if x == v and w != u]
    return main, siblings


def edge_flow_batch(placement: Placement,
                    edges: Tuple[Tuple[int, int], ...], k: int,
                    pe_alloc: Sequence[int], out_volumes: Sequence[int],
                    intra_skips: Sequence[Tuple[int, int, int]],
                    traffic_scale: float, fine: bool) -> FlowBatch:
    """The full flow set priced/transported for pipeline edge k — the one
    construction shared by the analytical stats and both simulator
    engines (``edge_flow_parts`` order; converging sibling streams enter
    through ``noc.join_flow_batch`` so the join's ingress ports arbitrate
    across every producer region)."""
    main, siblings = edge_flow_parts(edges, k, pe_alloc, out_volumes,
                                     intra_skips, traffic_scale)
    parts = [cached_flow_batch(placement, s, t, w, fine)
             for s, t, w in main]
    if siblings:
        v = edges[k][1]
        parts.append(join_flow_batch(placement,
                                     [w for w, _ in siblings], v,
                                     [wd for _, wd in siblings], fine))
    return FlowBatch.concat(parts)


def _prep_branch_segment(g: Graph, region: BranchRegion, hw: HWConfig,
                         topology: Topology, df_fn,
                         force_org: Optional[SpatialOrg] = None,
                         force_gb: Optional[bool] = None,
                         traffic_scale: float = 1.0) -> Optional[_SegPrep]:
    """Host-side half of one co-placed branch-region candidate.

    Returns ``None`` when the region cannot be placed (substrate too small
    for the branch geometry) — the DP then simply keeps the serialized
    alternatives.  Mirrors ``_plan_segment`` with the chain generalized to
    the region's slot DAG: granularities, NoC stats and the cost model all
    run per *edge* (each edge's flow set including the sibling streams
    converging on the same join — ``edge_flow_parts``).
    """
    seg = Segment(region.start, region.stop,
                  tuple(tuple(i - region.start for i in br)
                        for br in region.branches))
    ops = g.ops[seg.start:seg.stop]
    D = len(ops)
    edges = _region_edges(region)
    budget = hw.sram_bytes // max(1, D)
    dfs = [df_fn(op, hw, i, budget) for i, op in enumerate(ops)]
    grans = [finest_granularity(ops[u], dfs[u], ops[v], dfs[v])
             for u, v in edges]

    usable = hw.num_pes
    slot_work = [max(1.0, op_work(op, hw)) for op in ops]

    skips_all, crossing = _segment_skip_traffic(g, seg)
    edge_set = set(edges)
    intra_skips = tuple((s, t, vol) for s, t, vol in skips_all
                        if (s, t) not in edge_set)
    ext_in = ops[0].input_volume() * hw.bytes_per_word
    ext_out = ops[-1].output_volume() * hw.bytes_per_word
    skip_in = crossing * hw.bytes_per_word

    gran_bytes = max(gr.elements for gr in grans) * hw.bytes_per_word
    mean_pes = max(1, hw.num_pes // D)
    if force_org is not None:
        org = force_org
        via_gb = force_gb if force_gb is not None else False
    else:
        org, via_gb = choose_spatial_org(D, gran_bytes, mean_pes, hw)
    if any(not gr.pipelinable for gr in grans):
        via_gb = True
    try:
        placement = place_branches(
            org, slot_work, seg.branches,
            0 if region.has_fork else None, D - 1, hw, via_gb)
    except ValueError:
        return None
    # burst counts and flow volumes come from the *placed* PE counts so the
    # NoC word streams and the interval equations describe the same grid
    pe_alloc = [int((placement.grid == s).sum()) for s in range(D)]
    if any(p == 0 for p in pe_alloc):
        return None

    fine = org in (SpatialOrg.FINE_STRIPED_1D, SpatialOrg.CHECKERBOARD_2D)

    if via_gb:
        per_edge_stats = None
        worst = None
    else:
        out_volumes = [op.output_volume() for op in ops]
        per_edge_stats = analyze_batch(
            [edge_flow_batch(placement, edges, k, pe_alloc, out_volumes,
                             intra_skips, traffic_scale, fine)
             for k in range(len(edges))],
            hw, topology)
        worst = max(per_edge_stats, key=lambda st: st.worst_channel_load)

    return _SegPrep(seg, ops, dfs, grans, pe_alloc, org, placement, worst,
                    per_edge_stats, via_gb, ext_in, ext_out, skip_in,
                    usable, list(intra_skips), traffic_scale,
                    edges=edges, branches=seg.branches)


def _plan_branch_segment(g: Graph, region: BranchRegion, hw: HWConfig,
                         topology: Topology, df_fn,
                         force_org: Optional[SpatialOrg] = None,
                         force_gb: Optional[bool] = None,
                         traffic_scale: float = 1.0,
                         engine: str = "batch") -> Optional[SegmentPlan]:
    prep = _prep_branch_segment(g, region, hw, topology, df_fn,
                                force_org, force_gb, traffic_scale)
    if prep is None:
        return None
    if engine == "jax":
        cost = _jax_model().price_rows([_price_row(prep, hw)])[0]
    else:
        cost = _host_cost(prep, hw)
    return _finish_segment(prep, cost)


def _region_plans(g: Graph, seg: Segment, hw: HWConfig, topology: Topology,
                  df_fn, engine: str = "batch"
                  ) -> Dict[int, List[SegmentPlan]]:
    """Branch-segment DP candidates inside one stage-1 segment, keyed by
    their start position.

    Each useful region is offered with its fork (slot 0 feeds the branches
    on-chip) and, for multi-branch regions, without it (so the DP may
    leave the fork in the preceding sub-span) — and across the whole
    stage-2 mapping space: every spatial organization, PE-to-PE or staged
    through the global buffer.  The ``choose_spatial_org`` rule was
    derived for linear chains; for branched layouts the candidates go to
    the DP's Pareto selection instead, which also prices the serialized
    alternatives, so the enumeration can only improve the guarded result.
    Shape-identical (org, staging) pairs (e.g. the two blocked styles
    produce one banded grid) are deduplicated by their placement grid.
    """
    seen: set = set()
    preps: List[_SegPrep] = []
    for r in branch_regions(g, seg.start, seg.stop, hw.max_depth):
        if len(r.branches) < 2 and not r.fork_to_join:
            continue
        variants = [r]
        if r.has_fork and len(r.branches) >= 2:
            variants.append(BranchRegion(r.start + 1, r.stop, r.branches,
                                         has_fork=False))
        for v in variants:
            if (v.start, v.stop, v.has_fork) in seen:
                continue
            seen.add((v.start, v.stop, v.has_fork))
            if not _region_streamable(g, v):
                continue
            grids: set = set()
            for org in SpatialOrg:
                for gb in (False, True):
                    prep = _prep_branch_segment(g, v, hw, topology, df_fn,
                                                force_org=org, force_gb=gb)
                    if prep is None:
                        continue
                    gkey = (prep.placement.grid.tobytes(),
                            prep.placement.via_global_buffer)
                    if gkey in grids:
                        continue
                    grids.add(gkey)
                    preps.append(prep)
    # price the whole (region, org, staging) enumeration in one call on
    # the jax engine; one host segment_cost call each otherwise
    if engine == "jax" and preps:
        m = _jax_model()
        costs = m.price_rows([_price_row(p, hw) for p in preps])
    else:
        costs = [_host_cost(p, hw) for p in preps]
    out: Dict[int, List[SegmentPlan]] = {}
    for prep, cost in zip(preps, costs):
        out.setdefault(prep.seg.start, []).append(
            _finish_segment(prep, cost))
    return out


# ---------------------------------------------------------------------------
# PipeOrgan: memoized cut-point DP within each heuristic segment
# ---------------------------------------------------------------------------


def _pipeorgan_df_fn(op: Op, hw: HWConfig, i: int, budget: int) -> Dataflow:
    return choose_dataflow(op, hw, budget)


#: content-addressed span plans: same-shape layer runs (repeated conv
#: blocks, re-planned tasks) plan identically, wherever they sit in a graph.
#: This is the *memory tier*; ``set_span_shelf`` adds a persistent
#: on-disk tier behind it (``artifact.SpanShelf``) so a fleet of serve
#: engines cold-missing into the DP reuses each other's solved spans.
_SPAN_CACHE_MAX = 65536
_span_plan_cache: "collections.OrderedDict[Tuple, SegmentPlan]" = \
    collections.OrderedDict()
_span_mem_stats = {"hits": 0, "misses": 0}

#: the installed persistent span tier (an ``artifact.SpanShelf``), or None
_span_shelf = None


def span_cache_info() -> Tuple[int, int, int, int]:
    """(hits, misses, maxsize, currsize) of the memory span tier."""
    return (_span_mem_stats["hits"], _span_mem_stats["misses"],
            _SPAN_CACHE_MAX, len(_span_plan_cache))


def span_cache_clear() -> None:
    """Drop the memory span tier and its counters (the shelf, if any, is
    untouched — clearing memory is how the shelf-warm path is exercised)."""
    _span_plan_cache.clear()
    _span_mem_stats["hits"] = 0
    _span_mem_stats["misses"] = 0


def set_span_shelf(shelf) -> None:
    """Install (``artifact.SpanShelf``) or remove (``None``) the
    persistent span tier.  Installed, every span-cache memory miss
    consults the shelf before solving, and every freshly solved span is
    shelved; the shelf's hit/miss counters appear in
    ``Planner.cache_info_all()`` as ``span_shelf`` while installed."""
    global _span_shelf
    _span_shelf = shelf
    if shelf is None:
        unregister_cache("span_shelf")
    else:
        register_cache("span_shelf", shelf.info, overwrite=True)


def get_span_shelf():
    """The installed persistent span tier, or ``None``."""
    return _span_shelf


#: strategy family baked into every shelf token: shelved spans are DP
#: sub-segment solutions, shared by all pipeorgan DP variants (which is
#: sound — they price spans identically — but must never collide with a
#: future strategy family solving spans differently).
_SPAN_TOKEN_FAMILY = "pipeorgan-dp"


def _span_token(sig: Tuple) -> str:
    """Cross-process content address of one span-cache key: the span
    signature plus everything else the solved plan depends on (hardware,
    topology, pricing engine, DP family)."""
    span_sig, hw, topology, engine = sig
    return content_token((_SPAN_TOKEN_FAMILY, engine, topology.value,
                          sorted(dataclasses.asdict(hw).items()), span_sig))


def _span_store(sig: Tuple, plan: SegmentPlan) -> None:
    _span_plan_cache[sig] = plan
    if len(_span_plan_cache) > _SPAN_CACHE_MAX:
        _span_plan_cache.popitem(last=False)


def _shelf_fetch(sig: Tuple, g: Graph, i: int, j: int
                 ) -> Optional[SegmentPlan]:
    """Shelf tier lookup; a hit is rebound to this span's ops and
    promoted into the memory tier."""
    if _span_shelf is None:
        return None
    plan = _span_shelf.load(_span_token(sig))
    if plan is None:
        return None
    plan = _rebind_span(plan, g, i, j)
    _span_store(sig, plan)
    return plan


def _shelf_put(sig: Tuple, plan: SegmentPlan) -> None:
    if _span_shelf is not None:
        _span_shelf.save(_span_token(sig), plan)


def _span_signature(g: Graph, seg: Segment) -> Tuple:
    """Everything ``_plan_segment`` reads from a span, by value: op shapes
    and strides, the in-span input wiring (slot-relative; it decides the
    disconnected->GB fallback), intra-span skip pairs, and the
    boundary-crossing skip volume.  Memoized per (graph, span): the DP
    re-signs each span once per org/staging variant."""
    key = (id(g), seg.start, seg.stop)
    hit = _SPAN_SIG_CACHE.get(key)
    if hit is not None and hit[0] is g:
        return hit[1]
    intra, crossing = _segment_skip_traffic(g, seg)
    ops_sig = tuple(
        (op.kind.value, tuple(sorted(op.dims.items())), op.stride,
         tuple(sorted(g.index(s) - seg.start for s in op.inputs
                      if seg.start <= g.index(s) < seg.stop)))
        for op in g.ops[seg.start:seg.stop])
    sig = (ops_sig, tuple(intra), crossing)
    if len(_SPAN_SIG_CACHE) >= _SPAN_MEMO_MAX:
        _SPAN_SIG_CACHE.clear()
    _SPAN_SIG_CACHE[key] = (g, sig)
    return sig


def _rebind_span(plan: SegmentPlan, g: Graph, i: int, j: int) -> SegmentPlan:
    """Re-point a cached shape-identical plan at this span's actual ops."""
    ops = list(g.ops[i:j])
    dfs = [dataclasses.replace(df, op_name=op.name)
           for df, op in zip(plan.dataflows, ops)]
    grans = [dataclasses.replace(gr, producer=ops[k].name,
                                 consumer=ops[k + 1].name)
             for k, gr in enumerate(plan.granularities)]
    return dataclasses.replace(plan, segment=Segment(i, j), ops=ops,
                               dataflows=dfs, granularities=grans)


# ---------------------------------------------------------------------------
# Plan folding: solve one representative stage-1 segment per structural
# equivalence class, tile the rest by translation (docs/planner.md)
# ---------------------------------------------------------------------------


_FOLD_SIG_CACHE: Dict[Tuple[int, int, int], Tuple[Graph, Tuple]] = {}

#: per-op static signature (kind, sorted dims, stride), keyed by object
#: identity — ops are immutable, and both the DP (overlapping spans) and
#: the verifier (one sweep per plan right after planning, same objects)
#: revisit the same ops many times
_OP_SIG_CACHE: Dict[int, Tuple[Op, Tuple]] = {}

#: per-graph skip index: (graph, producer array, (consumer, idx) array)
#: so each span extracts its touching skips by bisection instead of
#: scanning every skip edge in the graph
_SKIP_INDEX_CACHE: Dict[int, Tuple[Graph, List, List]] = {}


def _op_static_sig(op: Op) -> Tuple:
    hit = _OP_SIG_CACHE.get(id(op))
    if hit is not None and hit[0] is op:
        return hit[1]
    sig = (op.kind.value, tuple(sorted(op.dims.items())), op.stride)
    if len(_OP_SIG_CACHE) >= _SPAN_MEMO_MAX:
        _OP_SIG_CACHE.clear()
    _OP_SIG_CACHE[id(op)] = (op, sig)
    return sig


def _skip_index(g: Graph) -> Tuple[List, List]:
    hit = _SKIP_INDEX_CACHE.get(id(g))
    if hit is not None and hit[0] is g:
        return hit[1], hit[2]
    edges = g.skip_edges()
    by_p = [(p, c) for p, c in edges]          # already sorted by (p, c)
    by_c = sorted(((c, p) for p, c in edges))
    if len(_SKIP_INDEX_CACHE) >= _SPAN_MEMO_MAX:
        _SKIP_INDEX_CACHE.clear()
    _SKIP_INDEX_CACHE[id(g)] = (g, by_p, by_c)
    return by_p, by_c


def _fold_signature(g: Graph, seg: Segment) -> Tuple:
    """Everything ``_best_subsegmentation`` reads from a stage-1 segment,
    by value and modulo slot offset: the ops' shapes, strides and
    in-segment wiring (the ``_span_signature`` value rules) plus EVERY
    skip edge touching the segment, slot-relative with a ``-1`` sentinel
    for an external endpoint.  The sentinel is sound because an external
    endpoint only ever contributes its volume — which sub-spans an edge
    crosses is decided by the in-segment endpoint alone.  Two segments
    with equal fold signatures plan identically up to translation: every
    sub-span signature, branch region, streamability verdict and prep
    input the DP consumes is a pure function of this value."""
    key = (id(g), seg.start, seg.stop)
    hit = _FOLD_SIG_CACHE.get(key)
    if hit is not None and hit[0] is g:
        return hit[1]
    s0, s1 = seg.start, seg.stop
    ops_sig = tuple(
        _op_static_sig(op)
        + (tuple(sorted(g.index(s) - s0 for s in op.inputs
                        if s0 <= g.index(s) < s1)),)
        for op in g.ops[s0:s1])
    # the union of "producer in span" and "consumer in span" ranges,
    # deduped — identical membership to the full scan, found by bisection
    by_p, by_c = _skip_index(g)
    touching = {pc for pc in by_p[bisect.bisect_left(by_p, (s0,)):
                                  bisect.bisect_left(by_p, (s1,))]}
    touching.update((p, c) for c, p in
                    by_c[bisect.bisect_left(by_c, (s0,)):
                         bisect.bisect_left(by_c, (s1,))])
    skips = []
    for p, c in touching:
        skips.append((p - s0 if s0 <= p < s1 else -1,
                      c - s0 if s0 <= c < s1 else -1,
                      g.ops[p].output_volume()))
    sig = (ops_sig, tuple(sorted(skips)))
    if len(_FOLD_SIG_CACHE) >= _SPAN_MEMO_MAX:
        _FOLD_SIG_CACHE.clear()
    _FOLD_SIG_CACHE[key] = (g, sig)
    return sig


def _translate_span(plan: SegmentPlan, g: Graph, delta: int) -> SegmentPlan:
    """Re-point a plan at the slot-translated copy of its span — the
    tiling step of plan folding.  Generalizes ``_rebind_span`` to
    branch-parallel plans: placement, costs, intra skips, the slot DAG
    and the branch groups are all slot-relative already, so only the
    segment indices and the op bindings move."""
    seg = plan.segment.translate(delta)
    ops = list(g.ops[seg.start:seg.stop])
    dfs = [dataclasses.replace(df, op_name=op.name)
           for df, op in zip(plan.dataflows, ops)]
    grans = [dataclasses.replace(gr, producer=ops[u].name,
                                 consumer=ops[v].name)
             for gr, (u, v) in zip(plan.granularities, plan.pipeline_edges)]
    return dataclasses.replace(plan, segment=seg, ops=ops,
                               dataflows=dfs, granularities=grans)


def _fold_keys(g: Graph):
    """Fold-equivalence key function over stage-1 segments.

    Fast path: segments in the *interior* of one periodic run — a full
    reuse-distance margin away from both run edges, so their whole wiring
    environment repeats with the run — fold by (run, phase, depth) alone,
    no signature computed.  Everything else, seam and boundary segments
    included, falls back to the exact content signature: the spans around
    each period seam are re-solved exactly, never assumed periodic.
    """
    runs = periodic_regions(g)
    margin = g.max_reuse_distance()

    def key(seg: Segment) -> Tuple:
        for run in runs:
            if (run.start + margin <= seg.start
                    and seg.stop + margin <= run.stop):
                return ("periodic", run.start, run.period,
                        (seg.start - run.start) % run.period,
                        seg.depth, seg.branches)
            if seg.start < run.stop and run.start < seg.stop:
                break          # overlaps this run but not interior
        return ("sig", _fold_signature(g, seg), seg.branches)

    return key


def _fold_plan_segments(g: Graph, segs: Sequence[Segment], solve
                        ) -> List[SegmentPlan]:
    """Plan stage-1 ``segs``, folding structurally identical ones: the
    first segment of each fold class is solved for real, the rest reuse
    its plans translated to their slot offsets.  Bit-identical to solving
    every segment independently because fold-equal segments present the
    planner with value-identical inputs and the pricing engines are
    deterministic value functions — the unfolded run would produce
    exactly the translated plans, float for float (pinned by the
    ``test_plan_folding`` parity suite)."""
    key_of = _fold_keys(g)
    solved: Dict[Tuple, Tuple[int, List[SegmentPlan]]] = {}
    out: List[SegmentPlan] = []
    for seg in segs:
        k = key_of(seg)
        hit = solved.get(k)
        if hit is None:
            plans = solve(seg)
            solved[k] = (seg.start, plans)
            out.extend(plans)
        else:
            rep_start, plans = hit
            out.extend(_translate_span(p, g, seg.start - rep_start)
                       for p in plans)
    return out


def _segment_planner(g: Graph, hw: HWConfig, topology: Topology, df_fn,
                     engine: str = "batch"):
    """Memoized ``plan(i, j)`` over sub-segment cut points.

    One planning run holds (g, hw, topology, df_fn) fixed, so (i, j) is a
    complete cache key; the DP and the uniform-depth candidates share the
    same cache, which is what makes the never-worse guard an *exact*
    float-for-float comparison.  Underneath, plans are also cached by span
    *content* so repeated same-shape layer runs plan once per process.
    """
    memo: Dict[Tuple[int, int], SegmentPlan] = {}
    cacheable = engine in ("batch", "jax") and df_fn is _pipeorgan_df_fn

    def plan_ij(i: int, j: int) -> SegmentPlan:
        key = (i, j)
        if key in memo:
            return memo[key]
        seg = Segment(i, j)
        if cacheable:
            # engine is part of the content key: the two engines' costs
            # agree to ~1e-9 relative, not bit-for-bit, and the caches
            # must never cross-pollinate an exact-equality guard
            sig = (_span_signature(g, seg), hw, topology, engine)
            hit = _span_plan_cache.get(sig)
            if hit is not None:
                _span_mem_stats["hits"] += 1
                _span_plan_cache.move_to_end(sig)
                plan = _rebind_span(hit, g, i, j)
            else:
                _span_mem_stats["misses"] += 1
                plan = _shelf_fetch(sig, g, i, j)
                if plan is None:
                    plan = _plan_segment(g, seg, hw, topology, df_fn,
                                         None, None, engine=engine)
                    _span_store(sig, plan)
                    _shelf_put(sig, plan)
        else:
            plan = _plan_segment(g, seg, hw, topology, df_fn,
                                 None, None, engine=engine)
        memo[key] = plan
        return plan

    def prime(spans: Iterable[Tuple[int, int]]) -> None:
        """Batch-process many spans ahead of the DP walk.

        Every span not already memoized (or span-content cached) is
        prepped back to back, so the whole frontier's NoC analysis runs
        as consecutive ``analyze_batch`` sweeps over the shared
        route-incidence tables (span ``[i, j]`` extends ``[i, j-1]``'s
        pair set, so the sweep is almost all incidence/pair-cache hits).
        The jax engine additionally materializes each prep as a
        struct-of-arrays row and prices them all in a single
        ``price_rows`` dispatch; the numpy engine prices host-side, one
        ``segment_cost`` per span.  Shape-identical spans are processed
        once and rebound.
        """
        if engine not in ("jax", "batch"):
            return
        todo: List[Tuple[int, int, Optional[Tuple]]] = []
        first_of_sig: Dict[Tuple, int] = {}
        aliases: List[Tuple[int, int, int]] = []   # (i, j, todo index)
        for i, j in spans:
            if (i, j) in memo:
                continue
            sig = None
            if cacheable:
                seg = Segment(i, j)
                sig = (_span_signature(g, seg), hw, topology, engine)
                hit = _span_plan_cache.get(sig)
                if hit is not None:
                    _span_mem_stats["hits"] += 1
                    _span_plan_cache.move_to_end(sig)
                    memo[(i, j)] = _rebind_span(hit, g, i, j)
                    continue
                if sig in first_of_sig:
                    aliases.append((i, j, first_of_sig[sig]))
                    continue
                _span_mem_stats["misses"] += 1
                shelf_plan = _shelf_fetch(sig, g, i, j)
                if shelf_plan is not None:
                    memo[(i, j)] = shelf_plan
                    continue
                first_of_sig[sig] = len(todo)
            todo.append((i, j, sig))
        if not todo:
            return
        preps = [_prep_segment(g, Segment(i, j), hw, topology, df_fn,
                               None, None, engine=engine)
                 for i, j, _ in todo]
        if engine == "jax":
            costs = _jax_model().price_rows([_price_row(p, hw)
                                             for p in preps])
        else:
            costs = [_host_cost(p, hw) for p in preps]
        plans: List[SegmentPlan] = []
        for (i, j, sig), prep, cost in zip(todo, preps, costs):
            plan = _finish_segment(prep, cost)
            plans.append(plan)
            memo[(i, j)] = plan
            if sig is not None:
                _span_store(sig, plan)
                _shelf_put(sig, plan)
        for i, j, t in aliases:
            memo[(i, j)] = _rebind_span(plans[t], g, i, j)

    plan_ij.prime = prime
    return plan_ij


Candidate = Tuple[float, float, Tuple[SegmentPlan, ...]]


def _search_spans(seg: Segment, max_span: int) -> List[Tuple[int, int]]:
    """Every (i, j) span the uniform enumeration + cut-point DP will
    price for ``seg`` — the prime set for batched jax pricing."""
    spans = set()
    for d in {1, 2, 4, 8, seg.depth}:
        if d > seg.depth:
            continue
        i = seg.start
        while i < seg.stop:
            j = min(i + d, seg.stop)
            spans.add((i, j))
            i = j
    if seg.depth > 1:
        for i in range(seg.start, seg.stop):
            for j in seg.spans_from(i, max_span):
                spans.add((i, j))
    return sorted(spans)


def _uniform_candidates(seg: Segment, plan_ij) -> List[Candidate]:
    """The original enumeration: uniform depths {1, 2, 4, 8, seg.depth}."""
    cands: List[Candidate] = []
    for d in sorted({1, 2, 4, 8, seg.depth}, reverse=True):
        if d > seg.depth:
            continue
        subplans: List[SegmentPlan] = []
        i = seg.start
        while i < seg.stop:
            j = min(i + d, seg.stop)
            subplans.append(plan_ij(i, j))
            i = j
        lat = sum(p.cost.latency_cycles for p in subplans)
        dram = sum(p.cost.dram_bytes for p in subplans)
        cands.append((lat, dram, tuple(subplans)))
    return cands


def _cand_metrics(c: Candidate) -> Dict[str, float]:
    """The objective-facing metrics of one candidate segmentation."""
    return {"latency_cycles": c[0], "dram_bytes": c[1],
            "energy": sum(p.cost.total_energy for p in c[2])}


def _select(cands: Sequence[Candidate],
            objective: Objective = DEFAULT_OBJECTIVE,
            constraints: Sequence[Constraint] = ()) -> Candidate:
    """Frontier selection, delegated to the request's ``Objective``.

    The default objective reproduces the historical hard-coded rule bit
    for bit: latency first; among candidates within 25% of the best
    latency, the lowest DRAM traffic (the paper optimizes both
    performance and energy — Fig. 13 / Fig. 14).
    """
    return objective.select(list(cands), [_cand_metrics(c) for c in cands],
                            constraints)


def _pareto(points: List[Candidate]) -> List[Candidate]:
    """Non-dominated subset under (latency, dram), latency-sorted."""
    points.sort(key=lambda p: (p[0], p[1]))
    front: List[Candidate] = []
    best_dram = math.inf
    for p in points:
        if p[1] < best_dram:
            front.append(p)
            best_dram = p[1]
    return front


def _dp_frontier(seg: Segment, plan_ij, max_span: int,
                 extra: Optional[Dict[int, List[SegmentPlan]]] = None
                 ) -> List[Candidate]:
    """Pareto frontier of all cut-point segmentations of ``seg``.

    best(i) = Pareto-min over j in (i, i+max_span] of cost(i, j) + best(j),
    solved right-to-left so each suffix is planned exactly once.

    ``extra`` adds pre-priced transitions — the branch-parallel region
    segments — keyed by start position: at position i the DP chooses
    between the linear sub-spans (serializing the region) and any offered
    co-placed alternative, which is exactly the paper's "co-place vs
    serialize" decision, settled by the Pareto objective.
    """
    best: Dict[int, List[Candidate]] = {seg.stop: [(0.0, 0.0, ())]}
    for i in range(seg.stop - 1, seg.start - 1, -1):
        cands: List[Candidate] = []
        for j in seg.spans_from(i, max_span):
            p = plan_ij(i, j)
            lat_ij, dram_ij = p.cost.objective
            for lat, dram, rest in best[j]:
                cands.append((lat_ij + lat, dram_ij + dram, (p,) + rest))
        for p in (extra or {}).get(i, ()):
            j = p.segment.stop
            if j > seg.stop:
                continue
            lat_ij, dram_ij = p.cost.objective
            for lat, dram, rest in best[j]:
                cands.append((lat_ij + lat, dram_ij + dram, (p,) + rest))
        best[i] = _pareto(cands)
    return best[seg.start]


def _sim_rerank(viable: Sequence[Candidate], hw: HWConfig,
                topology: Topology,
                objective: Objective = DEFAULT_OBJECTIVE,
                constraints: Sequence[Constraint] = (),
                max_bursts: Optional[int] = None) -> Candidate:
    """Re-rank the guarded Pareto frontier by *simulated* latency.

    Every candidate here already dominates (or is) the uniform choice on
    the analytical objective; the simulator breaks the remaining ties with
    measured fill, transport serialization and backpressure instead of the
    closed-form interval model.  Analytical (latency, dram) stay as the
    deterministic tie-breakers so ``sim_check`` is a refinement, never a
    regression, of the default selection order.

    Under a non-default objective (or constraints) the selection is the
    objective itself applied to the candidates' metrics with
    ``latency_cycles`` replaced by the simulated latency; the default
    latency-first path keeps the historical pure-lexicographic
    ``min(sim, lat, dram)`` exactly.
    """
    from .simulator import simulate_segment   # deferred: simulator imports us
    from .plan_api import DEFAULT_MAX_BURSTS

    bursts = DEFAULT_MAX_BURSTS if max_bursts is None else max_bursts

    def sim_latency(cand: Candidate) -> float:
        return sum(simulate_segment(p, hw, topology, bursts).latency_cycles
                   for p in cand[2])

    if objective == DEFAULT_OBJECTIVE and not constraints:
        return min(viable, key=lambda c: (sim_latency(c), c[0], c[1]))
    metrics = []
    for c in viable:
        m = _cand_metrics(c)
        m["latency_cycles"] = sim_latency(c)
        metrics.append(m)
    return objective.select(list(viable), metrics, constraints)


def _best_subsegmentation(g: Graph, seg: Segment, hw: HWConfig,
                          topology: Topology, df_fn,
                          engine: str = "batch",
                          sim_check: bool = False,
                          branch: bool = False,
                          objective: Objective = DEFAULT_OBJECTIVE,
                          constraints: Sequence[Constraint] = (),
                          max_bursts: Optional[int] = None
                          ) -> List[SegmentPlan]:
    plan_ij = _segment_planner(g, hw, topology, df_fn, engine=engine)
    max_span = min(seg.depth, hw.max_depth, DP_MAX_SPAN)
    plan_ij.prime(_search_spans(seg, max_span))
    u_lat, u_dram, u_plans = _select(_uniform_candidates(seg, plan_ij),
                                     objective, constraints)
    if seg.depth == 1:
        return list(u_plans)
    frontier = _dp_frontier(seg, plan_ij, max_span)
    # guard, re-expressed per objective: the DP result must dominate (or
    # match) the uniform enumeration's best *under the same objective and
    # constraints* on BOTH objective axes — strictly no-worse plans by
    # construction, whatever the selection rule
    viable = [(l, d, p) for l, d, p in frontier
              if l <= u_lat and d <= u_dram]
    viable.append((u_lat, u_dram, u_plans))
    regions = (_region_plans(g, seg, hw, topology, df_fn, engine=engine)
               if branch else {})
    if not regions:
        if sim_check:
            _, _, chosen = _sim_rerank(viable, hw, topology, objective,
                                       constraints, max_bursts)
        else:
            _, _, chosen = _select(viable, objective, constraints)
        return list(chosen)
    # second guard, same per-objective rule: the branch-extended DP must
    # dominate (or match) the *linearized* selection on BOTH axes, so
    # co-placement is strictly never-worse than serializing the
    # topological order under any objective
    lin_lat, lin_dram, lin_plans = _select(viable, objective, constraints)
    b_frontier = _dp_frontier(seg, plan_ij, max_span, regions)
    b_viable = [(l, d, p) for l, d, p in b_frontier
                if l <= lin_lat and d <= lin_dram]
    b_viable.append((lin_lat, lin_dram, lin_plans))
    if sim_check:
        _, _, chosen = _sim_rerank(b_viable, hw, topology, objective,
                                   constraints, max_bursts)
    else:
        _, _, chosen = _select(b_viable, objective, constraints)
    return list(chosen)


def plan_pipeorgan(g: Graph, hw: HWConfig,
                   topology: Topology = Topology.AMP,
                   sim_check: bool = False,
                   objective: Objective = DEFAULT_OBJECTIVE,
                   constraints: Sequence[Constraint] = (),
                   max_bursts: Optional[int] = None,
                   engine: str = "numpy",
                   fold: bool = True) -> PlanResult:
    """Full PipeOrgan flow (Fig. 7) with the cut-point DP mapper.

    Stage 1's footprint heuristic gives the *maximum useful* depth per
    segment; stage 2 then solves for the cheapest sub-segmentation with a
    memoized DP over cut points (deeper pipelines shrink per-layer tile
    budgets — Sec. III-A — so the mapper keeps the heuristic depth only
    when the evaluated cost agrees), allowing mixed depths the uniform
    enumeration cannot express while never doing worse than it.

    ``sim_check=True`` re-ranks each segment's guarded Pareto frontier by
    event-*simulated* latency (the differential oracle) instead of the
    analytical objective alone — worth its cost when plans are computed
    offline or the workload is served long enough to amortize it (see
    docs/simulator.md).

    Branch-aware planning (docs/planner.md): within each stage-1 segment
    the DP also considers co-placing every series-parallel region
    (``graph.branch_regions``) as a single branch-parallel segment, and a
    second guard keeps the result never-worse than the purely linearized
    selection (``plan_pipeorgan_linear``) on both objective axes.

    ``objective``/``constraints`` steer the frontier selection (and the
    ``sim_check`` re-rank); both guards are applied against the baseline
    selected *under the same objective*, so any objective's plan is
    never-worse than the uniform enumeration and the linearized planner
    would be for that objective.  The default reproduces the historical
    latency-first rule bit for bit.

    ``engine`` selects the candidate pricer: ``"numpy"`` (default — the
    vectorized host engine, bit-stable against the goldens), ``"jax"``
    (batched jit/vmap pricing, ~1e-9 relative agreement), or ``"auto"``
    (jax when available).  See docs/engines.md.

    ``fold=True`` (default) plans one representative per class of
    structurally identical stage-1 segments and tiles the rest by
    translation — near-O(unique structure) cold planning on periodic
    graphs (LM layer stacks), bit-identical to ``fold=False`` (a pure
    speed knob, deliberately NOT part of ``PlanRequest`` identity).
    """
    eng = resolve_engine(engine)

    def solve(s: Segment) -> List[SegmentPlan]:
        return _best_subsegmentation(g, s, hw, topology, _pipeorgan_df_fn,
                                     engine=eng, sim_check=sim_check,
                                     branch=True, objective=objective,
                                     constraints=constraints,
                                     max_bursts=max_bursts)

    segs = segment_graph(g, hw)
    if fold:
        plans = _fold_plan_segments(g, segs, solve)
    else:
        plans = [p for s in segs for p in solve(s)]
    return PlanResult(g.name, "pipeorgan", topology, plans)


def plan_pipeorgan_linear(g: Graph, hw: HWConfig,
                          topology: Topology = Topology.AMP,
                          sim_check: bool = False,
                          objective: Objective = DEFAULT_OBJECTIVE,
                          constraints: Sequence[Constraint] = (),
                          max_bursts: Optional[int] = None,
                          engine: str = "numpy",
                          fold: bool = True) -> PlanResult:
    """The cut-point DP *without* branch-parallel candidates.

    This is exactly the pre-branch-aware planner: every series-parallel
    region is serialized in topological order.  Kept as the guard baseline
    (``plan_pipeorgan`` must never lose to it on either objective axis,
    per objective) and for the co-placed-vs-serialized differential
    sweeps.  ``fold`` as in ``plan_pipeorgan``.
    """
    eng = resolve_engine(engine)

    def solve(s: Segment) -> List[SegmentPlan]:
        return _best_subsegmentation(g, s, hw, topology, _pipeorgan_df_fn,
                                     engine=eng, sim_check=sim_check,
                                     objective=objective,
                                     constraints=constraints,
                                     max_bursts=max_bursts)

    segs = segment_graph(g, hw)
    if fold:
        plans = _fold_plan_segments(g, segs, solve)
    else:
        plans = [p for s in segs for p in solve(s)]
    return PlanResult(g.name, "pipeorgan-linear", topology, plans)


def plan_pipeorgan_uniform(g: Graph, hw: HWConfig,
                           topology: Topology = Topology.AMP,
                           objective: Objective = DEFAULT_OBJECTIVE,
                           constraints: Sequence[Constraint] = (),
                           engine: str = "numpy") -> PlanResult:
    """The original uniform-depth enumeration on the vectorized engine.

    Same search space and selection rule as the seed planner; used by the
    equivalence tests as the baseline the DP must never lose to (selected
    under the same objective as the DP when one is given).
    """
    eng = resolve_engine(engine)
    plans: List[SegmentPlan] = []
    for s in segment_graph(g, hw):
        plan_ij = _segment_planner(g, hw, topology, _pipeorgan_df_fn,
                                   engine=eng)
        plan_ij.prime(_search_spans(s, 0))
        _, _, chosen = _select(_uniform_candidates(s, plan_ij),
                               objective, constraints)
        plans.extend(chosen)
    return PlanResult(g.name, "pipeorgan-uniform", topology, plans)


def plan_pipeorgan_reference(g: Graph, hw: HWConfig,
                             topology: Topology = Topology.AMP) -> PlanResult:
    """Pre-refactor planner: uniform enumeration, no memoization, scalar
    NoC walk.  Kept as the wall-clock baseline for ``planner_speed``."""
    plans: List[SegmentPlan] = []
    for s in segment_graph(g, hw):
        candidates: List[Candidate] = []
        for d in sorted({1, 2, 4, 8, s.depth}, reverse=True):
            if d > s.depth:
                continue
            subplans: List[SegmentPlan] = []
            i = s.start
            while i < s.stop:
                ss = Segment(i, min(i + d, s.stop))
                subplans.append(_plan_segment(g, ss, hw, topology,
                                              _pipeorgan_df_fn, None, None,
                                              engine="reference"))
                i = ss.stop
            lat = sum(p.cost.latency_cycles for p in subplans)
            dram = sum(p.cost.dram_bytes for p in subplans)
            candidates.append((lat, dram, tuple(subplans)))
        _, _, chosen = _select(candidates)
        plans.extend(chosen)
    return PlanResult(g.name, "pipeorgan", topology, plans)


# ---------------------------------------------------------------------------
# Baseline strategies
# ---------------------------------------------------------------------------


def plan_tangram_like(g: Graph, hw: HWConfig,
                      topology: Topology = Topology.MESH) -> PlanResult:
    """Fixed depth=2, alternating output/input stationary, blocked 1D."""
    segs = []
    i = 0
    while i < len(g.ops):
        d = 2 if i + 1 < len(g.ops) else 1
        # don't pair across a complex layer and require a direct edge
        if d == 2:
            nxt = g.ops[i + 1]
            direct = any(g.index(s) == i for s in nxt.inputs)
            if (nxt.kind in COMPLEX_KINDS or g.ops[i].kind in COMPLEX_KINDS
                    or not direct):
                d = 1
        segs.append(Segment(i, i + d))
        i += d

    def df_fn(op: Op, hw_: HWConfig, slot: int, budget: int) -> Dataflow:
        base = choose_dataflow(op, hw_, budget)
        if op.kind == OpKind.CONV:
            order = (("N", "H", "W", "K", "C", "R", "S") if slot == 0
                     else ("N", "H", "W", "C", "K", "R", "S"))
            return dataclasses.replace(base, loop_order=order,
                                       stationary="output" if slot == 0
                                       else "input")
        if op.kind == OpKind.GEMM:
            order = ("M", "N", "K") if slot == 0 else ("M", "K", "N")
            return dataclasses.replace(base, loop_order=order)
        return base

    # Alternating output-/input-stationary pipelining moves the forwarded
    # activation AND the consumer's spatially-spread partial sums through
    # the NoC (the reason the paper's TANGRAM congests at 1-cycle
    # intervals on KD-resnet) -> 2x burst traffic per interval.
    plans = [_plan_segment(g, s, hw, topology, df_fn,
                           SpatialOrg.BLOCKED_1D, False,
                           traffic_scale=2.0) for s in segs]
    return PlanResult(g.name, "tangram-like", topology, plans)


def plan_simba_like(g: Graph, hw: HWConfig,
                    topology: Topology = Topology.MESH) -> PlanResult:
    """Parallelize C,K; pipeline only on substrate under-utilization."""
    segs: List[Segment] = []
    i = 0
    while i < len(g.ops):
        op = g.ops[i]
        ck = op.dims.get("C", 1) * op.dims.get("K", op.dims.get("C", 1))
        underutilized = ck < hw.num_pes
        d = 1
        if underutilized and i + 1 < len(g.ops):
            nxt = g.ops[i + 1]
            direct = any(g.index(s) == i for s in nxt.inputs)
            if nxt.kind not in COMPLEX_KINDS and direct:
                d = 2
        segs.append(Segment(i, i + d))
        i += d

    def df_fn(op: Op, hw_: HWConfig, slot: int, budget: int) -> Dataflow:
        base = choose_dataflow(op, hw_, budget)
        if op.kind == OpKind.CONV:
            # C/K parallel => output stationary spatial over channels
            return dataclasses.replace(
                base, loop_order=("N", "H", "W", "K", "C", "R", "S"))
        return base

    def util_fn(op: Op, hw_: HWConfig) -> float:
        # SIMBA-like spreads only input/output channels spatially
        d = op.dims
        if op.kind == OpKind.CONV:
            par = d["C"] * d["K"]
        elif op.kind == OpKind.DWCONV:
            par = d["C"]
        elif op.kind == OpKind.GEMM:
            par = d["N"] * min(d["K"], 64)
        else:
            par = op.output_volume()
        return min(1.0, par / hw_.num_pes)

    plans = [_plan_segment(g, s, hw, topology, df_fn,
                           SpatialOrg.BLOCKED_1D, False, util_fn=util_fn)
             for s in segs]
    return PlanResult(g.name, "simba-like", topology, plans)


def plan_layer_by_layer(g: Graph, hw: HWConfig) -> PlanResult:
    segs = [Segment(i, i + 1) for i in range(len(g.ops))]
    plans = [_plan_segment(g, s, hw, Topology.MESH, _pipeorgan_df_fn,
                           None, None) for s in segs]
    return PlanResult(g.name, "layer-by-layer", Topology.MESH, plans)


# ---------------------------------------------------------------------------
# registration: the built-in strategies and this module's caches
# ---------------------------------------------------------------------------

register_strategy("pipeorgan", plan_pipeorgan, Topology.AMP,
                  supports_sim_check=True, supports_objective=True,
                  supports_engine=True)
register_strategy("pipeorgan-linear", plan_pipeorgan_linear, Topology.AMP,
                  supports_sim_check=True, supports_objective=True,
                  supports_engine=True)
register_strategy("pipeorgan-uniform", plan_pipeorgan_uniform, Topology.AMP,
                  supports_objective=True, supports_engine=True)
register_strategy("tangram", plan_tangram_like, Topology.MESH)
register_strategy("simba", plan_simba_like, Topology.MESH)
register_strategy("layerbylayer", plan_layer_by_layer, Topology.MESH,
                  takes_topology=False)

# the DP's memoization layers, published through the public cache registry
# (consumed by Planner.cache_info_all; plugins register alongside)
register_cache("place", lambda: tuple(_cached_place.cache_info()))
register_cache("pair_traffic", lambda: tuple(_pair_traffic.cache_info()))
# the route-incidence table cache lives in noc.py, which sits below
# plan_api in the import DAG — registered here like flow_batch is from
# the facade module
register_cache("route_incidence", route_incidence_cache_info)
# the span cache's memory tier; the persistent tier ("span_shelf")
# registers on set_span_shelf and unregisters on removal
register_cache("span_cache", span_cache_info)


def _jax_price_cache_info() -> Tuple[int, int, Optional[int], int]:
    """The jax engine's jitted-callable cache, read through ``sys.modules``
    so merely *listing* caches never forces the jax import."""
    mod = sys.modules.get((__package__ or "repro.core") +
                          ".pipeline_model_jax")
    if mod is None or not mod.is_available():
        return (0, 0, None, 0)
    return mod.price_cache_info()


register_cache("jax_price", _jax_price_cache_info)


class _StrategiesView(collections.abc.Mapping):
    """Read-only ``name -> plan function`` view over the strategy
    registry, kept for backward compatibility with the old module-level
    ``STRATEGIES`` dict; new code should use ``plan_api.get_strategy`` /
    ``register_strategy``."""

    def __getitem__(self, name: str):
        from .plan_api import get_strategy
        try:
            return get_strategy(name).fn
        except ValueError:
            raise KeyError(name) from None   # Mapping contract: 'in'/.get()

    def __iter__(self):
        from .plan_api import strategy_names
        return iter(strategy_names())

    def __len__(self) -> int:
        from .plan_api import strategy_names
        return len(strategy_names())


STRATEGIES = _StrategiesView()
