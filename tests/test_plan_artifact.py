"""Plan artifacts: lossless round-trip, schema gating, the PlanStore, and
the Planner's read-through of a store (zero strategy invocations on a
warm store)."""
import dataclasses
import json

import pytest

from repro.configs.xrbench import all_tasks
from repro.core import (PAPER_HW, PLAN_SCHEMA_VERSION, PlanArtifact,
                        PlanRequest, PlanSchemaError, PlanStore, Planner,
                        Topology, get_planner, min_dram, plan_diffs)

HW = PAPER_HW


def _request(task: str) -> PlanRequest:
    return PlanRequest(all_tasks()[task], hw=HW, topology=Topology.AMP)


# ---------------------------------------------------------------------------
# round trip: every golden plan, field-identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("task", sorted(all_tasks()))
def test_golden_plan_roundtrips_field_identical(task):
    """Every golden XR-bench plan survives save -> load with PlanResult
    field-identical — ops, dataflows, granularities, placement grids, NoC
    stats, costs, and the branch metadata (``edges`` slot DAG + branch
    groups) included."""
    request = _request(task)
    plan = get_planner().plan(request)
    art = PlanArtifact.from_plan(plan, request)
    loaded = PlanArtifact.from_json(art.to_json())
    assert plan_diffs(plan, loaded.plan) == []
    assert loaded.token == request.cache_token()
    assert loaded.schema_version == PLAN_SCHEMA_VERSION
    # branch metadata explicitly: same slot DAGs and branch groups
    assert [s.edges for s in loaded.plan.segments] == \
        [s.edges for s in plan.segments]
    assert [s.branches for s in loaded.plan.segments] == \
        [s.branches for s in plan.segments]


def test_roundtrip_covers_branch_segments():
    """The suite must actually exercise a branch-parallel plan (guards the
    round-trip test against silently losing its hardest case)."""
    plan = get_planner().plan(_request("object_detection"))
    assert any(s.edges for s in plan.segments)


# ---------------------------------------------------------------------------
# schema gating
# ---------------------------------------------------------------------------


def test_schema_version_mismatch_rejected(tmp_path):
    request = _request("keyword_spotting")
    plan = get_planner().plan(request)
    path = PlanArtifact.from_plan(plan, request).save(tmp_path / "p.json")
    doc = json.loads(path.read_text())
    doc["schema_version"] = PLAN_SCHEMA_VERSION + 1
    path.write_text(json.dumps(doc))
    with pytest.raises(PlanSchemaError):
        PlanArtifact.load(path)
    doc["schema_version"] = PLAN_SCHEMA_VERSION
    doc["kind"] = "not-a-plan"
    path.write_text(json.dumps(doc))
    with pytest.raises(PlanSchemaError):
        PlanArtifact.load(path)


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------


def test_store_save_load_scan(tmp_path):
    store = PlanStore(tmp_path)
    req = _request("keyword_spotting")
    plan = get_planner().plan(req)
    assert store.load(req) is None                # cold store: a miss
    path = store.save(req, plan)
    assert path.exists() and len(store) == 1
    got = store.load(req)
    assert plan_diffs(plan, got) == []
    # exact-identity keying: a different objective is a different artifact
    other = dataclasses.replace(req, objective=min_dram())
    assert store.load(other) is None
    scanned = store.scan()
    assert list(scanned) == [req.cache_token()]
    assert scanned[req.cache_token()].request["strategy"] == "pipeorgan"
    hits, misses, _, curr = store.info()
    assert (hits, misses, curr) == (1, 2, 1)


def test_store_rejects_token_mismatch_as_miss(tmp_path):
    """A copied/renamed artifact whose full token does not match the
    request is a miss, not a silent wrong-plan hit (the filename only
    carries a 16-char hash prefix)."""
    store = PlanStore(tmp_path)
    req = _request("keyword_spotting")
    other = dataclasses.replace(req, objective=min_dram())
    store.save(req, get_planner().plan(req))
    store.path_for(req).rename(store.path_for(other))   # wrong identity
    assert store.load(other) is None


def test_read_through_survives_schema_bump(tmp_path):
    """A stale-schema artifact must degrade to a re-plan in the
    read-through consumers (a serving fleet may not die at boot), while
    direct artifact loads stay loudly rejected."""
    store = PlanStore(tmp_path)
    req = _request("keyword_spotting")
    store.save(req, get_planner().plan(req))
    path = store.path_for(req)
    doc = json.loads(path.read_text())
    doc["schema_version"] = PLAN_SCHEMA_VERSION + 1
    path.write_text(json.dumps(doc))
    with pytest.raises(PlanSchemaError):
        store.load(req)                       # direct load: explicit
    planner = Planner(maxsize=4, store=store)
    plan = planner.plan(req)                  # read-through: re-plans
    assert planner.store_hits == 0
    assert plan_diffs(plan, get_planner().plan(req)) == []


def test_planner_reads_through_attached_store(tmp_path):
    """A Planner with a store serves LRU misses from disk instead of
    invoking a strategy."""
    store = PlanStore(tmp_path)
    req = _request("keyword_spotting")
    store.save(req, get_planner().plan(req))
    planner = Planner(maxsize=4, store=store)
    plan = planner.plan(req)
    assert planner.store_hits == 1
    assert plan_diffs(plan, get_planner().plan(req)) == []
    assert planner.plan(req) is plan              # now in the LRU
    assert planner.store_hits == 1
    assert "plan_store" in planner.cache_info_all()
