"""``BENCHMARK.json`` against the rules its files follow, and every name
in it against the file that the harness looks up for it."""
import json
import re
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][0] == "python3" and len(BENCH["command"]) <= 32
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_run_seconds_fits_a_check_of_24_cells():
    s = BENCH["run_seconds"]
    assert 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("p", BENCH["paths"])
def test_paths_are_plain_relative_directories(p):
    assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
    assert not p.startswith("/") and ".." not in p.split("/")
    assert (ROOT / p).is_dir()


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            yield group, e


@pytest.mark.parametrize("group,entry", list(_names()),
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_entry_keys_and_names(group, entry):
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}[group]
    assert set(entry) - {"workloads"} == keys
    assert NAME.match(entry["name"])
    for k in ("why", "layer", "source"):
        if k in entry and group in ("configs", "workloads", "per_layer"):
            assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k]
            assert "\t" not in entry[k]
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")


def test_unique_names():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    assert cfg["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    c = json.loads((ROOT / cfg["file"]).read_text())
    assert c["name"] == cfg["name"] and c["source"] == cfg["source"]
    assert c["reduced"] == cfg["reduced"]
    for kind in ("weights", "reference", "counts"):
        assert (ROOT / "bench" / kind / f"{c['arch_kind']}.py").is_file()


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_files(wl):
    assert wl["chips"] in (1, 4)
    assert wl["config"] in {c["name"] for c in BENCH["configs"]}
    mix = json.loads((ROOT / "bench/traffic" / f"{wl['traffic']}.json")
                     .read_text())
    assert (ROOT / "bench/kinds" / f"{mix['kind']}.py").is_file()
    if "driver" in mix:
        assert (ROOT / "bench/drivers" / f"{mix['driver']}.py").is_file()
    limits = json.loads((ROOT / "bench/limits" / f"{wl['name']}.json")
                        .read_text())
    gaps = [k for k in limits if k.endswith("_logit_gap")]
    assert gaps and all(limits[k] > 0 for k in gaps)
    assert set(limits) <= {"max_logit_gap", "mean_logit_gap",
                           "min_tokens_compared"}
    assert limits["min_tokens_compared"] > 0
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if wl["name"] in m.get("workloads", [wl["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(wl["name"] in m.get("workloads", [wl["name"]])
               for m in BENCH["per_layer"])


def test_pairs_of_config_and_traffic_appear_once():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    assert run.reader_path(m["name"]).is_file()


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(m):
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert m["moves"] in E2E
    assert run.reader_path(m["name"]).is_file()
    moved = E2E[m["moves"]]
    for w in m["workloads"]:
        assert w in moved.get("workloads", [w]), (m["name"], w)
    if m["unit"] == "%" and ("roofline" in m["name"] or "mfu" in m["name"]):
        assert m["better"] == "higher"


def test_metrics_split_by_cell_agree():
    """``<metric>.<suffix>`` entries share one reader: they measure one
    quantity in different cells, with one unit, direction, source and
    layer, each moving its cells' end-to-end metric."""
    groups = {}
    for m in BENCH["per_layer"]:
        groups.setdefault(run.reader_path(m["name"]), []).append(m)
    for path, ms in groups.items():
        same = {(m["unit"], m["better"], m["source"], m["layer"]) for m in ms}
        assert len(same) == 1, (path, same)
        cells = [w for m in ms for w in m["workloads"]]
        assert len(cells) == len(set(cells)), (path, cells)


def test_a_metric_without_a_reader_is_refused():
    with pytest.raises(SystemExit):
        run.reader_path("no_such_metric.chat")
