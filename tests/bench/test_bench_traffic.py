"""The traffic generator: each mix file gives the same requests for the
same seed, and every seed the same work in another order."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib import traffic

ROOT = Path(__file__).resolve().parents[2]
MIXES = sorted(p.stem for p in (ROOT / "bench/traffic").glob("*.json"))
BIG = 2**31 + 12345


def mix(name):
    return json.loads((ROOT / "bench/traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.take(mix(name), BIG, 151936, 150)
    b = traffic.take(mix(name), BIG, 151936, 150)
    assert [(r.due_s, r.prompt, r.max_new) for r in a] == \
        [(r.due_s, r.prompt, r.max_new) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    m = mix(name)
    n = m.get("block", 64) * 2
    a = traffic.take(m, 1, 1000, n)
    b = traffic.take(m, 2**40 + 7, 1000, n)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[-1].due_s == pytest.approx(b[-1].due_s)


@pytest.mark.parametrize("name", MIXES)
def test_sizes_follow_the_mix(name):
    m = mix(name)
    reqs = traffic.take(m, 3, 4096, m.get("block", 64))
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    for v, spec in ((p, m["prompt"]), (o, m["output"])):
        assert v.min() >= spec["min"] and v.max() <= spec["max"]
        assert abs(np.median(v) - spec["median"]) <= 0.05 * spec["median"]
    assert all(0 <= t < 4096 for r in reqs for t in r.prompt)
    due = np.array([r.due_s for r in reqs])
    assert np.all(np.diff(due) >= 0)
    if m.get("rate_per_s"):
        # stratified Poisson gaps: a block's mean gap is 1/rate to 2%
        assert due[-1] / len(due) == pytest.approx(1 / m["rate_per_s"],
                                                    rel=0.02)
    else:
        assert due[-1] == 0


def test_open_loop_rate_is_a_number():
    m = mix("chat")
    assert isinstance(m["rate_per_s"], float) and m["rate_per_s"] > 0
