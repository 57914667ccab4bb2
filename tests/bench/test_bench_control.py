"""The control of ``correct`` at a size a test run holds: the reference in
float8 (``fp8w``: weights; ``fp8``: weights and matmul inputs) in the
program's place reads a wider gap than the program itself, wide enough
that a limit between the two fails it.  On the chip the same readings,
at the cells' own sizes, come from ``bench/control.py``."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib import check, serving
from bench.lib.traffic import Req
from bench.weights import dense

ROOT = Path(__file__).resolve().parents[2]
# at this size the program's widest gap reads under 0.008 and fp8w's
# over 0.035 (CPU, seeds 21-23 of the backlog mix)
LIMIT = 0.02


@pytest.fixture(scope="module")
def served():
    c = json.loads((ROOT / "tests/bench/fixtures/tiny-dense.json")
                   .read_text())
    engine = serving.build(c, dense.layout(c), 21)
    rec = serving.Recorder(engine)
    rng = np.random.default_rng(3)
    for i in range(8):
        rec.submit(Req(i, 0.0, rng.integers(0, c["vocab_size"],
                                            int(rng.integers(8, 40))).tolist(),
                       int(rng.integers(16, 40))), 0.0)
    while not rec.idle():
        rec.tick()
    seqs = check.sequences(sorted(rec.finished, key=lambda q: q.rid))
    return c, check.logit_gaps(c, 21, seqs, ("f32", "fp8w", "fp8"))


def test_program_reads_under_the_limit(served):
    _, r = served
    assert r["f32"]["positions"] > 150
    assert r["f32"]["max_gap"] < LIMIT


@pytest.mark.parametrize("mode", ["fp8w", "fp8"])
def test_control_fails_the_limit(served, mode):
    _, r = served
    assert r[mode]["max_gap"] > LIMIT
    assert r[mode]["max_gap"] > 3 * r["f32"]["max_gap"]
    assert r[mode]["disagree"] > r["f32"]["disagree"]
