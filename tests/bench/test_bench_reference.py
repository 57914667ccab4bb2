"""The seeded weights and the float32 references against the program, at
a small size on the CPU."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import check, serving, weights
from bench.weights import dense as dense_layout
from bench.weights import moe as moe_layout

ROOT = Path(__file__).resolve().parents[2]
FIX = ROOT / "tests/bench/fixtures"
LAYOUTS = {"dense": dense_layout, "moe": moe_layout}
SEED = 2**33 + 5


def load(path):
    return json.loads(Path(path).read_text())


def fixture(name):
    return load(FIX / f"{name}.json")


TINY = ["tiny-dense", "tiny-moe"]


def program_cfg(c):
    from repro.configs import get_config
    return get_config(c["arch"], smoke=c.get("smoke", False))


@pytest.mark.parametrize("path", [FIX / "tiny-dense.json",
                                  FIX / "tiny-moe.json",
                                  ROOT / "bench/configs/qwen2.5-3b.json",
                                  ROOT / "bench/configs/"
                                  "granite-moe-1b-a400m.json"],
                         ids=lambda p: p.stem)
def test_weights_tree_is_the_programs(path):
    from repro.models import init_model
    c = load(path)
    lay = LAYOUTS[c["arch_kind"]].layout(c)
    lo, hi = weights.seed_words(SEED)
    ours = jax.eval_shape(weights.make_tree(lay, c["num_hidden_layers"]),
                          lo, hi)
    theirs = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0),
                                               program_cfg(c)))
    assert weights.tree_signature(ours) == weights.tree_signature(theirs)


@pytest.mark.parametrize("name", TINY)
def test_layer_by_layer_weights_equal_the_stacked_tree(name):
    c = fixture(name)
    lay = LAYOUTS[c["arch_kind"]].layout(c)
    lo, hi = weights.seed_words(SEED)
    tree = weights.make_tree(lay, c["num_hidden_layers"])(lo, hi)
    flat = {"/".join(k.key for k in p): v for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    glob = weights.make_globals(lay)(lo, hi)
    for k, v in glob.items():
        assert np.array_equal(np.asarray(flat[k]), np.asarray(v)), k
    for layer in range(c["num_hidden_layers"]):
        got = weights.make_layer(lay)(lo, hi, np.uint32(layer))
        for k, v in got.items():
            assert np.array_equal(np.asarray(flat[k][layer]),
                                  np.asarray(v)), (k, layer)
    other = weights.make_globals(lay)(*weights.seed_words(SEED + 1))
    assert not np.array_equal(np.asarray(other["embed"]),
                              np.asarray(glob["embed"]))
    assert float(jnp.std(glob["embed"].astype(jnp.float32))) == \
        pytest.approx(0.02, rel=0.5)


def _logits(c, dtype, n=40):
    """(program, reference) logits of an n-token prompt: the program's own
    ``forward`` with its weights and activations in ``dtype``."""
    import dataclasses
    from repro.models.transformer import forward
    lay = LAYOUTS[c["arch_kind"]].layout(c)
    params = weights.make_tree(lay, c["num_hidden_layers"])(
        *weights.seed_words(SEED))
    params = jax.tree.map(lambda x: x.astype(jnp.promote_types(x.dtype,
                                                               dtype)),
                          params)
    cfg = dataclasses.replace(program_cfg(c), dtype=dtype)
    toks = np.random.default_rng(0).integers(
        0, c["vocab_size"], (1, n)).astype(np.int32)
    fed = np.zeros((check.GROUP, c["serve"]["max_len"]), np.int32)
    fed[0, :n] = toks[0]
    _, ref_mod = check._layout_and_reference(c)
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(forward(params, cfg, jnp.asarray(toks))[0]
                          [0, :, :c["vocab_size"]], np.float32)
        xs, emb = check._hidden(c, lay, ref_mod, SEED, fed, "f32")
        ref = np.asarray(xs[0][0, :n] @ emb.T)
    return prog, ref


@pytest.mark.parametrize("name", TINY)
def test_reference_is_the_programs_equations(name):
    """In float32 the program's ``forward`` and the reference agree to
    float32 rounding: the same equations, routing included.  Eight tokens,
    because the program's prefill drops tokens past an expert's capacity
    (at least 8 rows), which decoding one token at a time never does."""
    prog, ref = _logits(fixture(name), jnp.float32, n=8)
    assert np.linalg.norm(prog - ref) / np.linalg.norm(ref) < 1e-4


@pytest.mark.parametrize("name", TINY)
def test_reference_logits_match_the_bf16_program(name):
    """The program as served (bfloat16) against the float32 reference:
    a relative L2 gap of bfloat16 rounding, about 1% at most positions.
    A position whose top-k experts flip on rounding may differ more."""
    prog, ref = _logits(fixture(name), jnp.bfloat16)
    per_pos = (np.linalg.norm(prog - ref, axis=-1)
               / np.linalg.norm(ref, axis=-1))
    assert np.median(per_pos) < 0.03, per_pos
    assert (prog.argmax(-1) == ref.argmax(-1)).mean() > 0.8


@pytest.mark.parametrize("name", TINY)
def test_served_tokens_through_refilled_slots(name):
    """Five requests through two slots, so three are served in a slot
    that an earlier request used; each served token is the reference's
    argmax up to bfloat16 rounding."""
    c = fixture(name)
    c = {**c, "serve": {"slots": 2, "max_len": 256}}
    lay = LAYOUTS[c["arch_kind"]].layout(c)
    engine = serving.build(c, lay, SEED)
    rec = serving.Recorder(engine)
    from bench.lib.traffic import Req
    rng = np.random.default_rng(1)
    for i, (p, o) in enumerate([(30, 12), (9, 20), (17, 8), (25, 15),
                                (12, 10)]):
        rec.submit(Req(i, 0.0, rng.integers(0, c["vocab_size"], p).tolist(),
                       o), 0.0)
    while not rec.idle():
        rec.tick()
    assert len(rec.finished) == 5
    admitted = sorted(q.admitted for q in rec.finished)
    assert admitted[2] > admitted[0]          # later requests reuse a slot
    seqs = check.sequences(sorted(rec.finished, key=lambda q: q.rid))
    gaps = check.logit_gaps(c, SEED, seqs)["f32"]
    assert gaps["positions"] == 12 + 20 + 8 + 15 + 10
    assert gaps["max_gap"] < 0.15, gaps
