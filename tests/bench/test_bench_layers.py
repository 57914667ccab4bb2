"""Models whose layers differ: layer stacks of their own lengths, a
reference layer that depends on its index, and an untied output head,
at a small size on the CPU against the program."""
import dataclasses
import hashlib
import importlib.util
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.lib import check, serving, weights
from bench.lib.traffic import Req

ROOT = Path(__file__).resolve().parents[2]
FIX = ROOT / "tests/bench/fixtures"
SEED = 2**33 + 5
# the chat cell's limits: the widest and mean gap a served token may read.
# On the CPU (seeds 2**33 + 5, 11, 12) the untied head reads widest gaps
# 0.018-0.032 and the windowed layers 0-0.0036; a head taken from the
# embedding reads 0.79-0.87, and a reference that takes every layer for
# local 0.48-0.51, for global 0.72-0.76
LIMITS = json.loads((ROOT / "bench/limits/qwen2.5-3b.chat.json").read_text())
# digests of the tiny fixtures' seeded trees as the single-stack
# formulation built them (one ``layers`` stack over num_hidden_layers)
PARENT_DIGESTS = {
    ("tiny-dense", SEED):
        "dd2d71d2519ca6ffb1bcca5aac136d867af65d4cfb2d27dae18f4bc056a0ff91",
    ("tiny-dense", 7):
        "0db027576d697b6771845850efba6a4ad48ab765c27efacc02f19321bd6d3417",
    ("tiny-moe", SEED):
        "49bc171112543c1b71752ba633275ce9dbfc40ad31c029fb427e4eb782c05658",
    ("tiny-moe", 7):
        "af5818e935a816229bccd041770a5038f5a7f53becf559e31acf02f81938eab2",
}


def fixture(name):
    return json.loads((FIX / f"{name}.json").read_text())


def load_module(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tree_digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for p, x in sorted(leaves, key=lambda t: jax.tree_util.keystr(t[0])):
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(p).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def use_arch_kind(mp, kind, weights_file, reference_file):
    """``bench.weights.<kind>`` and ``bench.reference.<kind>`` from the
    fixtures, as if the files were in the benchmark."""
    for pkg, f in (("bench.weights", weights_file),
                   ("bench.reference", reference_file)):
        mp.setitem(sys.modules, f"{pkg}.{kind}",
                   load_module(f"{pkg}.{kind}", FIX / f))


def use_program(mp, **fields):
    """The program's smoke config of the fixture's arch with ``fields``
    replaced, as ``serving.build`` looks it up."""
    import repro.configs
    get = repro.configs.get_config
    mp.setattr(repro.configs, "get_config",
               lambda arch, smoke=False: dataclasses.replace(
                   get(arch, smoke=smoke), **fields))


def serve(c, seed=SEED):
    """Six requests of 20-45 prompt tokens and 12-24 answer tokens served
    through the program's ``ServeEngine``; (prompt, served) in rid order."""
    engine = serving.build(c, weights.layout_module(c).layout(c), seed)
    rec = serving.Recorder(engine)
    rng = np.random.default_rng(4)
    for i in range(6):
        rec.submit(Req(i, 0.0, rng.integers(0, c["vocab_size"],
                                            int(rng.integers(20, 46))
                                            ).tolist(),
                       int(rng.integers(12, 25))), 0.0)
    while not rec.idle():
        rec.tick()
    return check.sequences(sorted(rec.finished, key=lambda q: q.rid))


def within_limits(g):
    return (g["max_gap"] <= LIMITS["max_logit_gap"]
            and g["mean_gap"] <= LIMITS["mean_logit_gap"])


# -- layer stacks ----------------------------------------------------------

def test_two_stacks_built_whole_equal_their_layers_one_by_one():
    c = fixture("tiny-stacked")
    mod = load_module("stacked_layout", FIX / "weights_stacked.py")
    lay, lengths = mod.layout(c), mod.stacks(c)
    assert lengths == {"dense_layers": 1, "layers": 3}
    lo, hi = weights.seed_words(SEED)
    tree = weights.make_tree(lay, lengths)(lo, hi)
    assert tree["dense_layers"]["ln1"].shape == (1, c["hidden_size"])
    assert tree["layers"]["moe"]["w_gate"].shape[0] == 3
    assert tree["unembed"].shape == (c["hidden_size"], 512)
    flat = {"/".join(k.key for k in p): v for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    seen = set(weights.make_globals(lay)(lo, hi))
    for stack, n in lengths.items():
        for layer in range(n):
            got = weights.make_layer(lay, stack)(lo, hi, np.uint32(layer))
            assert got and all(k.startswith(stack + "/") for k in got)
            for k, v in got.items():
                assert np.array_equal(np.asarray(flat[k][layer]),
                                      np.asarray(v)), (k, layer)
            seen |= set(got)
    assert seen == set(flat)
    # layer 0 of each stack draws its own values
    assert not np.array_equal(np.asarray(flat["dense_layers/attn/wq"][0]),
                              np.asarray(flat["layers/attn/wq"][0]))


@pytest.mark.parametrize("name,seed", sorted(PARENT_DIGESTS),
                         ids=lambda v: str(v))
def test_fixture_trees_are_the_single_stack_trees(name, seed):
    c = fixture(name)
    lay = weights.layout_module(c).layout(c)
    tree = weights.make_tree(lay, weights.stacks(c))(
        *weights.seed_words(seed))
    assert tree_digest(tree) == PARENT_DIGESTS[(name, seed)]
    # one number is the length of the one ``layers`` stack
    again = weights.make_tree(lay, c["num_hidden_layers"])(
        *weights.seed_words(seed))
    assert tree_digest(again) == PARENT_DIGESTS[(name, seed)]


def test_layer_order_is_the_stacks_in_turn_or_the_references(monkeypatch):
    c = fixture("tiny-stacked")
    use_arch_kind(monkeypatch, "stacked", "weights_stacked.py",
                  "reference_layered.py")
    ref = sys.modules["bench.reference.stacked"]
    assert check._order(c, ref) == [("dense_layers", 0), ("layers", 0),
                                    ("layers", 1), ("layers", 2)]
    monkeypatch.setattr(ref, "order", lambda c: [
        ("layers", 0), ("dense_layers", 0), ("layers", 2), ("layers", 1)],
        raising=False)
    assert check._order(c, ref)[:2] == [("layers", 0), ("dense_layers", 0)]
    assert check._order(fixture("tiny-dense"), None) == [("layers", 0),
                                                         ("layers", 1)]


# -- the program-config guard ----------------------------------------------

@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe", "tiny-untied"])
def test_the_gqa_kinds_add_no_program_keys(name):
    assert serving.program_keys(fixture(name)) == {}


def test_a_mismatch_on_an_extra_program_key_is_refused(monkeypatch):
    c = fixture("tiny-windowed")
    use_arch_kind(monkeypatch, "windowed", "weights_windowed.py",
                  "reference_layered.py")
    lay = weights.layout_module(c).layout(c)
    assert serving.program_keys(c) == {"local_window": 8, "global_every": 2}
    with pytest.raises(SystemExit, match="local_window"):
        serving.build(c, lay, SEED)                 # the program's 0, 0
    use_program(monkeypatch, local_window=8, global_every=3)
    with pytest.raises(SystemExit, match="global_every"):
        serving.build(c, lay, SEED)


# -- an untied output head -------------------------------------------------

@pytest.fixture(scope="module")
def untied():
    c = fixture("tiny-untied")
    with pytest.MonkeyPatch.context() as mp:
        use_program(mp, tie_embeddings=False)
        return c, serve(c)


def test_untied_head_is_served_and_read(untied):
    c, seqs = untied
    assert ("unembed",) in weights.layout_module(c).layout(c)
    g = check.logit_gaps(c, SEED, seqs)["f32"]
    assert g["positions"] == sum(len(o) for _, o in seqs)
    assert within_limits(g), g


def test_head_taken_from_the_embedding_is_caught(untied, monkeypatch):
    c, seqs = untied
    lay, ref = check._layout_and_reference(c)
    tied = {k: v for k, v in lay.items() if k != ("unembed",)}
    monkeypatch.setattr(check, "_layout_and_reference",
                        lambda c: (tied, ref))
    g = check.logit_gaps(c, SEED, seqs)["f32"]
    assert not within_limits(g), g
    assert g["max_gap"] > 3 * LIMITS["max_logit_gap"]


# -- a reference layer that takes its index --------------------------------

@pytest.fixture(scope="module")
def windowed():
    """The program's windowed layers (layer 0 local over 8 keys, layer 1
    global), on prompts several windows long."""
    c = fixture("tiny-windowed")
    with pytest.MonkeyPatch.context() as mp:
        use_arch_kind(mp, "windowed", "weights_windowed.py",
                      "reference_layered.py")
        use_program(mp, local_window=8, global_every=2)
        return c, serve(c)


def test_reference_with_the_layer_index_agrees(windowed, monkeypatch):
    c, seqs = windowed
    use_arch_kind(monkeypatch, "windowed", "weights_windowed.py",
                  "reference_layered.py")
    g = check.logit_gaps(c, SEED, seqs)["f32"]
    assert g["positions"] == sum(len(o) for _, o in seqs)
    assert within_limits(g), g


def test_reference_that_ignores_the_index_is_caught(windowed, monkeypatch):
    c, seqs = windowed
    use_arch_kind(monkeypatch, "windowed", "weights_windowed.py",
                  "reference_layered.py")
    blind = load_module("blind", FIX / "reference_layered.py")
    layer = blind.layer
    # every layer taken for layer 0, local
    blind.layer = lambda p, x, c, mode: layer(p, x, c, mode, index=0)
    monkeypatch.setitem(sys.modules, "bench.reference.windowed", blind)
    g = check.logit_gaps(c, SEED, seqs)["f32"]
    assert not within_limits(g), g
