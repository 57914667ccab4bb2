"""moonlight-16b-a3b in the benchmark: its seeded layout against the
program's tree, the float32 reference (published, non-absorbed form)
against the program's forward and its served tokens, the chip share of
the reference, the router's correction bias, and the decode step's
counts, at a small size on the CPU (``tiny-mla-moe``, the program's
smoke config) and, where only shapes are needed, at the published one."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.counts import mla_moe as counts
from bench.lib import check, serving, weights
from bench.lib.traffic import Req
from bench.reference import mla_moe as ref
from bench.weights import mla_moe as layout_mod

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 5
TINY = json.loads((ROOT / "tests/bench/fixtures/tiny-mla-moe.json")
                  .read_text())
FULL = json.loads((ROOT / "bench/configs/moonlight-16b-a3b.json")
                  .read_text())
LIMITS = json.loads((ROOT / "bench/limits/moonlight-16b-a3b.batch.json")
                    .read_text())


def program_cfg(c, **fields):
    from repro.configs import get_config
    return dataclasses.replace(get_config(c["arch"], smoke=c.get("smoke",
                                                                 False)),
                               **fields)


def within_limits(g):
    """The cell's check: each gap the limits name within its limit (the
    cell limits the mean gap only: the program's widest gaps on the chip
    overlap the fp8 controls', PERF.md section 6)."""
    return all(g[f"{k}_gap"] <= LIMITS[f"{k}_logit_gap"]
               for k in ("max", "mean") if f"{k}_logit_gap" in LIMITS)


def f32_fixture():
    """The tiny fixture with float32 weights, and the program's smoke
    config in float32 as ``serving.build`` looks it up."""
    return {**TINY, "torch_dtype": "float32"}


def use_f32_program(mp):
    import repro.configs
    get = repro.configs.get_config
    mp.setattr(repro.configs, "get_config",
               lambda arch, smoke=False: dataclasses.replace(
                   get(arch, smoke=smoke), dtype=jnp.float32))


def layer_leaves(c, l=0, seed=SEED):
    """Expert layer ``l``'s leaves, prepared as the reference takes them
    (keyed ``layers/...``)."""
    lay = layout_mod.layout(c)
    leaves = weights.make_layer(lay, "layers")(*weights.seed_words(seed),
                                               np.uint32(l))
    return ref.prepare(leaves, "f32")


# -- the seeded layout ---------------------------------------------------

@pytest.mark.parametrize("c", [TINY, FULL], ids=["tiny", "published"])
def test_weights_tree_is_the_programs(c):
    from repro.models import init_model
    lay = layout_mod.layout(c)
    ours = jax.eval_shape(weights.make_tree(lay, weights.stacks(c)),
                          *weights.seed_words(SEED))
    theirs = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0),
                                               program_cfg(c)))
    assert weights.tree_signature(ours) == weights.tree_signature(theirs)
    assert weights.stacks(c) == {"dense_layers": 1,
                                 "layers": c["num_hidden_layers"] - 1}


def test_published_layout_holds_the_chips_share():
    lay = layout_mod.layout(FULL)
    assert lay[("layers", "moe", "w_gate")].shape == (8, 2048, 1408)
    assert lay[("layers", "moe", "router")].shape == (2048, 64)
    assert lay[("layers", "moe", "router_bias")].shape == (64,)
    assert lay[("layers", "moe", "shared", "w_up")].shape == (2048, 2816)
    assert lay[("dense_layers", "mlp", "w_up")].shape == (2048, 11264)
    assert lay[("layers", "attn", "wkv_a")].shape == (2048, 576)
    assert lay[("layers", "attn", "wkv_b")].shape == (512, 4096)
    assert lay[("unembed",)].shape == (2048, 163840)


def test_program_keys_are_the_files():
    keys = serving.program_keys(FULL)
    assert keys == {"n_experts": 64, "kv_lora_rank": 512, "qk_rope_dim": 64,
                    "qk_nope_dim": 128, "v_head_dim": 128, "moe_d_ff": 1408,
                    "n_shared_experts": 2, "n_dense_layers": 1,
                    "router": "sigmoid", "routed_scaling": 2.446,
                    "ep_size": 8}
    cfg = program_cfg(FULL)
    assert {k: getattr(cfg, k) for k in keys} == keys
    with pytest.raises(SystemExit, match="n_group"):
        serving.program_keys({**FULL, "n_group": 8})


def test_stacks_built_whole_equal_their_layers_one_by_one():
    c = TINY
    lay = layout_mod.layout(c)
    lo, hi = weights.seed_words(SEED)
    tree = weights.make_tree(lay, weights.stacks(c))(lo, hi)
    flat = {"/".join(k.key for k in p): v for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    seen = set(weights.make_globals(lay)(lo, hi))
    for stack, n in weights.stacks(c).items():
        for layer in range(n):
            got = weights.make_layer(lay, stack)(lo, hi, np.uint32(layer))
            for k, v in got.items():
                assert np.array_equal(np.asarray(flat[k][layer]),
                                      np.asarray(v)), (k, layer)
            seen |= set(got)
    assert seen == set(flat)
    bias = np.asarray(flat["layers/moe/router_bias"])
    assert np.all(bias != 0) and 0.05 < bias.std() < 0.2


# -- the reference against the program ------------------------------------

def _hidden_logits(c, n):
    fed = np.zeros((check.GROUP, c["serve"]["max_len"]), np.int32)
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], n)
    fed[0, :n] = toks
    lay, mod = check._layout_and_reference(c)
    with jax.default_matmul_precision("highest"):
        xs, head = check._hidden(c, lay, mod, SEED, fed, "f32")
        return toks, np.asarray(xs[0][0, :n] @ head.T)


def _program_params(c, dtype):
    tree = weights.make_tree(layout_mod.layout(c), weights.stacks(c))(
        *weights.seed_words(SEED))
    return jax.tree.map(lambda x: x.astype(jnp.promote_types(x.dtype, dtype)),
                        tree)


def test_reference_is_the_programs_equations():
    """In float32 the program's ``forward`` (the latent expanded, as the
    reference does it) and the reference agree to float32 rounding, the
    dense layer, routing, chip share and shared experts included.  Eight
    tokens: the prefill's expert capacity (8 rows) drops none."""
    from repro.models.transformer import forward
    toks, want = _hidden_logits(TINY, 8)
    cfg = program_cfg(TINY, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(_program_params(TINY, jnp.float32), cfg,
                                 jnp.asarray(toks[None]))[0][0])
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5


def _decode_logits(c, dtype, toks):
    from repro.models.transformer import decode_step, init_cache
    cfg = program_cfg(c, dtype=dtype)
    params = _program_params(c, dtype)
    cache = init_cache(cfg, 1, len(toks))
    step = jax.jit(lambda cache, t, i: decode_step(params, cfg, t, cache, i))
    out = []
    with jax.default_matmul_precision("highest"):
        for i, t in enumerate(toks):
            lg, cache = step(cache, jnp.asarray([[t]], jnp.int32),
                             jnp.int32(i))
            out.append(np.asarray(lg[0, 0], np.float32))
    return np.stack(out)


def test_absorbed_decode_is_the_reference_in_float32():
    """The decode step, absorbed over the latent cache, against the
    reference's expanded form over the whole sequence: float32 rounding
    at each of 60 positions."""
    toks, want = _hidden_logits(TINY, 60)
    got = _decode_logits(TINY, jnp.float32, toks)
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert rel.max() < 1e-5, rel.max()


def test_bf16_decode_is_near_the_reference():
    """As served, in bfloat16: a relative L2 gap of bfloat16 rounding at
    most positions (1-3% at this width).  A position where bfloat16
    rounding flips one of a token's two experts, or that attends to such
    a position's latent, may differ by far more: 8 experts, top-2 and a
    scaling of 2.446 make one expert a large share of the layer here."""
    toks, want = _hidden_logits(TINY, 60)
    got = _decode_logits(TINY, jnp.bfloat16, toks)
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert np.median(rel) < 0.05, rel
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.8


def _serve(c, seed=SEED):
    """Six requests of 20-45 prompt tokens and 12-24 answer tokens through
    two slots of the program's ``ServeEngine``, so four are served in a
    slot that an earlier request used; (prompt, served) in rid order."""
    c = {**c, "serve": {"slots": 2, "max_len": 256}}
    engine = serving.build(c, layout_mod.layout(c), seed)
    rec = serving.Recorder(engine)
    rng = np.random.default_rng(4)
    for i in range(6):
        rec.submit(Req(i, 0.0, rng.integers(0, c["vocab_size"],
                                            int(rng.integers(20, 46))
                                            ).tolist(),
                       int(rng.integers(12, 25))), 0.0)
    while not rec.idle():
        rec.tick()
    done = sorted(rec.finished, key=lambda q: q.rid)
    assert sorted(q.admitted for q in done)[2] > min(q.admitted for q in done)
    return c, check.sequences(done)


@pytest.fixture(scope="module")
def served_f32():
    """Served in float32, where the program's tokens are the reference's
    argmax but for float32 rounding."""
    with pytest.MonkeyPatch.context() as mp:
        use_f32_program(mp)
        return _serve(f32_fixture())


def test_served_tokens_through_refilled_slots(served_f32):
    """Prefill token by token and decoding through the latent cache, in
    slots refilled after earlier requests, read against the reference
    with the cell's check: every served token compared, both gaps within
    the cell's limits (float32: no gap at all)."""
    c, seqs = served_f32
    g = check.logit_gaps(c, SEED, seqs)["f32"]
    assert g["positions"] == sum(len(o) for _, o in seqs)
    assert within_limits(g) and g["max_gap"] < 1e-4, g


def test_bias_in_the_gates_is_caught(served_f32, monkeypatch):
    """A reference that weighs the chosen experts by score plus bias, as
    it chooses them, reads the float32 program's logits a hundred times
    further off than float32 rounding, and some served tokens are not
    its argmax."""
    c, seqs = served_f32

    def biased(p, h, c):
        choice = (jax.nn.sigmoid(h @ p["layers/moe/router"])
                  + p["layers/moe/router_bias"])
        top, _ = jax.lax.top_k(choice, c["num_experts_per_tok"])
        g = jnp.where(choice >= top[..., -1:], choice, 0.0)
        return g / jnp.sum(g, -1, keepdims=True) * c["routed_scaling_factor"]

    toks, _ = _hidden_logits(TINY, 60)
    got = _decode_logits(TINY, jnp.float32, toks)
    monkeypatch.setattr(ref, "gates", biased)
    _, want = _hidden_logits(TINY, 60)
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert np.median(rel) > 1e-3, rel
    g = check.logit_gaps(c, SEED, seqs)["f32"]
    assert g["disagree"] > 0 and g["max_gap"] > 1e-2, g


# -- the chip's share ------------------------------------------------------

def test_shares_add_up_to_the_uncut_reference():
    """Over offsets 0 .. ep_size - 1, the routed parts of the program's
    expert layer (and of the reference's), with the shared experts
    counted once, add up to the reference's layer that holds every
    expert (``ep_size`` 1), on the same seeded weights."""
    from repro.models.layers import moe_ffn
    whole = {**TINY, "ep_size": 1}
    p = layer_leaves(whole)
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 7, TINY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        want = ref.ffn(p, h, whole, "f32")
        shared = ref.dense.swiglu(p["layers/moe/shared/w_gate"],
                                  p["layers/moe/shared/w_up"],
                                  p["layers/moe/shared/w_down"], h, "f32")
        n = TINY["n_routed_experts"] // TINY["ep_size"]
        cfg = program_cfg(TINY, dtype=jnp.float32)
        prog = {k.split("/", 2)[2]: v for k, v in p.items()
                if k.startswith("layers/moe/")}
        prog["shared"] = {k.split("/")[-1]: v for k, v in p.items()
                          if k.startswith("layers/moe/shared/")}
        ours, theirs = [], []
        for share in range(TINY["ep_size"]):
            cut = slice(share * n, (share + 1) * n)
            ps = {k: (v[cut] if k.startswith("layers/moe/w_") else v)
                  for k, v in p.items()}
            theirs.append(ref.ffn(ps, h, TINY, "f32", offset=share * n))
            ours.append(moe_ffn({**prog, **{k: prog[k][cut] for k in (
                "w_gate", "w_up", "w_down")}}, h, cfg,
                expert_offset=share * n)[0])
    extra = (TINY["ep_size"] - 1) * shared
    for parts in (theirs, ours):
        got = np.asarray(sum(parts) - extra)
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4,
                                   atol=1e-5)
    assert not np.allclose(np.asarray(theirs[0]), np.asarray(want),
                           atol=1e-3)


# -- counts ----------------------------------------------------------------

def test_counts_by_hand():
    # attention a layer: wq 2048 x 3072 + wkv_a 2048 x 576 + wkv_b
    # 512 x 4096 + wo 2048 x 2048 = 13,762,560; dense SwiGLU 69,206,016;
    # an expert 8,650,752; router 131,072
    assert counts._attn_params(FULL) == 13_762_560
    # per token: 27 attentions, one dense layer, 26 x (router + 2 shared
    # + 6/8 of a routed expert), the head over 163,840 rows
    per_token = (27 * 13_762_560 + 69_206_016
                 + 26 * (131_072 + 2.75 * 8_650_752) + 163_840 * 2048)
    assert counts.matmul_params_per_token(FULL) == per_token == 1_398_276_096
    # absorbed attention: 2 x 27 layers x 16 heads x (512 + 64 + 512)
    assert counts.attention_flops(FULL, 1000) == 940_032_000
    assert counts.decode_flops(FULL, 32, 1000) == (2 * per_token * 32
                                                   + 940_032_000)
    # 576 cached values a position a layer, bf16
    assert counts.kv_row_bytes(FULL) == 31_104
    assert counts.experts_read(FULL, 32) == pytest.approx(7.6572, abs=1e-4)
    assert counts.experts_read(FULL, 1) == pytest.approx(0.75)
    w = (2 * 27 * 13_762_560 + 2 * 69_206_016
         + 26 * (4 * (131_072 + 64) + 2 * (2 + 8 * (1 - (58 / 64) ** 32))
                 * 8_650_752) + 2 * 163_840 * 2048)
    assert counts.weight_bytes(FULL, 32) == pytest.approx(w, rel=1e-12)
    assert counts.decode_bytes(FULL, 32, 1000) == pytest.approx(
        w + 31_104 * 1032, rel=1e-12)
    assert counts.decode_bytes(FULL, 0, 0) == 0


def test_parameter_count_is_the_programs():
    from repro.configs import get_config
    from repro.models import init_model
    shapes = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0),
                                               get_config(FULL["arch"])))
    assert counts.params(FULL) == sum(x.size for x in jax.tree.leaves(shapes))
    assert counts.params(FULL) == 3_364_615_296
