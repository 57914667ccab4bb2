"""Latent attention (MLA) with an absorbed decode over a latent cache, and
the expert layer's routing variants, chip share and shared experts, at a
small size on the CPU (moonlight-16b-a3b's smoke config)."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import cache_shardings, params_shardings
from repro.models import decode_step, forward, init_cache, init_model
from repro.models.layers import _route, init_moe, moe_ffn, swiglu

KEY = jax.random.PRNGKey(0)


def _smoke(**fields):
    return dataclasses.replace(get_config("moonlight-16b-a3b", smoke=True),
                               **fields)


def _digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


def test_smoke_config_has_every_mechanism():
    cfg = get_config("moonlight-16b-a3b", smoke=True)
    assert cfg.kv_lora_rank and cfg.qk_rope_dim and cfg.n_shared_experts
    assert cfg.n_dense_layers == 1 and cfg.n_layers == 3
    assert cfg.router == "sigmoid" and cfg.ep_size == 2
    assert cfg.experts_held == 4 and not cfg.tie_embeddings
    params = jax.eval_shape(lambda: init_model(KEY, cfg))
    assert params["dense_layers"]["mlp"]["w_gate"].shape == (1, 64, 96)
    assert params["layers"]["moe"]["w_gate"].shape == (2, 4, 64, 32)
    assert params["layers"]["moe"]["router"].shape == (2, 64, 8)
    assert params["layers"]["moe"]["shared"]["w_up"].shape == (2, 64, 32)


def test_published_config():
    cfg = get_config("moonlight-16b-a3b")
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.d_model, cfg.n_heads,
            cfg.d_ff, cfg.moe_d_ff) == (27, 1, 2048, 16, 11264, 1408)
    assert (cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim,
            cfg.v_head_dim) == (512, 64, 128, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.experts_held,
            cfg.n_shared_experts) == (64, 6, 8, 2)
    assert cfg.routed_scaling == 2.446 and cfg.vocab == 163840
    # one chip's share: 83.0 M (dense layer) + 26 x 100.4 M + 671 M, less
    # the routers, their biases and the norms, which the count leaves out
    assert cfg.n_params() == 3_361_093_120
    shapes = jax.eval_shape(lambda: init_model(KEY, cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3_364_615_296


def test_absorbed_decode_equals_the_expanded_form():
    """In float32 the decode step, which reads scores and values off the
    cached latents, gives the logits of ``forward``, which expands the
    latent to per-head keys and values, at every position of two rows.
    Eight tokens: the prefill's expert capacity (8 rows) drops none."""
    cfg = _smoke(dtype=jnp.float32)
    params = init_model(KEY, cfg)
    toks = jax.random.randint(jax.random.fold_in(KEY, 1), (2, 8), 0,
                              cfg.vocab)
    full, _ = forward(params, cfg, toks, remat=False)
    cache = init_cache(cfg, 2, 8)
    step = jax.jit(lambda c, t, i: decode_step(params, cfg, t, c, i))
    outs = []
    for i in range(8):
        logits, cache = step(cache, toks[:, i:i + 1], jnp.int32(i))
        outs.append(logits[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full), rtol=1e-4, atol=1e-4)


def test_decode_never_expands_the_latent_over_the_cache():
    """No array of the decode step's jaxpr has both the cache-length
    axis and a per-head key or value axis.  Every size is distinct, so a
    shape names its axes."""
    cfg = _smoke(d_model=40, n_heads=3, n_kv_heads=3, head_dim=11,
                 qk_nope_dim=11, v_head_dim=13, qk_rope_dim=6,
                 kv_lora_rank=17, d_ff=44, moe_d_ff=12, vocab=300)
    T, B = 37, 2
    H, nope, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
    per_head = {nope, vd, nope + vd, H * nope, H * vd, H * (nope + vd)}
    params = jax.eval_shape(lambda: init_model(KEY, cfg))
    cache = jax.eval_shape(lambda: init_cache(cfg, B, T))
    jaxpr = jax.make_jaxpr(lambda p, c: decode_step(
        p, cfg, jnp.zeros((B, 1), jnp.int32), c,
        jnp.zeros((B,), jnp.int32)))(params, cache)

    def shapes(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                yield tuple(getattr(v.aval, "shape", ()))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    seen = list(shapes(jaxpr.jaxpr))
    assert any(T in s and cfg.kv_lora_rank in s for s in seen)
    bad = [s for s in seen if T in s and per_head & set(s)]
    assert not bad, bad


def test_kv_quant_is_refused_with_latent_attention():
    with pytest.raises(ValueError, match="latent"):
        init_cache(_smoke(kv_quant=True), 2, 8)


# -- the expert layer ---------------------------------------------------------

def _moe(cfg, seed=3):
    p = init_moe(jax.random.PRNGKey(seed), cfg)
    if "router_bias" in p:
        p["router_bias"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(seed + 1), p["router_bias"].shape)
    return p


def test_correction_bias_chooses_but_does_not_weigh():
    """The bias moves which experts are chosen; the gates are the chosen
    experts' sigmoid scores, renormalised, times ``routed_scaling``."""
    cfg = _smoke(dtype=jnp.float32)
    p = _moe(cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, cfg.d_model))
    idx, gate, _ = _route(p, x, cfg)
    idx0, _, _ = _route({**p, "router_bias": jnp.zeros_like(
        p["router_bias"])}, x, cfg)
    assert not np.array_equal(np.sort(idx, -1), np.sort(idx0, -1))
    scores = jax.nn.sigmoid(x @ p["router"])
    want = jnp.take_along_axis(scores, idx, -1)
    want = want / want.sum(-1, keepdims=True) * cfg.routed_scaling
    np.testing.assert_allclose(np.asarray(gate), np.asarray(want),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gate.sum(-1)),
                               cfg.routed_scaling, rtol=1e-6)
    biased = jnp.take_along_axis(scores + p["router_bias"], idx, -1)
    assert not np.allclose(np.asarray(gate), np.asarray(
        biased / biased.sum(-1, keepdims=True) * cfg.routed_scaling))


def test_shares_add_up_to_the_uncut_layer():
    """Each share's layer holds its experts and routes over all of them:
    the shares' outputs, with the shared experts counted once, add up to
    the layer that holds every expert (``ep_size`` 1).  One token a row,
    as at decode, where no expert's capacity is reached."""
    whole = _smoke(dtype=jnp.float32, ep_size=1)
    p = _moe(whole)
    x = jax.random.normal(jax.random.PRNGKey(8), (6, 1, whole.d_model))
    want, _ = moe_ffn(p, x, whole)
    cut = _smoke(dtype=jnp.float32)
    n = cut.experts_held
    parts = []
    for share in range(cut.ep_size):
        ps = {k: (v[share * n:(share + 1) * n]
                  if k in ("w_gate", "w_up", "w_down") else v)
              for k, v in p.items()}
        parts.append(moe_ffn(ps, x, cut, expert_offset=share * n)[0])
    got = sum(parts) - (cut.ep_size - 1) * swiglu(p["shared"], x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(parts[0]), np.asarray(want),
                           atol=1e-3)


#: granite's ``moe_ffn`` (softmax routing, no bias, no shared experts,
#: every expert held) on fixed inputs, as the softmax-only layer computed
#: it before the routing variants and the chip share: sha256 of the
#: bfloat16 output's bytes, and the aux loss (a float32 mean, whose last
#: bits follow the CPU backend's threading)
GRANITE_MOE = {
    (2, 32): ("650e87595400e93c7ec08b4bda3415345f940d44681525a281df9448c7265791",
              1.0012810230255127),
    (8, 1): ("6583bcceb8c2c1364b7f1c015b1f706ca1dab4a1fc36c562899fe45c313b17dd",
             1.0867621898651123),
}


@pytest.fixture
def x32():
    """As in a serving process: a planner test earlier in this worker may
    have turned x64 on, which changes the seeded inputs."""
    with jax.enable_x64(False):
        yield


@pytest.mark.parametrize("shape", list(GRANITE_MOE), ids=str)
def test_granite_moe_is_bitwise_unchanged(shape, x32):
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    p = init_moe(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), shape + (cfg.d_model,)
                          ).astype(cfg.dtype)
    y, aux = moe_ffn(p, x, cfg)
    digest, want_aux = GRANITE_MOE[shape]
    assert _digest(y) == digest
    assert float(aux) == pytest.approx(want_aux, rel=1e-6)


#: the smoke configs' forward logits, decode logits over six steps and
#: final cache, as the single-stack model computed them (sha256)
SINGLE_STACK = {
    "granite-moe-1b-a400m": (
        "56f488cde417d465ddc2c51f5487ee3147db436de7461a4dba353216e4bcebf6",
        "d3264d602fc215c99333f83c31691d129bbc0a47daf2e77b413f6e68703bc1c5",
        "ff577ec09461e88b6adb1cf0b4f4a78fb951a6f264bfcb4d29d19cfa084dc441"),
    "qwen2.5-3b": (
        "a37eddbd06819d29d9c688ed44ce830c0038a656c70d1d6bbf1460e0cbffdb6d",
        "0ccef5450465cfe67124f74d477169db34a235de314c31937fbefac784bf7224",
        "ce64c858cf8aaf0d64633c1e0d3be9750ed235b1ef394bb2c2e196264f17286f"),
}


@pytest.mark.parametrize("arch", list(SINGLE_STACK))
def test_single_stack_models_are_bitwise_unchanged(arch, x32):
    cfg = get_config(arch, smoke=True)
    params = init_model(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(5), (3, 6), 0, cfg.vocab)
    logits, _ = forward(params, cfg, toks)
    cache = init_cache(cfg, 3, 8)
    step = jax.jit(lambda p, t, c, i: decode_step(p, cfg, t, c, i))
    outs = []
    for i in range(6):
        lg, cache = step(params, toks[:, i:i + 1], cache,
                         jnp.full((3,), i, jnp.int32))
        outs.append(np.asarray(lg))
    got = (_digest(logits), _digest(np.stack(outs)),
           _digest(np.stack([np.asarray(a) for a in jax.tree.leaves(cache)])))
    assert got == SINGLE_STACK[arch]


# -- sharding -----------------------------------------------------------------

def test_latent_cache_and_dense_stack_shardings():
    """The latent cache is shared by the heads: its batch goes over
    "data" and nothing over "model".  The leading dense stack is
    scanned like ``layers``."""
    cfg = get_config("moonlight-16b-a3b", smoke=True)
    mesh = AbstractMesh((2, 4), ("data", "model"))
    cache = jax.eval_shape(lambda: init_cache(cfg, 4, 16))
    specs = {k: s.spec for k, s in cache_shardings(cfg, cache, mesh).items()}
    assert specs == {"c_kv": P(None, "data", None, None),
                     "k_pe": P(None, "data", None, None)}
    params = jax.eval_shape(lambda: init_model(KEY, cfg))
    sh = params_shardings(cfg, params, mesh)
    dense, moe = sh["dense_layers"], sh["layers"]
    assert dense["mlp"]["w_up"].spec == P(None, None, "model")
    assert dense["attn"]["wkv_b"].spec == P(None, None, "model")
    assert moe["moe"]["w_gate"].spec == P(None, "model", "data", None)
    assert moe["moe"]["shared"]["w_down"].spec == P(None, "model", None)
    assert moe["moe"]["router_bias"].spec == P(None, None)
