"""Per-arch smoke tests (reduced configs): one forward/train step on CPU,
shape + finiteness asserts; decode-vs-prefill consistency; kv-quant."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import (decode_step, encode_frames, forward, init_cache,
                          init_model, loss_fn, whisper_decode_step,
                          whisper_forward, whisper_loss_fn)
from repro.models.layers import _sdpa_chunked
from repro.optim.adamw import AdamWConfig, init_state
from repro.runtime.steps import make_serve_step, make_train_step

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16):
    batch = {"tokens": jnp.ones((B, S), jnp.int32) * 3,
             "labels": jnp.ones((B, S), jnp.int32) * 4}
    if cfg.arch_kind == "vlm":
        batch["patch_embeds"] = jnp.ones((B, cfg.n_patches, cfg.d_model),
                                         jnp.float32) * 0.1
    if cfg.arch_kind == "encdec":
        batch["frames"] = jnp.ones((B, cfg.enc_frames, cfg.d_model),
                                   jnp.float32) * 0.1
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward(arch):
    cfg = get_config(arch, smoke=True)
    params = init_model(KEY, cfg)
    batch = _batch(cfg)
    if cfg.arch_kind == "encdec":
        logits = whisper_forward(params, cfg, batch["frames"],
                                 batch["tokens"])
    else:
        logits, _ = forward(params, cfg, batch["tokens"],
                            patch_embeds=batch.get("patch_embeds"))
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_train_step(arch):
    cfg = get_config(arch, smoke=True)
    params = init_model(KEY, cfg)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    step = make_train_step(cfg, opt_cfg, microbatches=2)
    opt = init_state(params)
    batch = _batch(cfg, B=4, S=16)
    p2, o2, m = jax.jit(step)(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
    assert int(o2.step) == 1
    # params actually changed
    d = jax.tree.map(lambda a, b: float(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)).max()), params, p2)
    assert max(jax.tree.leaves(d)) > 0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_serve_step(arch):
    cfg = get_config(arch, smoke=True)
    params = init_model(KEY, cfg)
    B, T = 2, 32
    step = make_serve_step(cfg)
    if cfg.arch_kind == "encdec":
        frames = jnp.ones((B, cfg.enc_frames, cfg.d_model), jnp.float32)
        cache = {"enc": encode_frames(params, cfg, frames),
                 "k": jnp.zeros((cfg.n_layers, B, T, cfg.n_kv_heads,
                                 cfg.hd), cfg.dtype),
                 "v": jnp.zeros((cfg.n_layers, B, T, cfg.n_kv_heads,
                                 cfg.hd), cfg.dtype)}
    else:
        cache = init_cache(cfg, B, T)
    toks = jnp.ones((B, 1), jnp.int32)
    for i in range(3):
        toks, cache = step(params, toks, cache, jnp.int32(i))
    assert toks.shape == (B, 1)
    assert (np.asarray(toks) < cfg.vocab).all()      # pad vocab masked


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen2.5-3b", "granite-moe-1b-a400m",
                                  "gemma3-4b", "recurrentgemma-2b",
                                  "rwkv6-1.6b"])
def test_decode_matches_prefill(arch):
    """Greedy decode logits at step t == forward logits at position t."""
    cfg = get_config(arch, smoke=True)
    params = init_model(KEY, cfg)
    B, S = 1, 8
    toks = jax.random.randint(jax.random.fold_in(KEY, 9), (B, S), 0,
                              cfg.vocab)
    full_logits, _ = forward(params, cfg, toks)
    cache = init_cache(cfg, B, S)
    outs = []
    for i in range(S):
        lg, cache = decode_step(params, cfg, toks[:, i:i + 1], cache,
                                jnp.int32(i))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec, np.float32), np.asarray(full_logits, np.float32),
        atol=5e-2, rtol=5e-2)


def test_kv_quant_close_to_exact():
    cfg = get_config("qwen2.5-3b", smoke=True)
    cfgq = dataclasses.replace(cfg, kv_quant=True)
    params = init_model(KEY, cfg)
    B, T = 2, 16
    c1, c2 = init_cache(cfg, B, T), init_cache(cfgq, B, T)
    toks = jnp.array([[3], [5]], jnp.int32)
    for i in range(4):
        l1, c1 = decode_step(params, cfg, toks, c1, jnp.int32(i))
        l2, c2 = decode_step(params, cfgq, toks, c2, jnp.int32(i))
    rel = float(jnp.abs(l1.astype(jnp.float32) - l2.astype(jnp.float32)
                        ).max()) / float(jnp.abs(l1.astype(jnp.float32)
                                                 ).max())
    assert rel < 0.05
    assert c2["k"].dtype == jnp.int8


def test_chunked_attention_matches_dense():
    from repro.kernels import ref
    cfg = get_config("gemma3-4b", smoke=True)
    B, S, H, Hkv, hd = 2, 512, 4, 2, 16
    q = jax.random.normal(_fold(1), (B, S, H, hd), jnp.float32)
    k = jax.random.normal(_fold(2), (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(_fold(3), (B, S, Hkv, hd), jnp.float32)
    for window in (0, 64):
        out = _sdpa_chunked(q, k, v, cfg, window, chunk=128)
        G = H // Hkv
        kx = jnp.repeat(k, G, axis=2)
        vx = jnp.repeat(v, G, axis=2)
        exp = ref.attention_ref(
            q.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
            kx.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
            vx.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
            causal=True, window=window)
        exp = exp.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(out, exp, atol=3e-5, rtol=3e-5)


def _fold(i):
    return jax.random.fold_in(KEY, 100 + i)


def test_local_global_pattern():
    from repro.models.transformer import BIG_WINDOW, static_layer_windows
    cfg = get_config("gemma3-4b", smoke=True)     # 6 layers, global every 6
    wins = static_layer_windows(cfg)
    assert wins[5] == BIG_WINDOW
    assert all(w == cfg.local_window for w in wins[:5])


def test_moe_routing_properties():
    """Capacity respected; gates normalized; output finite."""
    from repro.models.layers import init_moe, moe_ffn
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    p = init_moe(KEY, cfg)
    x = jax.random.normal(_fold(7), (2, 32, cfg.d_model)).astype(cfg.dtype)
    y, aux = moe_ffn(p, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert float(aux) >= 0.9   # switch aux loss ~1 for near-uniform routing
