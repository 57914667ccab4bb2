"""The decode step's KV cache: its stored form, the in-place update
(donated by ``ServeEngine``, carried through the layer scan, one scatter
per array a layer), and that it leaves the math unchanged; for latent
attention (MLA) the cache is each position's latent and rotary key
part, read by the absorbed decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import decode_attention
from repro.models import decode_step, forward, init_cache, init_model, layers
from repro.runtime.serve_loop import Request, ServeEngine

KEY = jax.random.PRNGKey(0)


def _config(name):
    if name == "dense":
        return get_config("qwen2.5-3b", smoke=True)
    if name == "moe":
        return get_config("granite-moe-1b-a400m", smoke=True)
    if name == "mla":
        # in float32: the absorbed decode and forward's expanded form
        # round differently in bf16, which flips near-ties of the routing
        return dataclasses.replace(get_config("moonlight-16b-a3b",
                                              smoke=True), dtype=jnp.float32)
    # the int8 cache in float32: in bf16 its rounding flips near-ties of
    # a random model's logits, here it leaves the greedy tokens alone
    return dataclasses.replace(get_config("qwen2.5-3b", smoke=True),
                               kv_quant=True, dtype=jnp.float32)


CONFIGS = ["dense", "moe", "kv_quant", "mla"]


def _token_axis(key):
    """The position axis of a cache array: the rotary key part of a
    latent cache is stored position-minor."""
    return 3 if key == "k_pe" else 2


@pytest.fixture(scope="module")
def models():
    out = {}
    for name in CONFIGS:
        cfg = _config(name)
        out[name] = cfg, init_model(KEY, cfg)
    return out


@pytest.mark.parametrize("name", CONFIGS)
def test_cache_form(name):
    cfg = _config(name)
    L, B, T = cfg.n_layers, 3, 16
    cache = jax.eval_shape(lambda: init_cache(cfg, B, T))
    if cfg.kv_lora_rank:
        assert set(cache) == {"c_kv", "k_pe"}
        assert cache["c_kv"].shape == (L, B, T, cfg.kv_lora_rank)
        assert cache["k_pe"].shape == (L, B, cfg.qk_rope_dim, T)
        assert cache["c_kv"].dtype == cache["k_pe"].dtype == cfg.dtype
        return
    kv = (L, B, T, cfg.n_kv_heads * cfg.hd)
    assert cache["k"].shape == cache["v"].shape == kv
    if cfg.kv_quant:
        assert cache["k"].dtype == jnp.int8
        assert (cache["k_scale"].shape == cache["v_scale"].shape
                == (L, B, T, cfg.n_kv_heads))
    else:
        assert set(cache) == {"k", "v"}


@pytest.mark.parametrize("name", CONFIGS)
def test_step_writes_each_slot_at_its_cursor(models, name):
    """One step with per-slot cursors writes every layer's row at
    (slot, cursor) and nothing else."""
    cfg, params = models[name]
    B, T = 3, 8
    cursor = np.array([5, 0, 2], np.int32)
    cache = init_cache(cfg, B, T)
    _, new = decode_step(params, cfg, jnp.array([[3], [5], [7]], jnp.int32),
                         cache, jnp.asarray(cursor))
    for key, a in new.items():
        assert a.shape == cache[key].shape and a.dtype == cache[key].dtype
        a = jnp.moveaxis(a, _token_axis(key), -1)
        written = np.asarray(jnp.any(a != 0, axis=-2))       # (L, B, T)
        want = np.zeros((B, T), bool)
        want[np.arange(B), cursor] = True
        for layer in range(cfg.n_layers):
            np.testing.assert_array_equal(written[layer], want, key)


@pytest.mark.parametrize("name", CONFIGS)
def test_engine_step_donates_the_cache(models, name):
    cfg, params = models[name]
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=16)
    eng.submit(Request(rid=0, prompt=[3, 4], max_new_tokens=2))
    eng.step()
    before = jax.tree.leaves(eng.cache)
    eng.step()
    assert all(a.is_deleted() for a in before)
    after = jax.tree.leaves(eng.cache)
    assert not any(a.is_deleted() for a in after)
    assert [a.shape for a in after] == [a.shape for a in before]


def _greedy(params, cfg, prompt, n):
    """The request alone, greedy through the full-sequence forward."""
    seq, out = list(prompt), []
    for _ in range(n):
        logits, _ = forward(params, cfg, jnp.asarray([seq], jnp.int32),
                            remat=False)
        out.append(int(jnp.argmax(logits[0, -1])))
        seq.append(out[-1])
    return out


def _serves_greedy_tokens(cfg, params):
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=16)
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=[int(t) for t in rng.integers(
        0, cfg.vocab, 1 + i % 4)], max_new_tokens=3 + i % 2)
        for i in range(5)]
    for r in reqs:
        eng.submit(r)
    done = {r.rid: r.output for r in eng.run()}
    assert eng.stats()["wipes"] >= 3
    for r in reqs:
        assert done[r.rid] == _greedy(params, cfg, r.prompt,
                                      r.max_new_tokens), r.rid


@pytest.mark.parametrize("name", CONFIGS)
def test_engine_tokens_equal_greedy_forward(models, name):
    """More requests than slots, so slots are wiped and refilled: each
    request's tokens are those of greedy decoding it alone through
    ``forward``.  Sequences stay within 8 tokens, where the MoE layer's
    prefill capacity drops none."""
    _serves_greedy_tokens(*models[name])


@pytest.fixture
def kernel_read(monkeypatch):
    """The one-token rows' cache read through the decode-attention kernel
    (interpret mode), as on a TPU, in blocks of 4 positions."""
    monkeypatch.setattr(decode_attention, "BLOCK_T", 4)
    monkeypatch.setattr(layers, "_read_in_place",
                        lambda kernel, einsum: kernel(interpret=True))


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_kernel_read_serves_the_greedy_tokens(models, kernel_read, name):
    """As ``test_engine_tokens_equal_greedy_forward``, with the cache read
    in place by the kernel: cursors run past the first block."""
    _serves_greedy_tokens(*models[name])


def test_kv_read_share_counts_the_blocks_read(models):
    """Two slots, one prefilling 17 tokens one a tick and the other idle
    at cursor 0, over 64 positions in blocks of 16: 17 ticks read 35 of
    136 blocks a layer.  The einsum path reads every row."""
    cfg, params = models["dense"]

    def share(read_block):
        eng = ServeEngine(params, cfg, batch_slots=2, max_len=64, chunk=0)
        eng.read_block = read_block    # as the engine sets it on a TPU
        eng.submit(Request(rid=0, prompt=list(range(1, 18)),
                           max_new_tokens=1))
        eng.run()
        assert eng.stats()["ticks"] == 17
        return eng.stats()["kv_read_share"]

    assert share(16) == 35 / 136
    assert share(None) == 1.0
