"""Chunked prefill: a prompt written into the cache a chunk per tick, in
the same jitted step as the slots that decode.  The engine with chunks
serves the tokens of the one-token engine and of greedy decoding through
``forward``; a recurrent cache keeps one token a tick; after the
warm-up the bench harness makes, serving compiles nothing new."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import decode_attention
from repro.models import forward, init_model, layers
from repro.models.layers import moe_capacity, moe_ffn
from repro.runtime.serve_loop import Request, ServeEngine

KEY = jax.random.PRNGKey(0)
C = 8            # chunk size of these tests
MAX_LEN = 60     # not a multiple of C: a late chunk runs past the cache


def _config(name):
    if name == "dense":
        return get_config("qwen2.5-3b", smoke=True)
    if name == "moe":
        return get_config("granite-moe-1b-a400m", smoke=True)
    # in float32: the absorbed decode and forward's expanded form round
    # differently in bf16, which flips near-ties of the routing
    return dataclasses.replace(get_config("moonlight-16b-a3b", smoke=True),
                               dtype=jnp.float32)


CONFIGS = ["dense", "moe", "mla"]


@pytest.fixture(scope="module")
def models():
    return {name: (_config(name), init_model(KEY, _config(name)))
            for name in CONFIGS}


def _no_drop(cfg):
    """The config with an expert capacity that drops no token in
    ``forward`` (capacity S + 1 over S tokens); the weights are the
    same."""
    if not cfg.n_experts:
        return cfg
    return dataclasses.replace(cfg,
                               capacity_factor=cfg.n_experts / cfg.top_k)


_forward = jax.jit(forward, static_argnames=("cfg", "remat"))


def _greedy(params, cfg, prompt, n, max_len=MAX_LEN):
    """The request alone, greedy through the full-sequence forward, cut
    where the engine cuts it (at ``max_len - 1`` positions).  The
    sequence is padded to ``max_len``: causal attention keeps the padding
    out of every earlier position's logits, and at the no-drop capacity
    it takes no expert's room."""
    seq, out = np.zeros((1, max_len), np.int32), []
    seq[0, :len(prompt)] = prompt
    n_seq = len(prompt)
    while len(out) < n and (not out or n_seq < max_len):
        logits, _ = _forward(params, _no_drop(cfg), jnp.asarray(seq),
                             remat=False)
        out.append(int(jnp.argmax(logits[0, n_seq - 1])))
        if n_seq < max_len:
            seq[0, n_seq] = out[-1]
        n_seq += 1
    return out


def _serve(params, cfg, reqs, chunk, slots=3, max_len=MAX_LEN):
    eng = ServeEngine(params, cfg, batch_slots=slots, max_len=max_len,
                      chunk=chunk)
    for rid, (prompt, n) in enumerate(reqs):
        eng.submit(Request(rid=rid, prompt=list(prompt), max_new_tokens=n))
    done = {r.rid: r.output for r in eng.run()}
    return [done[rid] for rid in range(len(reqs))], eng


def _prompt(cfg, rng, n):
    return [int(t) for t in rng.integers(0, cfg.vocab, n)]


@pytest.mark.parametrize("name", CONFIGS)
def test_chunks_serve_the_tokens_of_one_token_prefill(models, name):
    """Prompts shorter than a chunk, equal to it, longer and not a
    multiple of it, a multiple, one of a single token, and one whose
    last chunk runs past the cache's end; more requests than slots."""
    cfg, params = models[name]
    rng = np.random.default_rng(1)
    lens = [3, C, 2 * C + 5, 1, 2 * C, MAX_LEN - 3]
    reqs = [(_prompt(cfg, rng, n), 4 + i % 3) for i, n in enumerate(lens)]
    chunked, eng = _serve(params, cfg, reqs, chunk=C)
    one_token, eng1 = _serve(params, cfg, reqs, chunk=0)
    assert chunked == one_token
    assert eng.chunk == C and eng1.chunk == 0
    st = eng.stats()
    assert 0 < st["prefill_chunks"] <= st["chunk_tokens"] <= sum(lens)
    assert eng1.stats()["prefill_chunks"] == 0
    assert st["ticks"] < eng1.stats()["ticks"]
    for (prompt, n), out in zip(reqs, chunked):
        assert out == _greedy(params, cfg, prompt, n), len(prompt)


@pytest.mark.parametrize("name", CONFIGS)
def test_a_slot_refilled_after_a_longer_occupant(models, name):
    """One slot: a long request, then a short one in its wiped slot, whose
    chunk leaves the first occupant's later rows behind its cursor."""
    cfg, params = models[name]
    rng = np.random.default_rng(2)
    reqs = [(_prompt(cfg, rng, 5 * C + 1), 3), (_prompt(cfg, rng, 5), 6)]
    out, eng = _serve(params, cfg, reqs, chunk=C, slots=1)
    assert eng.stats()["wipes"] == 1
    assert out[1] == _greedy(params, cfg, *reqs[1])
    assert out[0] == _greedy(params, cfg, *reqs[0])


def test_a_chunk_rides_beside_decoding_slots(models):
    """A tick carries one slot's chunk while another slot decodes: both
    advance in it, and both serve their greedy tokens."""
    cfg, params = models["dense"]
    rng = np.random.default_rng(3)
    a = Request(rid=0, prompt=_prompt(cfg, rng, 2), max_new_tokens=12)
    b = Request(rid=1, prompt=_prompt(cfg, rng, 2 * C + 3), max_new_tokens=4)
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN, chunk=C)
    eng.submit(a)
    eng.step()                            # a's chunk: its first token
    assert len(a.output) == 1
    eng.submit(b)                         # a fresh slot: nothing to wipe
    eng.step()
    st = eng.stats()
    assert st["prefill_chunks"] == 2 and st["chunk_tokens"] == 2 + C
    assert len(a.output) == 2 and not b.output
    assert list(eng.pos) == [3, C]
    eng.run()
    assert a.output == _greedy(params, cfg, a.prompt, 12)
    assert b.output == _greedy(params, cfg, b.prompt, 4)


def test_other_prefilling_slots_take_one_token_a_tick(models):
    """Two prompts admitted together: the earlier-admitted slot takes the
    chunks until its prompt is in; meanwhile the other takes one prompt
    token a tick in its own row, then the chunk for the rest."""
    cfg, params = models["dense"]
    rng = np.random.default_rng(4)
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN, chunk=C)
    reqs = [Request(rid=rid, prompt=_prompt(cfg, rng, C + 2),
                    max_new_tokens=2) for rid in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    assert list(eng.pos) == [C, 1]
    eng.step()
    assert list(eng.pos) == [C + 2, 2]
    eng.step()
    assert list(eng.pos) == [C + 3, C + 2]
    assert eng.stats()["chunk_tokens"] == C + 2 + C
    eng.run()
    for r in reqs:
        assert r.output == _greedy(params, cfg, r.prompt, 2)


def test_the_chunk_goes_to_the_slot_admitted_earliest(models):
    """A request placed in a lower slot after another slot began its
    prompt waits for that prompt to be in, whatever its slot."""
    cfg, params = models["dense"]
    rng = np.random.default_rng(6)
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN, chunk=C)
    eng.submit(Request(rid=0, prompt=[3], max_new_tokens=1))
    eng.submit(Request(rid=1, prompt=_prompt(cfg, rng, 3 * C),
                       max_new_tokens=1))
    eng.step()                            # rid 0 served whole; slot 0 free
    assert eng.active[0] is None and list(eng.pos) == [1, 1]
    eng.submit(Request(rid=2, prompt=_prompt(cfg, rng, C), max_new_tokens=1))
    eng.step()                            # rid 2 wipes slot 0: no chunk
    assert list(eng.pos) == [1, 2]
    eng.step()
    assert list(eng.pos) == [2, C + 2]
    eng.step()
    eng.step()                            # rid 1's last chunk
    assert list(eng.pos) == [4, 3 * C] and eng.active[1] is None
    eng.step()
    assert list(eng.pos) == [C, 3 * C]


def test_a_tick_that_wipes_carries_no_chunk(models):
    """The wiped slot takes its first prompt token in its own row, and
    the chunk for the rest the tick after."""
    cfg, params = models["dense"]
    eng = ServeEngine(params, cfg, batch_slots=1, max_len=MAX_LEN, chunk=C)
    for rid in range(2):
        eng.submit(Request(rid=rid, prompt=[5, 6, 7], max_new_tokens=1))
    eng.step()                            # rid 0: its chunk, then done
    eng.step()                            # rid 1 wipes the slot
    st = eng.stats()
    assert st["wipes"] == 1 and st["prefill_chunks"] == 1
    assert eng.pos[0] == 1
    eng.step()                            # its chunk, the tick after
    st = eng.stats()
    assert st["prefill_chunks"] == 2 and st["chunk_tokens"] == 3 + 2
    assert eng.pos[0] == 3


def test_an_expert_chunk_drops_no_token(models):
    """A chunk of one repeated token: at the first expert layer every row
    is the same, so all of them pick the same experts, past what
    ``moe_capacity`` gives a sequence of C.  The chunk's capacity of C
    keeps them all, as one-token decoding does."""
    cfg, params = models["moe"]
    chunk = 4 * C
    assert moe_capacity(cfg, chunk) < chunk
    x = jnp.broadcast_to(jax.random.normal(KEY, (cfg.d_model,), cfg.dtype),
                         (1, chunk, cfg.d_model))
    p0 = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    kept = moe_ffn(p0, x, cfg, capacity=chunk)[0]
    dropped = moe_ffn(p0, x, cfg)[0]
    assert not jnp.allclose(kept, dropped)
    np.testing.assert_allclose(np.asarray(kept[0, -1], np.float32),
                               np.asarray(kept[0, 0], np.float32))
    reqs = [([7] * (chunk + 5), 5)]
    out, eng = _serve(params, cfg, reqs, chunk=chunk, slots=2, max_len=64)
    assert eng.stats()["prefill_chunks"] == 2
    assert out == _serve(params, cfg, reqs, chunk=0, slots=2, max_len=64)[0]
    assert out[0] == _greedy(params, cfg, *reqs[0], max_len=64)


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_kernel_read_keeps_chunk_parity(models, monkeypatch, name):
    """With the one-token rows' cache read through the decode-attention
    kernel (interpret mode, blocks of 4 positions, as on a TPU), the
    engine with chunks serves the tokens of the one-token engine and of
    greedy decoding through ``forward``.  The expert model runs in
    float32: the kernel's float32 scores and the einsum's bfloat16 ones
    round differently, which flips near-ties of the routing."""
    monkeypatch.setattr(decode_attention, "BLOCK_T", 4)
    monkeypatch.setattr(layers, "_read_in_place",
                        lambda kernel, einsum: kernel(interpret=True))
    cfg, params = models[name]
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        params = init_model(KEY, cfg)
    rng = np.random.default_rng(2)
    reqs = [(_prompt(cfg, rng, n), 4 + i % 3)
            for i, n in enumerate([3, C, 2 * C + 5, 1, 30])]
    chunked, eng = _serve(params, cfg, reqs, chunk=C, max_len=64)
    assert eng.stats()["prefill_chunks"] > 0
    assert chunked == _serve(params, cfg, reqs, chunk=0, max_len=64)[0]
    for (prompt, n), out in zip(reqs, chunked):
        assert out == _greedy(params, cfg, prompt, n, max_len=64)


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "recurrentgemma-2b"])
def test_a_recurrent_cache_feeds_one_token_a_tick(arch):
    cfg = get_config(arch, smoke=True)
    params = init_model(KEY, cfg)
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=32)
    assert eng.chunk == 0
    eng.submit(Request(rid=0, prompt=[3, 4, 5, 6], max_new_tokens=3))
    eng.run()
    st = eng.stats()
    assert st["prefill_chunks"] == st["chunk_tokens"] == 0
    assert st["ticks"] == 4 + 3 - 1
    assert st["prompt_tokens"] == 3 and st["decode_tokens"] == 3


@pytest.mark.parametrize("name", CONFIGS)
def test_no_compile_after_the_harness_warm_up(models, name):
    """The warm-up ``bench/lib/serving.build`` makes (one-token prompts
    into every slot, then one into a used slot) compiles both shapes of
    the step in two ticks; prompts of 1, C and 3 C + 5 tokens compile
    nothing new."""
    cfg, params = models[name]
    eng = ServeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN, chunk=C)
    for rid in range(eng.B + 1):
        eng.submit(Request(rid=-1 - rid, prompt=[0], max_new_tokens=1))
    eng.run()
    assert eng._step._cache_size() == 2
    assert eng.stats()["ticks"] == 2      # as long as without chunks
    rng = np.random.default_rng(5)
    for rid, n in enumerate([1, C, 3 * C + 5, 1, C]):
        eng.submit(Request(rid=rid, prompt=_prompt(cfg, rng, n),
                           max_new_tokens=3))
    assert len(eng.run()) == 5
    assert eng.stats()["prefill_chunks"] > 0
    assert eng._step._cache_size() == 2
