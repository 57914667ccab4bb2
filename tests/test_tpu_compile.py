"""Compile the main path for a described TPU v5e chip (nothing runs).

The TPU compiler is installed even where no chip is attached, and it
refuses what interpret-mode tests cannot see: misaligned tiles, too much
VMEM, programs that do not fit HBM.  The chip is described inside a
module-scoped fixture, never at import: only one process at a time may
load the TPU library, and every test worker imports this file.
"""
import functools
import inspect
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS, get_config
from repro.kernels.decode_attention import block_t, decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_mlp import fit_block
from repro.kernels.ops import mlp_block
from repro.models import init_model
from repro.models.transformer import (ChunkedTokens, init_cache,
                                      reads_kv_in_place)
from repro.runtime.serve_loop import ServeEngine, jit_serve_step

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    # x64 stays off as in a serving process: a planner test earlier in
    # this worker may have turned it on, and under it the kernels' index
    # maps return i64, which Mosaic refuses.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    with jax.enable_x64(False):
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


#: the served configurations, at their cells' 32 slots x 2048 positions
SERVED = ["qwen2.5-3b", "granite-moe-1b-a400m", "moonlight-16b-a3b"]
#: while loops in the compiled step: the layer scan, and in the MoE layer
#: the routing's searchsorted; none runs over the slots (moonlight's
#: one-layer dense stack compiles to no loop)
WHILE_LOOPS = {"qwen2.5-3b": 1, "granite-moe-1b-a400m": 2,
               "moonlight-16b-a3b": 2}


#: the engine's default prompt chunk
CHUNK = inspect.signature(ServeEngine).parameters["chunk"].default


def _compile_served(one_chip, chunk: bool):
    """ServeEngine's jitted step for each served configuration, compiled
    for one v5e, with the bytes of its cache: the one-token shape, or
    with a prompt chunk (``ChunkedTokens``)."""
    out = {}
    for arch in SERVED:
        cfg = get_config(arch)
        params = _on(one_chip, jax.eval_shape(
            lambda: init_model(jax.random.PRNGKey(0), cfg)))
        cache = _on(one_chip,
                    jax.eval_shape(lambda: init_cache(cfg, 32, 2048)))

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

        tokens = (ChunkedTokens(i32(32, 1), i32(CHUNK), i32(), i32())
                  if chunk else i32(32, 1))
        compiled = jit_serve_step(cfg).lower(
            params, tokens, cache, i32(32)).compile()
        cache_bytes = sum(a.size * a.dtype.itemsize
                          for a in jax.tree.leaves(cache))
        out[arch] = compiled, cache_bytes
    return out


@pytest.fixture(scope="module")
def served_steps(one_chip):
    return _compile_served(one_chip, chunk=False)


@pytest.fixture(scope="module")
def chunk_steps(one_chip):
    return _compile_served(one_chip, chunk=True)


def test_serve_decode_step_fits_one_chip(served_steps):
    """ServeEngine's step at qwen2.5-3b's published widths, 32 slots of
    2048 tokens, compiles for one v5e and fits its 16 GB."""
    compiled, _ = served_steps["qwen2.5-3b"]
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 6 * 10**9     # the bf16 weights
    assert total < V5E_HBM_BYTES, mem


def test_moonlight_decode_step_fits_one_chip(served_steps):
    """moonlight-16b-a3b's share of an 8-way expert-parallel layer (all
    27 layers, 8 of 64 routed experts, both shared, the whole untied
    vocabulary) with its latent cache at 32 slots of 2048 tokens fits
    one v5e's 16 GB."""
    compiled, cache_bytes = served_steps["moonlight-16b-a3b"]
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 6.5e9        # weights and cache
    assert cache_bytes == 27 * 32 * 2048 * 576 * 2   # latent + rotary key
    assert total < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("arch", SERVED)
def test_serve_step_updates_cache_in_place(served_steps, arch):
    """The donated cache is the output's buffer, and no temporary near
    its size stands in for it."""
    compiled, cache_bytes = served_steps[arch]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes, mem
    assert mem.temp_size_in_bytes < 0.3 * 10**9, mem


@pytest.mark.parametrize("arch", SERVED)
def test_serve_step_keeps_cache_unpadded(served_steps, arch):
    """The outputs are the cache at init_cache's bytes and the sampled
    tokens: a head size padded to the tile would double the cache."""
    compiled, cache_bytes = served_steps[arch]
    out = compiled.memory_analysis().output_size_in_bytes
    assert cache_bytes <= out < cache_bytes + 2**20, (out, cache_bytes)


@pytest.mark.parametrize("arch", SERVED)
def test_serve_step_has_no_loop_over_slots(served_steps, arch):
    """Each slot's write is one scatter, not a loop over the slots."""
    compiled, _ = served_steps[arch]
    loops = re.findall(r"= \S.* while\(", compiled.as_text())
    assert len(loops) == WHILE_LOOPS[arch], loops


def _device_bytes(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


@pytest.mark.parametrize("arch", SERVED)
def test_chunk_step_fits_one_chip_in_place(chunk_steps, arch):
    """The step with a prompt chunk of the engine's default size, at 32
    slots of 2048 tokens, fits one v5e's 16 GB, and updates the donated
    cache in place: aliased, the outputs the cache and the samples, no
    temporary near the cache's size."""
    compiled, cache_bytes = chunk_steps[arch]
    mem = compiled.memory_analysis()
    assert CHUNK == 128
    assert _device_bytes(mem) < V5E_HBM_BYTES, mem
    assert mem.alias_size_in_bytes >= cache_bytes, mem
    assert cache_bytes <= mem.output_size_in_bytes < cache_bytes + 2**20
    assert mem.temp_size_in_bytes < 0.3 * 10**9, mem


@pytest.mark.parametrize("shape", ["served_steps", "chunk_steps"])
@pytest.mark.parametrize("arch", SERVED)
def test_decode_rows_read_the_cache_in_place(request, shape, arch):
    """In both step shapes the GQA configurations' one-token rows read the
    stacked cache through the decode-attention kernel, one call in the
    layer scan, and no layer's (1, 32, 2048, Hkv*hd) block is sliced out
    or relaid; the latent cache's step holds no such kernel."""
    compiled, _ = request.getfixturevalue(shape)[arch]
    hlo = compiled.as_text()
    kernel = re.findall(
        r'%decode_attention\S* = .*custom_call_target="tpu_custom_call"', hlo)
    cfg = get_config(arch)
    if cfg.kv_lora_rank:
        assert not kernel
        return
    assert len(kernel) == 1
    width = cfg.n_kv_heads * cfg.hd
    assert not re.findall(rf"%(\S+) = bf16\[1,32,2048,{width}\]", hlo)


#: every configuration whose one-token rows the decode kernel reads
READS_IN_PLACE = [a for a in sorted(ARCHS) if reads_kv_in_place(get_config(a))]


@pytest.mark.parametrize("arch", READS_IN_PLACE)
def test_decode_attention_compiles_at_each_head_layout(one_chip, arch):
    """The decode-attention kernel at each GQA configuration's published
    heads, over 8 slots of 2048 positions, in the block ``block_t`` picks
    (a multi-head 40 x 128 cache takes 32 positions a block, where 512
    would not fit VMEM), lowers to a Mosaic kernel."""
    cfg = get_config(arch)
    width = cfg.n_kv_heads * cfg.hd
    block = block_t(2048, width * jnp.dtype(cfg.dtype).itemsize)

    def shape(*dims, dtype=cfg.dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cache = shape(2, 8, 2048, width)
    compiled = jax.jit(functools.partial(decode_attention, block=block)).lower(
        shape(8, cfg.n_heads, cfg.hd), cache, cache, shape(dtype=jnp.int32),
        shape(8, dtype=jnp.int32), shape(8, dtype=jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tokens", [37, 300])
def test_fused_mlp_compiles_at_qwen_widths(one_chip, tokens):
    """The model path's fused SwiGLU at D=2048, F=11008: the tile it
    picks (bf = 256; the old fixed 512 does not divide 11008), with rows
    whole (37) or padded to the 256-row tile (300), lowers to a Mosaic
    kernel."""
    D, F = 2048, 11008
    assert fit_block(F, 512, 128) == 256
    x = jax.ShapeDtypeStruct((1, tokens, D), jnp.bfloat16, sharding=one_chip)
    w_in = jax.ShapeDtypeStruct((D, F), jnp.bfloat16, sharding=one_chip)
    w_out = jax.ShapeDtypeStruct((F, D), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: mlp_block(*a, use_pallas=True, interpret=False)
    ).lower(x, w_in, w_in, w_out).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((32, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention(q, k, v)).lower(
        q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()
