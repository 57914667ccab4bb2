"""Fused SwiGLU MLP — PipeOrgan's fine-grained inter-op pipelining on TPU.

The paper forwards a producer's output tile to its consumer through the
NoC/register files instead of the global buffer.  The TPU analogue keeps
the (block_t x block_f) intermediate tile of

    out = (silu(x @ W_gate) * (x @ W_up)) @ W_down

resident in VMEM: the two producer GEMMs emit a tile that the consumer
GEMM reduces into the output accumulator immediately — the (T, F)
intermediate never exists in HBM.  Pipeline depth = 3 einsum ops + the
elementwise activation; granularity = one (bt, bf) tile (the Alg. 1
analogue is the BlockSpec); the systolic MXU replaces the PE array, so the
"spatial organization" is the BlockSpec index map.

Grid: (T/bt, F/bf).  The f axis is innermost, so the fp32 accumulator
tile persists in the output ref across the f sweep (revisiting pattern).
Rows are zero-padded to a multiple of ``block_t``.  ``block_f`` is an
upper bound: the f tile is the largest 128-aligned one under it that
divides F, so d_ff = 11008 = 43 * 256 (qwen2.5-3b) runs with bf = 256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def fit_block(n: int, bound: int, align: int) -> int:
    """The largest tile <= ``bound`` that divides ``n``: ``n`` itself when
    it fits, else ``bound`` or a multiple of ``align`` below it."""
    if n <= bound:
        return n
    if n % bound == 0:
        return bound
    for b in range(bound - bound % align, 0, -align):
        if n % b == 0:
            return b
    raise ValueError(f"no {align}-aligned tile <= {bound} divides {n}")


def _fused_mlp_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, *, n_f: int):
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                                   # (bt, D)
    g = jnp.dot(x, wg_ref[...],
                preferred_element_type=jnp.float32)  # (bt, bf) producer 1
    u = jnp.dot(x, wu_ref[...],
                preferred_element_type=jnp.float32)  # (bt, bf) producer 2
    h = (jax.nn.silu(g) * u).astype(x.dtype)         # VMEM-resident tile
    # consumer GEMM reads the tile straight from VMEM (no HBM round-trip)
    o_ref[...] += jnp.dot(h, wd_ref[...],
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_t", "block_f",
                                             "interpret"))
def fused_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
              w_down: jax.Array, *, block_t: int = 256, block_f: int = 512,
              interpret: bool = False) -> jax.Array:
    """x: (T, D); w_gate/w_up: (D, F); w_down: (F, D) -> (T, D)."""
    T, D = x.shape
    F = w_gate.shape[1]
    bt = min(block_t, T)
    bf = fit_block(F, block_f, 128)
    pad = -T % bt
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    grid = ((T + pad) // bt, F // bf)

    out = pl.pallas_call(
        functools.partial(_fused_mlp_kernel, n_f=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, D), lambda t, f: (t, 0)),       # x tile
            pl.BlockSpec((D, bf), lambda t, f: (0, f)),       # W_gate col
            pl.BlockSpec((D, bf), lambda t, f: (0, f)),       # W_up col
            pl.BlockSpec((bf, D), lambda t, f: (f, 0)),       # W_down row
        ],
        out_specs=pl.BlockSpec((bt, D), lambda t, f: (t, 0)),  # revisited
        out_shape=jax.ShapeDtypeStruct((T + pad, D), jnp.float32),
        interpret=interpret,
    )(x, w_gate, w_up, w_down)
    return out[:T].astype(x.dtype)
