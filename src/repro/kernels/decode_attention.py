"""Decode attention over the stacked KV cache, read in place (TPU Pallas).

A serve step's one-token rows attend over the cache that the decode step
carries through its layer scan, ``(L, B, T_max, Hkv*hd)``.  Sliced out
per layer and handed to an einsum, each layer's block of every slot and
all ``T_max`` positions is copied, relaid with T minor, and then mostly
masked away.  This kernel reads the layer's rows where they lie: the
layer, and each slot's first and last block of positions to read, are
scalar-prefetch arguments of the BlockSpec index maps, so a slot's K and
V blocks are fetched only from its first readable position to its
cursor.  Past the last block the index map repeats it, the pipeline
issues no copy, and ``pl.when`` skips the compute.

Grid: ``(B, T_max // bt)``, the T axis innermost, with the online softmax
of ``flash_attention.py`` in float32 VMEM scratch.  A K or V block spans
the full ``Hkv*hd`` lanes; the query is block-diagonal, ``(H, Hkv*hd)``,
each head's row zero outside its KV group's columns, so one matmul gives
every head's scores and one more their values, ``(H, Hkv*hd)``, of which
the wrapper keeps each head's own group.  The step is bound by the cache
read, so the ``Hkv``-fold FLOPs cost nothing that shows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_mlp import fit_block

NEG_INF = -1e30

#: the most positions a K or V block holds
BLOCK_T = 512
#: the most bytes a K or V block holds (512 positions of 8 heads of 64 in
#: bfloat16): with both double-buffered, 2 MiB of VMEM
BLOCK_BYTES = 1 << 19


def block_t(T: int, row_bytes: int) -> Optional[int]:
    """The kernel's block of positions for a cache of ``T`` positions of
    ``row_bytes`` each: ``T`` itself where it fits ``BLOCK_T`` and
    ``BLOCK_BYTES``, else the largest 16-aligned divisor of ``T`` that
    does (a bfloat16 tile is 16 rows); None where there is none."""
    bound = min(BLOCK_T, max(16, BLOCK_BYTES // row_bytes))
    try:
        return fit_block(T, bound, 16)
    except ValueError:
        return None


def _kernel(layer_ref, first_ref, last_ref, lo_ref, hi_ref,
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bt: int, scale: float):
    b, t = pl.program_id(0), pl.program_id(1)
    blk = first_ref[b] + t

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(blk <= last_ref[b])
    def _step():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (H, bt)
        kpos = blk * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (kpos >= lo_ref[b]) & (kpos < hi_ref[b])
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     layer: jax.Array, lo: jax.Array, hi: jax.Array, *,
                     block: int, interpret: bool = False) -> jax.Array:
    """Each slot's one query row over its layer's cache rows.

    q: (B, H, hd); k, v: the stacked cache, (L, B, T, Hkv*hd); layer: ()
    int32; lo, hi: (B,) int32, slot b reads positions ``[lo[b], hi[b])``
    of layer ``layer``.  Returns (B, H, hd) in q's dtype; a slot with no
    position to read gets zeros.  ``block``: positions a block, dividing
    T (``block_t``)."""
    B, H, hd = q.shape
    T, W = k.shape[2], k.shape[3]
    Hkv = W // hd
    G = H // Hkv
    assert T % block == 0, (T, block)
    n_t = T // block
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    # first and last block of [lo, hi) within the cache, last >= first
    first = jnp.clip(lo // block, 0, n_t - 1)
    last = jnp.clip((hi - 1) // block, first, n_t - 1)
    # head h reads its group's columns: (B, Hkv, G, Hkv, hd), zero off
    # the diagonal of the two KV-group axes
    eye = jnp.eye(Hkv, dtype=q.dtype)
    q_bd = (q.reshape(B, Hkv, G, 1, hd) * eye[None, :, None, :, None]
            ).reshape(B, H, W)

    def kv_map(b, t, layer_ref, first_ref, last_ref, lo_ref, hi_ref):
        return (layer_ref[0], b,
                jnp.minimum(first_ref[b] + t, last_ref[b]), 0)

    def row_map(b, t, *_):
        return (b, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, bt=block, scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, n_t),
            in_specs=[
                pl.BlockSpec((None, H, W), row_map),
                pl.BlockSpec((None, None, block, W), kv_map),
                pl.BlockSpec((None, None, block, W), kv_map),
            ],
            out_specs=pl.BlockSpec((None, H, W), row_map),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),     # running max
                pltpu.VMEM((H, 1), jnp.float32),     # running sum
                pltpu.VMEM((H, W), jnp.float32),     # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, W), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), first, last, lo, hi,
      q_bd, k, v)
    # each head's own group: the diagonal of (B, Hkv, G, Hkv, hd)
    out = jnp.diagonal(out.reshape(B, Hkv, G, Hkv, hd), axis1=1, axis2=3)
    return jnp.moveaxis(out, -1, 1).reshape(B, H, hd)


def rows_read(cursor: np.ndarray, windows: np.ndarray, T: int, bt: int
              ) -> int:
    """Cache rows, over layers and slots, that the kernel reads for
    one-token rows at ``cursor`` (B,) in layers of ``windows`` (L,):
    whole blocks, from the block of the first position in the window to
    the cursor's."""
    cursor = np.minimum(np.asarray(cursor, np.int64), T - 1)[None, :]
    lo = np.maximum(cursor - np.asarray(windows, np.int64)[:, None] + 1, 0)
    first = lo // bt
    last = np.maximum(cursor // bt, first)
    return int((last - first + 1).sum()) * bt
