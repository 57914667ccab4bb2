"""Dispatching wrappers: Pallas kernel on TPU, jnp oracle elsewhere.

``use_pallas`` can be forced (e.g. interpret-mode validation in tests);
by default kernels run only on TPU backends, keeping CPU smoke tests on
the exact reference path.  The chunked scans (``wkv6``, ``rglru_chunked``)
have no dispatcher: the TPU compiler refuses them (no Mosaic lowering
for ``cumsum``), and the models run their ``lax`` scans instead.
"""
from __future__ import annotations

from typing import Optional

import jax

from . import ref
from .flash_attention import flash_attention
from .fused_mlp import fused_mlp


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def mlp_block(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
              w_down: jax.Array, use_pallas: Optional[bool] = None,
              interpret: bool = False) -> jax.Array:
    """(B,S,D) SwiGLU with VMEM-fused intermediate on TPU."""
    B, S, D = x.shape
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return ref.fused_mlp_ref(x.reshape(B * S, D), w_gate, w_up,
                                 w_down).reshape(B, S, D)
    y = fused_mlp(x.reshape(B * S, D), w_gate, w_up, w_down,
                  interpret=interpret)
    return y.reshape(B, S, D)


def attention_op(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 causal: bool = True, window: int = 0,
                 use_pallas: Optional[bool] = None,
                 interpret: bool = False) -> jax.Array:
    """(BH, S, hd) attention; flash kernel on TPU, exact ref elsewhere."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=interpret)

