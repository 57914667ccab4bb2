"""Kernel checks on the CPU: interpret-mode correctness + jnp-path timing.

The wall-clock column times the jnp *reference* path on the host CPU; it
is not a kernel time.  The Pallas kernels are checked for correctness in
interpret mode here; that they compile for a TPU v5e is checked by
``tests/test_tpu_compile.py``, and the fused MLP runs compiled on the
chip in ``chip_smoke.py``.  Their device times are not measured.
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_mlp import fused_mlp
from repro.kernels.rglru_scan import rglru_chunked
from repro.kernels.rwkv6_scan import wkv6


def _time(fn, *args, reps: int = 5) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def kernel_validation() -> List[dict]:
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 12)
    rows = []

    T, D, F = 256, 128, 512
    x = jax.random.normal(ks[0], (T, D), jnp.float32) * 0.3
    wg, wu = (jax.random.normal(ks[i], (D, F), jnp.float32) * 0.05
              for i in (1, 2))
    wd = jax.random.normal(ks[3], (F, D), jnp.float32) * 0.05
    out = fused_mlp(x, wg, wu, wd, block_t=128, block_f=256, interpret=True)
    err = float(jnp.abs(out - ref.fused_mlp_ref(x, wg, wu, wd)).max())
    us = _time(lambda *a: ref.fused_mlp_ref(*a), x, wg, wu, wd)
    rows.append({"kernel": "fused_mlp", "shape": f"T{T}xD{D}xF{F}",
                 "max_err": err, "ref_us_per_call": round(us, 1),
                 "vmem_tile_bytes": 128 * D * 4 + 2 * D * 256 * 4
                 + 256 * D * 4})

    BH, S, hd = 8, 512, 64
    q, k, v = (jax.random.normal(ks[i], (BH, S, hd), jnp.float32)
               for i in (4, 5, 6))
    o = flash_attention(q, k, v, causal=True, window=128, block_q=128,
                        block_k=128, interpret=True)
    err = float(jnp.abs(
        o - ref.attention_ref(q, k, v, causal=True, window=128)).max())
    us = _time(lambda *a: ref.attention_ref(*a, causal=True, window=128),
               q, k, v)
    rows.append({"kernel": "flash_attention", "shape": f"BH{BH}xS{S}",
                 "max_err": err, "ref_us_per_call": round(us, 1)})

    BH2, T2, N = 4, 256, 64
    r = jax.random.normal(ks[7], (BH2, T2, N)) * 0.5
    kk = jax.random.normal(ks[8], (BH2, T2, N)) * 0.5
    vv = jax.random.normal(ks[9], (BH2, T2, N)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[10], (BH2, T2, N)) - 1) * 0.98 \
        + 0.01
    u = jax.random.normal(ks[11], (BH2, 1, N)) * 0.3
    y, s = wkv6(r, kk, vv, w, u, chunk=64, interpret=True)
    ye, se = ref.wkv6_ref(r, kk, vv, w, u)
    err = float(jnp.abs(y - ye).max())
    us = _time(lambda *a: ref.wkv6_ref(*a)[0], r, kk, vv, w, u)
    rows.append({"kernel": "wkv6", "shape": f"BH{BH2}xT{T2}xN{N}",
                 "max_err": err, "ref_us_per_call": round(us, 1)})

    B3, T3, W3 = 4, 256, 128
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B3, T3, W3))) * 0.9 + 0.05
    b = jax.random.normal(ks[1], (B3, T3, W3)) * 0.5
    h, _ = rglru_chunked(a, b, chunk=64, interpret=True)
    he, _ = ref.rglru_ref(a, b)
    err = float(jnp.abs(h - he).max())
    us = _time(lambda *args: ref.rglru_ref(*args)[0], a, b)
    rows.append({"kernel": "rglru", "shape": f"B{B3}xT{T3}xW{W3}",
                 "max_err": err, "ref_us_per_call": round(us, 1)})
    return rows
