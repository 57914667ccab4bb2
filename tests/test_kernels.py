"""Pallas kernels: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as da
from repro.kernels import ref
from repro.kernels.decode_attention import rows_read
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_mlp import fused_mlp
from repro.kernels.ops import mlp_block
from repro.kernels.rglru_scan import rglru_chunked
from repro.kernels.rwkv6_scan import wkv6
from repro.models import layers
from repro.models.common import ModelConfig

KEY = jax.random.PRNGKey(0)


def _k(i):
    return jax.random.fold_in(KEY, i)


# ---------------------------------------------------------------------------
# fused MLP (the paper's fine-grained pipelining in VMEM)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,D,F,bt,bf", [
    (64, 32, 64, 32, 32),
    (128, 64, 256, 64, 128),
    (256, 128, 512, 128, 256),
    (96, 48, 96, 32, 48),       # non-power-of-two dims
    (100, 32, 384, 32, 256),    # rows padded to the tile; bf 256 -> 128
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_mlp_sweep(T, D, F, bt, bf, dtype):
    x = (jax.random.normal(_k(1), (T, D), jnp.float32) * 0.3).astype(dtype)
    wg = (jax.random.normal(_k(2), (D, F), jnp.float32) * 0.1).astype(dtype)
    wu = (jax.random.normal(_k(3), (D, F), jnp.float32) * 0.1).astype(dtype)
    wd = (jax.random.normal(_k(4), (F, D), jnp.float32) * 0.1).astype(dtype)
    out = fused_mlp(x, wg, wu, wd, block_t=bt, block_f=bf, interpret=True)
    exp = ref.fused_mlp_ref(x, wg, wu, wd)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,hd,bq,bk", [
    (128, 32, 32, 32), (256, 64, 64, 128), (512, 64, 128, 64)])
@pytest.mark.parametrize("window", [0, 64])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(S, hd, bq, bk, window, causal):
    BH = 3
    q, k, v = (jax.random.normal(_k(i), (BH, S, hd), jnp.float32)
               for i in (5, 6, 7))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, exp, atol=3e-4, rtol=3e-4)


def test_flash_attention_bf16():
    q, k, v = (jax.random.normal(_k(i), (2, 128, 32), jnp.float32)
               .astype(jnp.bfloat16) for i in (8, 9, 10))
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    exp = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------------
# WKV6 chunked recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,N,chunk", [
    (64, 32, 16), (128, 64, 32), (256, 64, 64), (96, 32, 32)])
def test_wkv6_sweep(T, N, chunk):
    BH = 2
    r, k, v = (jax.random.normal(_k(i), (BH, T, N), jnp.float32) * 0.5
               for i in (11, 12, 13))
    w = jax.nn.sigmoid(jax.random.normal(_k(14), (BH, T, N)) - 1.0) \
        * 0.98 + 0.01
    u = jax.random.normal(_k(15), (BH, 1, N)) * 0.3
    y, s = wkv6(r, k, v, w, u, chunk=chunk, interpret=True)
    ye, se = ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(y, ye, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(s, se, atol=2e-3, rtol=2e-3)


def test_wkv6_extreme_decay_stable():
    """Fast decays must not overflow (log-space chunking)."""
    BH, T, N = 1, 128, 32
    r = jax.random.normal(_k(16), (BH, T, N)) * 0.5
    k = jax.random.normal(_k(17), (BH, T, N)) * 0.5
    v = jax.random.normal(_k(18), (BH, T, N)) * 0.5
    w = jnp.full((BH, T, N), 1e-4)          # near-instant forgetting
    u = jnp.zeros((BH, 1, N))
    y, s = wkv6(r, k, v, w, u, chunk=32, interpret=True)
    ye, se = ref.wkv6_ref(r, k, v, w, u)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(y, ye, atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# RG-LRU chunked recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,W,chunk", [(64, 32, 16), (128, 128, 64),
                                       (96, 64, 32)])
def test_rglru_sweep(T, W, chunk):
    B = 2
    a = jax.nn.sigmoid(jax.random.normal(_k(19), (B, T, W))) * 0.9 + 0.05
    b = jax.random.normal(_k(20), (B, T, W)) * 0.5
    h, hl = rglru_chunked(a, b, chunk=chunk, interpret=True)
    he, hle = ref.rglru_ref(a, b)
    np.testing.assert_allclose(h, he, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(hl, hle, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# ops.py dispatch
# ---------------------------------------------------------------------------

def test_ops_dispatch_cpu_falls_back_to_ref():
    x = jax.random.normal(_k(21), (2, 16, 32))
    wg = jax.random.normal(_k(22), (32, 64)) * 0.1
    wu = jax.random.normal(_k(23), (32, 64)) * 0.1
    wd = jax.random.normal(_k(24), (64, 32)) * 0.1
    out = mlp_block(x, wg, wu, wd)          # auto: CPU -> ref path
    exp = ref.fused_mlp_ref(x.reshape(32, 32), wg, wu, wd).reshape(2, 16, 32)
    np.testing.assert_allclose(out, exp, atol=1e-5, rtol=1e-5)


def test_ops_forced_pallas_matches():
    x = jax.random.normal(_k(25), (2, 32, 32))
    wg = jax.random.normal(_k(26), (32, 64)) * 0.1
    wu = jax.random.normal(_k(27), (32, 64)) * 0.1
    wd = jax.random.normal(_k(28), (64, 32)) * 0.1
    a = mlp_block(x, wg, wu, wd, use_pallas=False)
    b = mlp_block(x, wg, wu, wd, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# decode attention over the stacked cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 20])
@pytest.mark.parametrize("n_kv,hd,groups", [(2, 128, 8), (8, 64, 2)])
def test_decode_attention_equals_the_einsum_read(monkeypatch, n_kv, hd,
                                                 groups, window):
    """One-token rows over layer 1 of a stacked bf16 cache, in blocks of
    16 positions, slots filled to 0, 1, bt - 1, bt, bt + 1 and T - 1:
    the kernel gives the einsum path's output (``_sdpa`` over the
    layer's rows, masked), with and without a window."""
    monkeypatch.setattr(da, "BLOCK_T", 16)
    L, T, bt = 3, 64, 16
    fill = jnp.asarray([0, 1, bt - 1, bt, bt + 1, T - 1], jnp.int32)
    B, H = fill.shape[0], n_kv * groups
    cfg = ModelConfig(name="t", arch_kind="dense", n_layers=L,
                      d_model=H * hd, n_heads=H, n_kv_heads=n_kv, d_ff=64,
                      vocab=64, head_dim=hd, dtype=jnp.bfloat16)
    q = jax.random.normal(_k(40), (B, 1, H, hd), jnp.bfloat16)
    cache = tuple(jax.random.normal(_k(i), (L, B, T, n_kv * hd),
                                    jnp.bfloat16) for i in (41, 42))
    g = layers._Group(0, B, 1, None, fill)
    positions = fill[:, None]
    win = None if window is None else jnp.int32(window)
    out = layers._decode_kernel(g, q, cache, jnp.int32(1), positions, win,
                                interpret=True)
    k, v = (c[1].reshape(B, T, n_kv, hd) for c in cache)
    want = layers._sdpa(q, k, v, g.mask(positions, T, win), cfg)
    assert out.shape == (B, 1, H, hd) and out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_rows_read_counts_whole_blocks():
    """Blocks of 16 over 64 positions: a global layer reads each slot's
    blocks up to its cursor (1, 1, 2, 4), a layer of window 20 from the
    block of the window's first position (1, 1, 2, 2)."""
    cursor = np.array([0, 15, 16, 63])
    assert rows_read(cursor, np.array([1 << 30]), 64, 16) == 8 * 16
    assert rows_read(cursor, np.array([1 << 30, 20]), 64, 16) == 14 * 16
