"""Chunked max-plus scan, TPU Pallas (+ XLA and numpy fallbacks).

The simulator's per-burst recurrences are all instances of one max-plus
linear scan (``core/simulator.py``):

  x_t = max(x_{t-1} + s_t, u_t),   x_{-1} = h0

(emits gated by upstream readiness, the GB port server, the drain's
absorb loop).  Within a chunk the scan has a cumulative-sum closed form —
the max-plus analogue of ``rglru_scan``'s cumulative-log-decay trick:

  x_t = P_t + max(h_in, max_{tau<=t} (u_tau - P_tau)),
  P_t = sum_{sigma<=t} s_sigma   (inclusive),

computed with one ``cumsum`` + one ``cummax`` per (1, L) VMEM block, with
the (1, 1) carry in scratch across the chunk sweep — the same grid/block
structure as ``rglru_scan``.

Engines (``maxplus_scan(..., engine=...)``):

  * ``"pallas"`` — the chunked kernel above; ``interpret=True`` runs it on
    CPU (dtype-polymorphic, so float64 works in interpret mode).  The
    TPU compiler refuses it (no Mosaic lowering for ``cumsum``, and a
    ``(1, L)`` block breaks the (8, 128) tiling at B > 1), so on a TPU
    an explicit ``"pallas"`` raises that error; it never drops to
    interpret mode there.
  * ``"xla"``    — ``lax.associative_scan`` over the max-plus semiring
    pairs ``(s, u) . (s', u') = (s + s', max(u + s', u'))``.
  * ``"numpy"``  — the same closed form in numpy.
  * ``"auto"``   — ``REPRO_MAXPLUS_ENGINE`` env override, else numpy on
    every backend: on CPU the jax engines' dispatch overhead loses to
    the numpy closed form (docs/engines.md); on a TPU the kernel does
    not compile, and the float64 the jax engines need would flip
    ``jax_enable_x64`` in a process that may be serving a model.

``maxplus_scan_reference`` is the scalar loop both parity suites pin the
engines against.

Cycle counts overflow float32 past 2**24 (the simulator's long-prefix
segments exceed that), so the jax engines require float64: the module
enables ``jax_enable_x64`` on first use and raises a clear error if the
flag cannot take effect (e.g. jax was already initialized with x64 off).
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_X64_OK: Optional[bool] = None


def ensure_x64() -> None:
    """Enable float64 in jax (idempotent); raise if it cannot take effect.

    Max-plus cycle counts are absolute times (easily > 2**24 cycles), so
    float32 silently loses whole cycles; the engines refuse to run in
    that mode rather than drift from the numpy reference.
    """
    global _X64_OK
    if _X64_OK is None:
        jax.config.update("jax_enable_x64", True)
        probe = jnp.asarray(np.float64(2.0 ** 53 + 1.0))
        _X64_OK = (probe.dtype == jnp.float64
                   and float(probe) == 2.0 ** 53 + 1.0)
    if not _X64_OK:
        raise RuntimeError(
            "could not enable jax float64 (jax_enable_x64) — max-plus "
            "cycle counts overflow float32; set JAX_ENABLE_X64=1 before "
            "jax initializes, or use engine='numpy'")


# ---------------------------------------------------------------------------
# reference + numpy closed form
# ---------------------------------------------------------------------------


def maxplus_scan_reference(u, s, h0: float = -math.inf) -> np.ndarray:
    """Scalar loop: x_t = max(x_{t-1} + s_t, u_t).  The semantic pin."""
    u = np.asarray(u, np.float64)
    s = np.asarray(s, np.float64)
    out = np.empty_like(u)
    x = h0
    for t in range(u.shape[0]):
        x = max(x + s[t], u[t])
        out[t] = x
    return out


def _maxplus_numpy(u: np.ndarray, s: np.ndarray, h0: float) -> np.ndarray:
    P = np.cumsum(s)
    return P + np.maximum(np.maximum.accumulate(u - P), h0)


# ---------------------------------------------------------------------------
# Pallas kernel (rglru_scan's grid/block structure)
# ---------------------------------------------------------------------------

def _maxplus_kernel(u_ref, s_ref, h0_ref, y_ref, h_ref, *,
                    n_chunks: int):
    cb = pl.program_id(1)

    @pl.when(cb == 0)
    def _init():
        h_ref[...] = h0_ref[...]

    u = u_ref[...]                        # (1, L)
    s = s_ref[...]                        # (1, L)
    c = h_ref[...]                        # (1, 1) carry in scratch
    P = jnp.cumsum(s, axis=1)
    q = jax.lax.cummax(u - P, axis=1)
    y = P + jnp.maximum(q, c)
    y_ref[...] = y
    h_ref[...] = y[:, -1:]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def maxplus_chunked(u: "jax.Array", s: "jax.Array", h0: "jax.Array", *,
                    chunk: int = 256, interpret: bool = False):
    """u, s: (B, T); h0: (B, 1) -> x: (B, T).  T must divide by chunk
    (callers pad with u = -inf, s = 0 — a max-plus no-op)."""
    B, T = u.shape
    L = min(chunk, T)
    assert T % L == 0
    grid = (B, T // L)
    return pl.pallas_call(
        functools.partial(_maxplus_kernel, n_chunks=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L), lambda b_, c_: (b_, c_)),
            pl.BlockSpec((1, L), lambda b_, c_: (b_, c_)),
            pl.BlockSpec((1, 1), lambda b_, c_: (b_, 0)),
        ],
        out_specs=pl.BlockSpec((1, L), lambda b_, c_: (b_, c_)),
        out_shape=jax.ShapeDtypeStruct((B, T), u.dtype),
        scratch_shapes=[pltpu.VMEM((1, 1), u.dtype)],
        interpret=interpret,
    )(u, s, h0)


@jax.jit
def _maxplus_xla(u: "jax.Array", s: "jax.Array", h0: "jax.Array"):
    """(B, T) associative scan over the max-plus semiring pairs."""
    def combine(a, b):
        s1, u1 = a
        s2, u2 = b
        return s1 + s2, jnp.maximum(u1 + s2, u2)
    S, U = jax.lax.associative_scan(combine, (s, u), axis=1)
    return jnp.maximum(h0 + S, U)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

_CHUNK = 256


def _resolve_engine(engine: str) -> str:
    if engine != "auto":
        return engine
    env = os.environ.get("REPRO_MAXPLUS_ENGINE", "").strip().lower()
    if env in ("pallas", "xla", "numpy"):
        return env
    return "numpy"


def maxplus_scan(u, s, h0: float = -math.inf, engine: str = "auto",
                 interpret: Optional[bool] = None) -> np.ndarray:
    """x_t = max(x_{t-1} + s_t, u_t) over the last axis, x_{-1} = h0.

    Accepts 1-D (T,) or 2-D (B, T) arrays; returns numpy float64 of the
    same shape.  ``interpret`` (pallas only) defaults to True off-TPU so
    the kernel runs everywhere; force ``interpret=False`` on TPU CI.
    """
    u = np.asarray(u, np.float64)
    s = np.asarray(s, np.float64)
    squeeze = u.ndim == 1
    if squeeze:
        u, s = u[None, :], s[None, :]
    B, T = u.shape
    # resolve + validate the engine before the empty-input early return:
    # a bogus engine name must raise even when there is nothing to scan
    eng = _resolve_engine(engine)
    if eng not in ("pallas", "xla", "numpy"):
        raise ValueError(f"unknown maxplus engine {eng!r}; one of "
                         "('auto', 'pallas', 'xla', 'numpy')")
    if T == 0:
        return np.zeros(0) if squeeze else np.zeros((B, 0))
    if eng == "numpy":
        out = np.stack([_maxplus_numpy(u[b], s[b], h0) for b in range(B)])
        return out[0] if squeeze else out
    ensure_x64()
    h = jnp.full((B, 1), h0, jnp.float64)
    if eng == "xla":
        out = np.asarray(_maxplus_xla(jnp.asarray(u), jnp.asarray(s), h))
    else:  # pallas (engine names validated above)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        # pad to the next power of two (sliced off below): bounds the
        # number of distinct jit shapes while keeping tiny scans cheap
        T2 = 1 << (T - 1).bit_length()
        if T2 != T:
            u = np.pad(u, ((0, 0), (0, T2 - T)),
                       constant_values=-np.inf)
            s = np.pad(s, ((0, 0), (0, T2 - T)))
        out = np.asarray(maxplus_chunked(
            jnp.asarray(u), jnp.asarray(s), h,
            chunk=min(_CHUNK, T2),
            interpret=bool(interpret)))[:, :T]
    return out[0] if squeeze else out
