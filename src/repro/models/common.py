"""Shared model machinery: config, norms, RoPE (incl. M-RoPE), init.

Models are pure pytrees of jnp arrays + pure apply functions (no flax).
Per-layer parameters are stacked along a leading axis so the transformer
can `lax.scan` over layers with rematerialization.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_kind: str                   # dense | moe | hybrid | rwkv | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    # local/global attention pattern (gemma3): window>0 => sliding window;
    # every `global_every`-th layer is global (window = -1)
    local_window: int = 0
    global_every: int = 0
    # MoE
    n_experts: int = 0               # routed experts the router scores
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_d_ff: int = 0                # expert width; 0 means d_ff
    n_shared_experts: int = 0        # SwiGLU of n_shared * moe width, every token
    n_dense_layers: int = 0          # leading layers with a dense MLP of d_ff
    router: str = "softmax"          # softmax | sigmoid (+ correction bias)
    routed_scaling: float = 1.0      # gate multiplier after renormalising
    # expert parallelism: a layer is divided over ep_size chips, and this
    # one holds n_experts // ep_size of its experts (routing is over all)
    ep_size: int = 1
    # latent attention (MLA, DeepSeek-V2/V3); kv_lora_rank 0 means GQA
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    qk_nope_dim: int = 0
    v_head_dim: int = 0
    # hybrid (recurrentgemma): pattern unit, e.g. ("rglru","rglru","attn")
    block_pattern: Tuple[str, ...] = ()
    rglru_dim: int = 0               # recurrence width (lru_width)
    conv1d_width: int = 4
    # enc-dec (whisper)
    n_enc_layers: int = 0
    enc_frames: int = 1500
    # vlm
    n_patches: int = 256
    mrope_sections: Tuple[int, int, int] = (0, 0, 0)
    # numerics
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    # beyond-paper: int8 KV cache (per-vector scales) halves the decode
    # roofline's dominant term (HBM cache reads)
    kv_quant: bool = False
    # execute hot ops through the Pallas kernels (TPU; interpret on CPU)
    use_kernels: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Embedding rows padded to a TP-shardable multiple (256)."""
        return -(-self.vocab // 256) * 256

    @property
    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def experts_held(self) -> int:
        """Routed experts this chip holds of each expert layer."""
        return self.n_experts // self.ep_size

    def _attn_params(self) -> int:
        d, H = self.d_model, self.n_heads
        if self.kv_lora_rank:
            r, rope = self.kv_lora_rank, self.qk_rope_dim
            return (d * H * (self.qk_nope_dim + rope) + d * (r + rope) + r
                    + r * H * (self.qk_nope_dim + self.v_head_dim)
                    + H * self.v_head_dim * d)
        hd = self.hd
        return d * hd * (H + 2 * self.n_kv_heads) + H * hd * d

    def _params(self, routed: int) -> int:
        """``routed`` parameters of routed experts in each expert layer
        (routers left out), its shared experts, leading dense layers, and
        the embedding (and an untied head)."""
        d = self.d_model
        n_moe = self.n_layers - self.n_dense_layers
        if self.n_experts:
            mlp = routed + 3 * d * self.expert_ff * self.n_shared_experts
        else:
            mlp = 3 * d * self.d_ff
        dense = self.n_dense_layers * (self._attn_params() + 3 * d * self.d_ff)
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        return n_moe * (self._attn_params() + mlp) + dense + emb

    def n_params(self) -> int:
        """Approximate parameter count held on this chip (for roofline
        MODEL_FLOPS): every routed expert it holds."""
        return self._params(3 * self.d_model * self.expert_ff
                            * self.experts_held)

    def n_active_params(self) -> int:
        """Parameters one token multiplies through on this chip: its
        ``top_k`` routed experts, of which a 1/ep_size share lies here."""
        if not self.n_experts:
            return self.n_params()
        return self._params(3 * self.d_model * self.expert_ff * self.top_k
                            // self.ep_size)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One (input shape) cell of the assignment."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))
            ).astype(dt)


def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float
               ) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                      # (..., S, 1, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    positions: (..., S, 3) — (temporal, height, width) position ids.
    sections: how many rotary frequency PAIRS go to each of (t, h, w);
    must sum to hd//2.
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    # pick the position stream per frequency-pair section
    sec_ids = jnp.concatenate([
        jnp.full((sections[0],), 0), jnp.full((sections[1],), 1),
        jnp.full((sections[2],), 2)]).astype(jnp.int32)  # (hd/2,)
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),
        jnp.broadcast_to(sec_ids, positions.shape[:-1] + (hd // 2,)),
        axis=-1)                                        # (..., S, hd/2)
    angles = (pos * freqs)[..., None, :]                # (..., S, 1, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int) -> jax.Array:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32)
                  * (-jnp.log(10000.0) / dim))
    pe = jnp.zeros((length, dim), dtype=jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def dense_init(key: jax.Array, shape: Tuple[int, ...], dtype,
               scale: Optional[float] = None) -> jax.Array:
    fan_in = shape[0]
    s = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       z_loss: float = 1e-4) -> jax.Array:
    """Mean token cross-entropy with optional z-loss, fp32 accumulation."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return jnp.mean(loss)
