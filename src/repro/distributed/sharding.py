"""Sharding rules: DP / FSDP / TP / SP / EP via named-path PartitionSpecs.

The rules implement a MaxText-style 2D scheme on the ("data", "model")
mesh (+ an outer "pod" axis as extra data parallelism):

  * weight matrices: contraction-side dim sharded over "data" (FSDP:
    gathered per-layer inside the scan, so XLA overlaps the gather of
    layer i+1 with the compute of layer i) and the parallel dim over
    "model" (TP);
  * MoE expert tensors: expert dim over "model" (EP);
  * activations: batch over ("pod","data");
  * KV caches: batch over "data", whole kv-heads over "model" when
    divisible, otherwise sequence over "model" (cache sequence-parallelism);
    a latent cache (MLA) is shared by the heads: batch over "data" only.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ModelConfig

#: leaf names whose LAST dim is the parallel (TP) dim
_LAST_MODEL = {
    "wq", "wk", "wv", "w_gate", "w_up", "w_in", "w_x", "w_y",
    "w_r", "w_g", "w_decay", "w_k", "patch_proj", "unembed", "wkv_b",
}
#: leaf names whose FIRST (non-stacked) dim is the parallel dim
_FIRST_MODEL = {"wo", "w_down", "w_out", "w_v", "w_o"}


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _divisible(dim: int, mesh: Mesh, axis: str) -> bool:
    return dim % max(1, _axis_size(mesh, axis)) == 0


def param_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
               mesh: Mesh, scanned: bool) -> P:
    """PartitionSpec for one parameter leaf."""
    name = path[-1]
    nd = len(shape)
    lead: Tuple[Optional[str], ...] = (None,) if scanned else ()
    body = shape[1:] if scanned else shape

    def ok(dim_idx: int, axis: str) -> bool:
        return body[dim_idx] % max(1, _axis_size(mesh, axis)) == 0

    if name == "embed":
        # vocab over model (TP of the embedding/unembedding)
        if len(body) == 2 and ok(0, "model"):
            return P(*lead, "model", None)
        return P(*lead, None, None)

    if len(body) == 3 and name in ("w_gate", "w_up", "w_down"):
        # MoE expert tensors (E, D, F): expert-parallel over "model" plus
        # FSDP of the per-expert matrix over "data" (gathered per layer)
        e = "model" if ok(0, "model") else None
        d1 = "data" if ok(1, "data") else None
        return P(*lead, e, d1, None)

    if len(body) == 2:
        if name in _LAST_MODEL:
            d0 = "data" if ok(0, "data") and body[0] >= 1024 else None
            d1 = "model" if ok(1, "model") else None
            return P(*lead, d0, d1)
        if name in _FIRST_MODEL:
            d0 = "model" if ok(0, "model") else None
            d1 = "data" if ok(1, "data") and body[1] >= 1024 else None
            return P(*lead, d0, d1)
        return P(*lead, *([None] * len(body)))

    return P(*lead, *([None] * len(body)))


def _is_scanned(cfg: ModelConfig, path: Tuple[str, ...]) -> bool:
    return any(p in ("layers", "dense_layers", "enc_layers", "dec_layers")
               for p in path) \
        and cfg.arch_kind != "hybrid"


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return tuple(out)


def params_shardings(cfg: ModelConfig, params_shape: Any, mesh: Mesh):
    """NamedSharding pytree matching a params (shape) pytree."""
    def leaf(path, x):
        names = _path_names(path)
        spec = param_spec(names, tuple(x.shape), mesh,
                          scanned=_is_scanned(cfg, names))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, params_shape)


def batch_shardings(cfg: ModelConfig, specs: Any, mesh: Mesh):
    """Inputs: batch over ("pod","data"); everything else replicated."""
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def leaf(path, x):
        if len(x.shape) >= 1 and x.shape[0] % int(
                np.prod([mesh.shape[a] for a in daxes])) == 0:
            return NamedSharding(mesh, P(daxes, *([None] * (len(x.shape) - 1))))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(leaf, specs)


def cache_shardings(cfg: ModelConfig, cache_shape: Any, mesh: Mesh):
    """KV-cache sharding for decode.

    Layout (L, B, T, Hkv*hd), int8 scales (L, B, T, Hkv) (whisper keeps
    (L, B, T, Hkv, hd); other families, per-arch states).  Batch over
    "data"; axis 3 over "model" when the kv-heads divide it, so each
    shard holds whole heads, else the sequence dim (cache sequence
    parallelism — essential for GQA with few kv heads).  The latent
    cache, (L, B, T, rank) and (L, B, rope, T), has no head axis: batch
    over "data", replicated over "model".
    """
    msize = _axis_size(mesh, "model")
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = int(np.prod([mesh.shape[a] for a in daxes]))
    dspec = daxes if len(daxes) > 1 else daxes[0]

    def leaf(path, x):
        names = _path_names(path)
        shape = tuple(x.shape)
        nd = len(shape)
        if names and names[-1] in ("c_kv", "k_pe"):
            spec = [None] * nd
            spec[1] = dspec if shape[1] % dsize == 0 else None
            return NamedSharding(mesh, P(*spec))
        if (cfg.arch_kind != "hybrid" and names
                and names[-1] in ("k", "v", "k_scale", "v_scale")):
            spec = [None] * nd
            spec[1] = dspec if shape[1] % dsize == 0 else None
            if cfg.n_kv_heads % msize == 0:
                spec[3] = "model"
            elif shape[2] % msize == 0:
                spec[2] = "model"
            return NamedSharding(mesh, P(*spec))
        if nd == 5:          # rwkv time-mix state (L, B, H, 64, 64)
            b = dspec if shape[1] % dsize == 0 else None
            if shape[3] % msize == 0:
                return NamedSharding(mesh, P(None, b, None, "model", None))
            if shape[2] % msize == 0:
                return NamedSharding(mesh, P(None, b, "model", None, None))
            return NamedSharding(mesh, P(None, b, None, None, None))
        if nd == 4 and names and names[-1] in ("k", "v"):  # hybrid (B,T,H,hd)
            b = dspec if shape[0] % dsize == 0 else None
            if shape[1] % msize == 0:
                return NamedSharding(mesh, P(b, "model", None, None))
            return NamedSharding(mesh, P(b, None, None, None))
        # recurrent states: batch over data axes, width over model if it fits
        if nd >= 2 and shape[0] % dsize == 0:
            spec = [dspec] + [None] * (nd - 1)
            if shape[-1] % msize == 0 and shape[-1] >= msize * 64:
                spec[-1] = "model"
            return NamedSharding(mesh, P(*spec))
        if nd >= 2 and shape[1] % dsize == 0:
            spec = [None, dspec] + [None] * (nd - 2)
            if shape[-1] % msize == 0 and shape[-1] >= msize * 64:
                spec[-1] = "model"
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(leaf, cache_shape)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
