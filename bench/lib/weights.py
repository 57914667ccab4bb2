"""Seeded weights, made on the device, bit for bit the same however built.

A layout (``bench/weights/<arch_kind>.py``) names every leaf of the
program's parameter tree with its shape, dtype, standard deviation and
whether it is stacked over layers.  Each value is a uniform 24-bit
integer from ``jax.random.bits`` times a power of two, cast to the leaf's
dtype: integer arithmetic, an exact scaling and one rounding cast, so the
program's tree (built in one jitted call) and the reference's layer by
layer (built again from the seed) hold the same numbers.

Layer stacks: a stacked leaf belongs to the stack named by the first
part of its path, and is built over that stack's length.  The layout
module may give ``stacks(c) -> {name: length}``, in the order the
layers run (a leading dense stack before the expert stack, say);
without it there is one stack, ``layers``, over ``num_hidden_layers``.
Layer ``l`` of a stack draws its values from the leaf's key folded with
``l``, its index within that stack, so a stack's layers do not depend
on the other stacks.
"""
from __future__ import annotations

import dataclasses
import importlib
import math
import zlib
from typing import Dict, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

Path = Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: Tuple[int, ...]      # per layer, for a stacked leaf
    dtype: str
    std: float
    stacked: bool = False


def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    """A seed of any size as two 32-bit words (traced, so one compiled
    program serves every seed)."""
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def _base_key(lo, hi):
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                              hi)


def _leaf_key(base, path: Path):
    return jax.random.fold_in(base, zlib.crc32("/".join(path).encode())
                              & 0x7FFFFFFF)


def _values(key, leaf: Leaf) -> jax.Array:
    bits = jax.random.bits(key, leaf.shape, jnp.uint32)
    k = (bits >> 8).astype(jnp.int32) - (1 << 23)       # uniform 24-bit
    # uniform over [-2^23, 2^23) has std 2^23 / sqrt(3)
    e = round(math.log2((2 ** 23 / math.sqrt(3)) / leaf.std))
    return (k.astype(jnp.float32) * jnp.float32(2.0 ** -e)).astype(
        jnp.dtype(leaf.dtype))


def _nest(flat: Dict[Path, jax.Array]) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v
    return out


def layout_module(c: dict):
    """``bench/weights/<arch_kind>.py`` of a configuration."""
    return importlib.import_module(f"bench.weights.{c['arch_kind']}")


def stacks(c: dict) -> Dict[str, int]:
    """The layer stacks of a configuration's layout, {name: length}, in
    the order the layers run: the layout module's ``stacks(c)`` where it
    has one, else ``layers`` over ``num_hidden_layers``."""
    mod = layout_module(c)
    if hasattr(mod, "stacks"):
        return dict(mod.stacks(c))
    return {"layers": c["num_hidden_layers"]}


def make_tree(layout: Dict[Path, Leaf], stack_lengths: Union[int, dict]):
    """jitted (lo, hi) -> the whole parameter tree, stacked leaves built
    one layer at a time so no leaf's random bits are held whole.
    ``stack_lengths``: {stack: length} (``stacks``), or one number, the
    length of the one stack ``layers``."""
    if isinstance(stack_lengths, int):
        stack_lengths = {"layers": stack_lengths}

    def build(lo, hi):
        base = _base_key(lo, hi)
        flat = {}
        for path, leaf in layout.items():
            key = _leaf_key(base, path)
            if leaf.stacked:
                flat[path] = jax.lax.map(
                    lambda l, key=key, leaf=leaf: _values(
                        jax.random.fold_in(key, l), leaf),
                    jnp.arange(stack_lengths[path[0]], dtype=jnp.uint32))
            else:
                flat[path] = _values(key, leaf)
        return _nest(flat)
    return jax.jit(build)


def make_layer(layout: Dict[Path, Leaf], stack: str = "layers"):
    """jitted (lo, hi, l) -> the leaves of layer ``l`` of ``stack`` (flat,
    keyed by their whole path: ``<stack>/attn/wq``)."""
    def build(lo, hi, l):
        base = _base_key(lo, hi)
        return {"/".join(path): _values(
                    jax.random.fold_in(_leaf_key(base, path), l), leaf)
                for path, leaf in layout.items()
                if leaf.stacked and path[0] == stack}
    return jax.jit(build)


def make_globals(layout: Dict[Path, Leaf]):
    """jitted (lo, hi) -> the leaves outside the layer stacks (flat)."""
    def build(lo, hi):
        base = _base_key(lo, hi)
        return {"/".join(path): _values(_leaf_key(base, path), leaf)
                for path, leaf in layout.items() if not leaf.stacked}
    return jax.jit(build)


def tree_signature(tree) -> list:
    """[(path, shape, dtype)] of a tree of arrays or shape structs."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return sorted((jax.tree_util.keystr(p), tuple(x.shape),
                   jnp.dtype(x.dtype).name) for p, x in leaves)
