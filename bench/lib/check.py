"""``correct`` for a served model: the served tokens against a plain
float32 reference.

After the window, a sample of the finished requests, drawn from the seed
with the longest one always in it, is run through the reference
(``bench/reference/<arch_kind>.py``) layer by layer over each prompt with
its served tokens.  At every position where the engine served a token,
the gap is the reference's best logit less the reference's logit of the
served token: 0 where the engine picked the reference's argmax.  The
numbers compared are the widest gap and the mean gap over the compared
positions.

A control runs the reference in a lower precision in the program's
place (``mode`` ``fp8w`` or ``fp8``) and reads, at the same positions,
the gap of the token the control puts first.

The reference module gives ``prepare(leaves, mode)``, ``matmul(a, w,
mode)``, ``rms_norm(x, s, eps)`` and ``layer(p, x, c, mode)``, where
``p`` holds one layer's leaves keyed by their whole path
(``<stack>/attn/wq``).  Layers run in the order ``order(c)`` gives, a
list of (stack, index within the stack) from the first layer to the
last, where the module has it; else every stack of the layout
(``weights.stacks``) in turn: for the one default stack, ``layers`` 0
to ``num_hidden_layers - 1``.  A ``layer`` that takes an ``index`` argument gets the
layer's place in the whole model as a traced int32 scalar (one
compiled layer a stack serves every index), for a layer that depends on
where it stands: a window on some layers and not others.  The output
head is the layout's ``unembed`` leaf, (d, padded vocabulary) cut to
``vocab_size``, where it has one, else the tied ``embed``; the fp8
controls round it over its input axis, as every other weight.
"""
from __future__ import annotations

import importlib
import inspect
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights

GROUP = 2          # sequences per reference call
HEAD_CHUNK = 256   # positions per LM-head block


def sample(finished: Sequence, seed: int, n: int) -> list:
    """The longest finished request and n - 1 others drawn from the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.request.output), r.rid))
    rest = order[1:]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [order[0]] + [rest[i] for i in sorted(pick)]


def sequences(reqs: Sequence) -> List[Tuple[List[int], List[int]]]:
    return [(list(r.request.prompt), list(r.request.output)) for r in reqs]


def _layout_and_reference(c: dict):
    lay = weights.layout_module(c).layout(c)
    ref = importlib.import_module(f"bench.reference.{c['arch_kind']}")
    return lay, ref


def _pack(seqs, T: int):
    """Fed tokens (prompt + all but the last served token), the served
    token expected at each position, and which positions to compare."""
    n = -(-len(seqs) // GROUP) * GROUP
    fed = np.zeros((n, T), np.int32)
    served = np.zeros((n, T), np.int32)
    mask = np.zeros((n, T), bool)
    for i, (prompt, out) in enumerate(seqs):
        toks = (prompt + out[:-1])[:T]
        fed[i, :len(toks)] = toks
        p = len(prompt) - 1
        m = min(len(out), T - p)
        served[i, p:p + m] = out[:m]
        mask[i, p:p + m] = True
    return fed, served, mask


def _head_fn(mode: str, matmul):
    def head(x, emb, nxt):
        g, T, d = x.shape

        def block(i):
            xs = jax.lax.dynamic_slice(x, (0, i * HEAD_CHUNK, 0),
                                       (g, HEAD_CHUNK, d))
            ns = jax.lax.dynamic_slice(nxt, (0, i * HEAD_CHUNK),
                                       (g, HEAD_CHUNK))
            logits = matmul(xs, emb.T, mode)
            at = jnp.take_along_axis(logits, ns[..., None], -1)[..., 0]
            return logits.max(-1), at, jnp.argmax(logits, -1).astype(jnp.int32)

        best, at, arg = jax.lax.map(block, jnp.arange(T // HEAD_CHUNK))
        fix = lambda a: jnp.moveaxis(a, 0, 1).reshape(g, T)  # noqa: E731
        return fix(best), fix(at), fix(arg)
    return jax.jit(head)


def _order(c: dict, ref) -> List[Tuple[str, int]]:
    """(stack, index in the stack) of every layer, first to last."""
    if hasattr(ref, "order"):
        return [(s, int(i)) for s, i in ref.order(c)]
    return [(s, i) for s, n in weights.stacks(c).items() for i in range(n)]


def _hidden(c, lay, ref, seed, fed, mode):
    """Final-normed hidden states of every group, layer by layer, and the
    output head as rows, (vocab_size, d)."""
    lo, hi = weights.seed_words(seed)
    glob = ref.prepare(weights.make_globals(lay)(lo, hi), mode)
    order = _order(c, ref)
    layer_w = {s: weights.make_layer(lay, s) for s in dict(order)}
    prep = jax.jit(lambda leaves: ref.prepare(leaves, mode))
    if "index" in inspect.signature(ref.layer).parameters:
        step = jax.jit(lambda p, x, i: ref.layer(p, x, c, mode, index=i))
    else:
        step = jax.jit(lambda p, x, i: ref.layer(p, x, c, mode))
    V = c["vocab_size"]
    emb = glob["embed"][:V]
    head = glob["unembed"][:, :V].T if "unembed" in glob else emb
    xs = [emb[jnp.asarray(fed[i:i + GROUP])]
          for i in range(0, len(fed), GROUP)]
    for index, (stack, l) in enumerate(order):
        p = prep(layer_w[stack](lo, hi, np.uint32(l)))
        xs = [step(p, x, np.int32(index)) for x in xs]
    eps = c["rms_norm_eps"]
    xs = [ref.rms_norm(x, glob["ln_f"], eps) for x in xs]
    return xs, head


def logit_gaps(c: dict, seed: int, seqs, modes=("f32",)) -> dict:
    """Widest gaps, by mode: ``f32`` compares the served tokens; a control
    mode compares the tokens it puts first itself.  Runs under
    ``default_matmul_precision("highest")``."""
    if not seqs:
        return {m: {"max_gap": 0.0, "mean_gap": 0.0, "positions": 0,
                    "disagree": 0} for m in modes}
    lay, ref = _layout_and_reference(c)
    T = c["serve"]["max_len"]
    fed, served, mask = _pack(seqs, T)
    out = {}
    with jax.default_matmul_precision("highest"):
        xs_ref, head_w = _hidden(c, lay, ref, seed, fed, "f32")
        head = _head_fn("f32", ref.matmul)
        for mode in modes:
            if mode == "f32":
                nxt = served
            else:
                xs_c, head_wc = _hidden(c, lay, ref, seed, fed, mode)
                head_c = _head_fn(mode, ref.matmul)
                nxt = np.concatenate([
                    np.asarray(head_c(x, head_wc, jnp.zeros(x.shape[:2],
                                                            jnp.int32))[2])
                    for x in xs_c])
                del xs_c, head_wc
            gap = np.concatenate([
                np.asarray(b - a) for b, a, _ in (
                    head(x, head_w,
                         jnp.asarray(nxt[i * GROUP:(i + 1) * GROUP]))
                    for i, x in enumerate(xs_ref))])
            g = gap[mask]
            out[mode] = {"max_gap": float(g.max()) if g.size else 0.0,
                         "mean_gap": float(g.mean()) if g.size else 0.0,
                         "positions": int(g.size),
                         "disagree": int((g > 0).sum())}
    return out
