#!/usr/bin/env python3
"""Bring the main path up on a TPU at qwen2.5-3b's published widths.

    python chip_smoke.py             # one chip: serve + logits checks
    python chip_smoke.py --chips 4   # four chips: sharded train() steps only

One chip (36 layers, d_model 2048, vocab 151936, bf16 weights from
``--seed``), every phase in this one process:

  1. device  — refuse any platform but ``tpu``;
  2. serve   — 8 requests of a few dozen prompt tokens, 16 new tokens
     each, through ``ServeEngine.run()`` on 4 slots; all must complete;
  3. prefill/decode — ``forward()`` logits at a prompt's last position
     against ``decode_step`` fed the same prompt token by token;
  4. kernels — the same ``forward()`` with ``use_kernels=True`` (the
     fused SwiGLU Pallas kernel, compiled) against the einsum path;
  5. x64     — a ``PlanRequest`` built beside the served model leaves
     ``jax_enable_x64`` off and the weights' dtypes as they were.

Four chips: ``train()`` on a (4, 1) data mesh with parameters and Adam
state sharded over it; its step-1 loss must match ``loss_fn`` on one
device for the same parameters and batch, and the loss must fall.

Times printed are informational, never a claim.  Any failed phase exits
non-zero; the last line, on success only, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The compile cache goes where ``repro.launch.compile_cache`` says.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.lm_graphs import decode_graph  # noqa: E402
from repro.core import PAPER_HW, PlanRequest, Topology  # noqa: E402
from repro.data.pipeline import DataConfig, TokenDataset  # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import init_model  # noqa: E402
from repro.models.transformer import (decode_step, forward,  # noqa: E402
                                      init_cache, loss_fn)
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.runtime.serve_loop import Request, ServeEngine  # noqa: E402
from repro.runtime.train_loop import TrainLoopConfig, train  # noqa: E402

ARCH = "qwen2.5-3b"

#: Bound on the relative L2 gap ||a - b|| / ||b|| between two bf16
#: computations of the same last-position logits (prefill vs decode, and
#: fused kernel vs einsum).  Derived on CPU in bf16 at small size
#: (d_model 256-512, 32-token prompts): the prefill/decode gap was
#: <= 0.0105 at 8 layers and grew about as sqrt(depth) from 2 to 8 layers;
#: kernel/einsum <= 0.0123.  At 36 layers that extrapolates to ~0.02-0.03;
#: the bound leaves about twice that.
LOGITS_RTOL = 0.05

#: Bound on |sharded step-1 loss - single-device loss_fn| (nats).  On 4
#: virtual CPU devices at small size the two agree to ~1e-6; the chip's
#: sharded reductions run in another order, so allow ~1e-3 of a loss
#: near ln(151936) = 11.9.
LOSS_ATOL = 0.01


class SmokeFailure(Exception):
    """A phase produced a wrong result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_l2(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def prompts(cfg, rng: np.random.Generator, n: int) -> list:
    return [rng.integers(0, cfg.vocab, size=int(rng.integers(24, 41))).tolist()
            for _ in range(n)]


def serve_phase(params, cfg, rng, n_requests: int = 8, slots: int = 4,
                max_new: int = 16, max_len: int = 128) -> dict:
    engine = ServeEngine(params, cfg, batch_slots=slots, max_len=max_len)
    for rid, p in enumerate(prompts(cfg, rng, n_requests)):
        engine.submit(Request(rid=rid, prompt=p, max_new_tokens=max_new))
    t0 = time.perf_counter()
    done = engine.run()
    secs = time.perf_counter() - t0
    check(not engine.truncated, "serve run truncated")
    check(len(done) == n_requests,
          f"{len(done)} of {n_requests} requests completed")
    for r in done:
        check(r.done and len(r.output) == max_new,
              f"request {r.rid}: {len(r.output)} of {max_new} tokens")
        check(all(0 <= t < cfg.vocab for t in r.output),
              f"request {r.rid}: token out of vocab")
    return {"requests": len(done), "tokens": sum(len(r.output) for r in done),
            "ticks": engine.ticks, "seconds_incl_compile": secs}


def _last_logits(params, cfg, tokens) -> tuple:
    """Logits over the real vocabulary (the padded rows are -1e30)."""
    fn = jax.jit(lambda p, t: forward(p, cfg, t)[0][0, -1, :cfg.vocab])
    t0 = time.perf_counter()
    compiled = fn.lower(params, tokens).compile()
    secs = time.perf_counter() - t0
    return np.asarray(compiled(params, tokens), np.float32), secs


def prefill_decode_phase(params, cfg, prompt: list) -> dict:
    tokens = jnp.asarray([prompt], jnp.int32)
    prefill, compile_s = _last_logits(params, cfg, tokens)
    step = jax.jit(lambda p, t, c, i: decode_step(p, cfg, t, c, i))
    cache = init_cache(cfg, 1, len(prompt))
    for i in range(len(prompt)):
        logits, cache = step(params, tokens[:, i:i + 1], cache, jnp.int32(i))
    decode = np.asarray(logits[0, -1, :cfg.vocab], np.float32)
    check(prefill.shape == decode.shape == (cfg.vocab,),
          f"logits shapes {prefill.shape} / {decode.shape}")
    check(bool(np.isfinite(prefill).all() and np.isfinite(decode).all()),
          "non-finite logits")
    gap = rel_l2(decode, prefill)
    check(gap <= LOGITS_RTOL,
          f"prefill/decode relative L2 gap {gap} > {LOGITS_RTOL}")
    return {"rel_l2": gap, "tol": LOGITS_RTOL,
            "argmax_equal": bool(prefill.argmax() == decode.argmax()),
            "forward_compile_s": compile_s, "_prefill": prefill}


def kernel_phase(params, cfg, prompt: list, einsum: np.ndarray) -> dict:
    kcfg = dataclasses.replace(cfg, use_kernels=True)
    fused, compile_s = _last_logits(params, kcfg,
                                    jnp.asarray([prompt], jnp.int32))
    check(bool(np.isfinite(fused).all()), "non-finite logits")
    gap = rel_l2(fused, einsum)
    check(gap <= LOGITS_RTOL,
          f"kernel/einsum relative L2 gap {gap} > {LOGITS_RTOL}")
    return {"rel_l2": gap, "tol": LOGITS_RTOL,
            "argmax_equal": bool(fused.argmax() == einsum.argmax()),
            "forward_compile_s": compile_s}


def x64_phase(params, cfg) -> dict:
    dtypes = [x.dtype for x in jax.tree.leaves(params)]
    request = PlanRequest(decode_graph(cfg), hw=PAPER_HW,
                          topology=Topology.AMP)
    check(request.engine == "numpy",
          f"auto engine resolved to {request.engine!r} beside the model")
    check(not jax.config.jax_enable_x64, "jax_enable_x64 was turned on")
    check([x.dtype for x in jax.tree.leaves(params)] == dtypes,
          "weight dtypes changed")
    check(jnp.arange(3).dtype == jnp.int32, "default int is not int32")
    return {"engine": request.engine, "x64": False}


def train_phase(cfg, seed: int, steps: int = 4, batch: int = 4,
                seq: int = 256) -> dict:
    data = DataConfig(seq_len=seq, global_batch=batch, vocab=cfg.vocab,
                      seed=seed)
    host = TokenDataset(data).global_batch_at(0)
    # single-device reference first: its weights leave the chip before
    # the sharded state takes the memory
    params = init_model(jax.random.PRNGKey(seed), cfg)
    ref = float(jax.jit(loss_fn, static_argnums=1)(params, cfg, host))
    del params
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=steps)
    loop = TrainLoopConfig(steps=steps, log_every=1, microbatches=1,
                           seed=seed)
    t0 = time.perf_counter()
    out = train(cfg, opt, loop, make_host_mesh, data)
    secs = time.perf_counter() - t0
    losses = [h["loss"] for h in out["history"]]
    check(len(losses) == steps and bool(np.isfinite(losses).all()),
          f"losses {losses}")
    check(out["failures"] == 0, f"{out['failures']} step failures")
    check(abs(losses[0] - ref) <= LOSS_ATOL,
          f"sharded step-1 loss {losses[0]} vs one device {ref}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return {"single_device_loss": ref, "losses": losses,
            "seconds_incl_compile": secs}


def one_chip(cfg, seed: int) -> None:
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_model(jax.random.PRNGKey(seed), cfg))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n} ({n / 1e9:.3f} B), init incl. compile "
          f"{time.perf_counter() - t0:.1f} s")
    print("serve:", serve_phase(params, cfg, rng))
    prompt = prompts(cfg, rng, 1)[0]
    pd = prefill_decode_phase(params, cfg, prompt)
    einsum = pd.pop("_prefill")
    print(f"prefill/decode ({len(prompt)}-token prompt):", pd)
    print("kernels:", kernel_phase(params, cfg, prompt, einsum))
    print("x64:", x64_phase(params, cfg))


def four_chips(cfg, seed: int) -> None:
    print("train:", train_phase(cfg, seed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {len(devices)} chip(s), {args.chips} needed",
              file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}")
    print("compile cache:", place_compile_cache())
    cfg = get_config(ARCH)
    print(f"config: {cfg.name} n_layers={cfg.n_layers} "
          f"d_model={cfg.d_model} d_ff={cfg.d_ff} vocab={cfg.vocab} "
          f"dtype={jnp.dtype(cfg.dtype).name}")
    try:
        if args.chips == 4:
            four_chips(cfg, args.seed)
        else:
            one_chip(cfg, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    print("peak_bytes_in_use:", stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
