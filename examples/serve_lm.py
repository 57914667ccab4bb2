"""Two halves side by side: a PipeOrgan plan of a small model's decode
step, read from a persisted plan artifact, and that model served with
batched greedy decoding (optionally over an int8 KV cache).

The plan half: the first run ("warm-up") plans the decode graph
(``configs.lm_graphs.decode_graph``) once and files the plan as a
``PlanArtifact`` in a ``PlanStore`` directory; every later run admits
the artifact with ZERO planner invocations — asserted below via the
facade's cache counters.  The serve half jits ``make_serve_step`` and
reads nothing of the plan: the serving runtime does not import the
planner.

    PYTHONPATH=src python examples/serve_lm.py --tokens 32 --kv-quant \
        [--plan-store DIR]
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.lm_graphs import decode_graph
from repro.core import PAPER_HW, PlanRequest, PlanStore, Topology, get_planner
from repro.models import init_cache, init_model
from repro.runtime.steps import make_serve_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--plan-store", default=".pipeorgan_plans",
                    help="directory of serialized plan artifacts")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)  # reduced config on CPU
    if args.kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)

    # -- accelerator plan: artifact first, planner only on a cold store ----
    planner = get_planner()
    store = PlanStore(args.plan_store)
    request = PlanRequest(decode_graph(cfg), hw=PAPER_HW,
                          topology=Topology.AMP)
    plan = store.load(request)
    if plan is None:                       # warm-up: plan once, persist
        plan = planner.plan(request)
        path = store.save(request, plan)
        print(f"warm-up: planned and saved artifact -> {path}")
    misses_before = planner.cache_info().misses
    served = store.load(request)           # the serving path
    assert served is not None
    assert planner.cache_info().misses == misses_before, \
        "serving made a planner invocation despite a warm store"
    print(f"decode plan from store ({store.info()[0]} store hits, "
          f"0 planner invocations): {served.latency_cycles:.3e} cycles"
          f"/token, {served.dram_bytes:.3e} DRAM B/token")

    params = init_model(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_serve_step(cfg))

    B, T = args.batch, args.tokens + 8
    cache = init_cache(cfg, B, T)
    toks = jnp.ones((B, 1), jnp.int32)
    generated = [toks]
    t0 = time.perf_counter()
    for i in range(args.tokens):
        toks, cache = step(params, toks, cache, jnp.int32(i))
        generated.append(toks)
    jax.block_until_ready(toks)
    dt = time.perf_counter() - t0
    seq = jnp.concatenate(generated, axis=1)
    print(f"arch={cfg.name} kv_quant={cfg.kv_quant}")
    print(f"generated {args.tokens} tokens x batch {B} in {dt*1e3:.1f} ms "
          f"({args.tokens*B/dt:.0f} tok/s on CPU smoke config)")
    print("sample:", seq[0].tolist())


if __name__ == "__main__":
    main()
