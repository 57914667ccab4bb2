"""Serving launcher: continuous-batching engine over a slot pool.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b [--smoke] \
        --requests 6 --max-new 12 [--kv-quant]

Without ``--smoke`` the architecture's published config is served (for
qwen2.5-3b: 36 layers, d_model 2048, bf16 weights; one TPU v5e holds
it).  The compile cache goes where ``launch.compile_cache`` says.  The
single-engine path plans nothing: the serving runtime does not import
the planner.

At the end of a run the engine's counters from ``ServeEngine.stats()``
are printed, one line per lane: requests admitted, slot wipes, prompt
and decode slot-ticks, the longest queue at a tick's start, and spans
the telemetry ring overwrote.

``--tenants "name:share[:priority],..."`` serves several architectures as
co-resident tenants on one substrate instead: their decode graphs
(``configs.lm_graphs.decode_graph``) go through
``core.multi_tenant.resolve_multi_tenant`` (spatial column bands / time
slices / serialized, under the double guard, with cross-tenant link +
DRAM interference priced), and an ``AdmissionScheduler`` drives one
``ServeEngine`` per tenant in the resolved plan's mode.  With
``--plan-store`` the multi-tenant plan is admitted from / saved to a
directory of serialized artifacts, so a warm store resolves the tenants
with zero planner invocations:

    PYTHONPATH=src python -m repro.launch.serve --smoke \
        --tenants "qwen2.5-3b:2:1,qwen2.5-3b:1" [--plan-store DIR]

The engine jits ``runtime.steps.make_serve_step``: the same serve_step
the dry-run compiles for decode_32k / long_500k.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from repro.configs import ARCHS, get_config
from repro.configs.lm_graphs import decode_graph
from repro.core import (MultiTenantRequest, PAPER_HW, PlanRequest, PlanStore,
                        TenantSpec, Topology, resolve_multi_tenant)
from repro.launch.compile_cache import place_compile_cache
from repro.models import init_model
from repro.runtime.serve_loop import AdmissionScheduler, Request, ServeEngine


COUNTERS = ("admitted", "wipes", "prompt_tokens", "decode_tokens",
            "prefill_chunks", "chunk_tokens", "queue_peak", "spans_dropped")


def counter_line(engine: ServeEngine) -> str:
    """The engine's counters from ``stats()``, for the end of a run."""
    st = engine.stats()
    return ", ".join(f"{k} {st[k]:.0f}" for k in COUNTERS)


def parse_tenants(spec: str) -> list:
    """Parse ``"arch[:share[:priority]],..."`` into (arch, share, prio)."""
    out = []
    for i, part in enumerate(filter(None, spec.split(","))):
        bits = part.split(":")
        if len(bits) > 3 or not bits[0]:
            raise ValueError(f"bad tenant spec {part!r}; "
                             "expected arch[:share[:priority]]")
        arch = bits[0]
        share = float(bits[1]) if len(bits) > 1 else 1.0
        prio = int(bits[2]) if len(bits) > 2 else 0
        out.append((arch, share, prio))
    if len(out) < 2:
        raise ValueError("--tenants needs at least two tenants")
    return out


def serve_tenants(args) -> dict:
    """The multi-tenant serving path: plan the substrate split, then run
    one admission-scheduled engine per tenant; returns each tenant's
    completed requests."""
    tenants = parse_tenants(args.tenants)
    plan_store = PlanStore(args.plan_store) if args.plan_store else None

    specs, engines = [], {}
    for i, (arch, share, prio) in enumerate(tenants):
        cfg = get_config(arch, smoke=args.smoke)
        if args.kv_quant:
            cfg = dataclasses.replace(cfg, kv_quant=True)
        name = f"{arch}#{i}"
        graph = decode_graph(cfg)
        # tenant graphs need distinct names for distinct tenants of one
        # arch (the plan keys tenants by name)
        graph = dataclasses.replace(graph, name=f"{graph.name}#{i}")
        specs.append(TenantSpec(
            PlanRequest(graph, hw=PAPER_HW, topology=Topology.AMP),
            share=share, priority=prio, name=name))
        params = init_model(jax.random.PRNGKey(i), cfg)
        engines[name] = ServeEngine(params, cfg, batch_slots=args.slots,
                                    max_len=args.max_len)

    mt_request = MultiTenantRequest(tuple(specs))
    t0 = time.perf_counter()
    plan = resolve_multi_tenant(mt_request, store=plan_store)
    t_plan = time.perf_counter() - t0
    print(f"multi-tenant plan: mode={plan.mode} "
          f"source={getattr(plan, 'source', 'planner')} ({t_plan*1e3:.0f} ms)")
    print(f"  makespan {plan.makespan_cycles:.3e} cy vs serialized "
          f"{plan.serialized_cycles:.3e} cy "
          f"(speedup {plan.speedup_vs_serialized:.2f}x), "
          f"DRAM {plan.dram_bytes:.3e} B vs {plan.serialized_dram:.3e} B")
    for t in plan.tenants:
        band = f"cols[{t.band[0]}:{t.band[1]})" if t.band else "whole array"
        print(f"  {t.name}: {band}, {t.latency_cycles:.3e} cy/token, "
              f"dram_bw_fraction={t.dram_bw_fraction:.2f}, "
              f"link_dx={t.link_interference:.1f}")

    sched = AdmissionScheduler.from_plan(plan, engines)
    rid = 0
    for name in engines:           # a bursty stream per tenant
        for _ in range(args.requests):
            sched.submit(name, Request(rid=rid,
                                       prompt=[2 + rid, 7, 3 * rid + 1],
                                       max_new_tokens=args.max_new))
            rid += 1
    t0 = time.perf_counter()
    done = sched.run()
    dt = time.perf_counter() - t0
    total = sum(len(r.output) for v in done.values() for r in v)
    print(f"served {sum(map(len, done.values()))} requests / {total} tokens "
          f"in {dt*1e3:.0f} ms across {len(engines)} tenants "
          f"(mode={sched.mode})")
    st = sched.stats()
    for name in sorted(engines):
        print(f"  {name}: {st[f'{name}.completed']:.0f} done, "
              f"mean finish tick {st.get(f'{name}.mean_finish_tick', 0):.1f}"
              f"; {counter_line(engines[name])}")
    return done


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (2 layers, width 64)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--plan-store", default=None, metavar="DIR",
                    help="with --tenants: admit/persist the multi-tenant "
                         "plan as an artifact in DIR")
    ap.add_argument("--tenants", default=None, metavar="SPEC",
                    help='serve co-resident tenants on one substrate: '
                         '"arch[:share[:priority]],..." (>= 2 entries)')
    return ap


def main() -> None:
    args = parser().parse_args()
    place_compile_cache()
    if args.tenants:
        serve_tenants(args)
        return

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, batch_slots=args.slots,
                         max_len=args.max_len)
    for i in range(args.requests):
        engine.submit(Request(rid=i, prompt=[2 + i, 7, 3 * i + 1],
                              max_new_tokens=args.max_new))
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    total = sum(len(r.output) for r in done)
    print(f"served {len(done)} requests / {total} tokens in {dt*1e3:.0f} ms "
          f"({total/dt:.0f} tok/s, {args.slots} slots, "
          f"kv_quant={cfg.kv_quant})")
    print(f"engine: {counter_line(engine)}")
    for r in sorted(done, key=lambda r: r.rid)[:3]:
        print(f"  rid={r.rid} out={r.output}")


if __name__ == "__main__":
    main()
