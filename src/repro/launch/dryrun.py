"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each supported cell this jits the real step function (train_step with
optimizer for train shapes, prefill/serve steps for inference shapes) with
explicit in/out shardings on the production mesh, compiles it, and records

  * memory_analysis()  — proves the cell fits per-device HBM,
  * cost_analysis()    — HLO FLOPs / bytes for the roofline,
  * collective bytes   — parsed from the post-SPMD compiled HLO,

into benchmarks/results/dryrun_<mesh>_<arch>_<shape>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
      --shape train_4k [--multipod] [--all] [--list]
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede every other import (jax locks device count on first init).

import argparse
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import (ARCHS, SHAPES, cell_supported, decode_input_specs,
                           get_config, input_specs)
from repro.distributed.sharding import (batch_shardings, cache_shardings,
                                        params_shardings, replicated)
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import init_model
from repro.optim.adamw import AdamWConfig, init_state
from repro.runtime.steps import (make_prefill_step, make_serve_step,
                                 make_train_step, pick_microbatches)

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results"

_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)",
)

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-operand bytes of every collective in post-SPMD HLO."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    count = {k: 0 for k in out}
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(2), m.group(3), m.group(4)
        if dtype not in _DTYPE_BYTES:
            continue
        numel = 1
        if dims:
            for d in dims.split(","):
                if d:
                    numel *= int(d)
        out[kind] += numel * _DTYPE_BYTES[dtype]
        count[kind] += 1
    return {"bytes": out, "count": count,
            "total_bytes": sum(out.values())}


def _mem_dict(mem) -> dict:
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes"]
    d = {}
    for k in keys:
        v = getattr(mem, k, None)
        if callable(v):
            v = v()
        if v is not None:
            d[k] = int(v)
    return d


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, kv_quant: bool = False) -> dict:
    import dataclasses as _dc
    cfg = get_config(arch)
    if kv_quant and SHAPES[shape_name].kind == "decode" \
            and cfg.arch_kind in ("dense", "moe", "vlm"):
        cfg = _dc.replace(cfg, kv_quant=True)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    dp = mesh.shape["data"] * (mesh.shape.get("pod", 1))
    t0 = time.time()

    params_shape = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg))
    p_shard = params_shardings(cfg, params_shape, mesh)

    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        mb = pick_microbatches(cfg, shape.global_batch, dp)
        daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        step = make_train_step(cfg, opt_cfg, microbatches=mb,
                               data_axes=daxes)
        opt_shape = jax.eval_shape(lambda: init_state(params_shape))
        o_shard = type(opt_shape)(step=replicated(mesh),
                                  mu=params_shardings(cfg, opt_shape.mu, mesh),
                                  nu=params_shardings(cfg, opt_shape.nu, mesh))
        specs = input_specs(cfg, shape)
        b_shard = batch_shardings(cfg, specs, mesh)
        jitted = jax.jit(step,
                         in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))
        args = (params_shape, opt_shape, specs)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg)
        specs = input_specs(cfg, shape)
        specs.pop("labels", None)
        b_shard = batch_shardings(cfg, specs, mesh)
        jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
        args = (params_shape, specs)
    else:  # decode
        step = make_serve_step(cfg)
        dspecs = decode_input_specs(cfg, shape)
        c_shard = cache_shardings(cfg, dspecs["cache"], mesh)
        t_shard = batch_shardings(cfg, {"t": dspecs["tokens"]}, mesh)["t"]
        jitted = jax.jit(step,
                         in_shardings=(p_shard, t_shard, c_shard,
                                       replicated(mesh)),
                         out_shardings=(t_shard, c_shard),
                         donate_argnums=(2,))
        args = (params_shape, dspecs["tokens"], dspecs["cache"],
                dspecs["index"])

    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": int(n_chips),
        "kind": shape.kind,
        "microbatches": (pick_microbatches(cfg, shape.global_batch, dp)
                         if shape.kind == "train" else 1),
        "memory": _mem_dict(mem),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
        "kv_quant": bool(cfg.kv_quant),
        "compile_seconds": round(time.time() - t0, 1),
    }
    if verbose:
        per_dev = result["memory"].get("temp_size_in_bytes", 0) / 2**30
        print(f"[dryrun] {arch} x {shape_name} x {result['mesh']}: "
              f"OK  temp={per_dev:.2f}GiB/dev  "
              f"flops={result['flops']:.3e}  "
              f"coll={coll['total_bytes']:.3e}B  "
              f"({result['compile_seconds']}s)")
        print(f"  memory_analysis: {result['memory']}")
    return result


def save_result(res: dict) -> Path:
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    name = f"dryrun_{res['mesh'].replace('x','-')}_{res['arch']}_{res['shape']}.json"
    path = RESULTS_DIR / name
    path.write_text(json.dumps(res, indent=1))
    return path


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache for decode cells (beyond-paper)")
    args = ap.parse_args()

    cells = []
    for arch in (sorted(ARCHS) if args.arch is None else [args.arch]):
        for shape in (sorted(SHAPES) if args.shape is None else [args.shape]):
            meshes = [args.multipod]
            if args.both_meshes:
                meshes = [False, True]
            for mp in meshes:
                cells.append((arch, shape, mp))
    if not (args.all or (args.arch and args.shape)):
        ap.error("pass --all or both --arch and --shape")

    if args.list:
        for c in cells:
            sup = cell_supported(c[0], c[1])
            print(("RUN " if sup else "SKIP"), *c)
        return 0

    failures = []
    for arch, shape, mp in cells:
        mesh_tag = "2-16-16" if mp else "16-16"
        out = RESULTS_DIR / f"dryrun_{mesh_tag}_{arch}_{shape}.json"
        if args.skip_existing and out.exists():
            print(f"[dryrun] {arch} x {shape} x {mesh_tag}: cached")
            continue
        if not cell_supported(arch, shape):
            res = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16",
                   "skipped": True,
                   "reason": "long_500k requires sub-quadratic attention "
                             "(see DESIGN.md Arch-applicability)"}
            save_result(res)
            print(f"[dryrun] {arch} x {shape}: SKIP (documented)")
            continue
        try:
            res = run_cell(arch, shape, mp, kv_quant=args.kv_quant)
            save_result(res)
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            failures.append((arch, shape, mp, repr(e)[:400]))
            print(f"[dryrun] {arch} x {shape} x {mesh_tag}: FAIL {e!r}"[:500])
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        return 1
    print("\nall requested cells passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
