"""Training launcher.

    # smoke run on local devices:
    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
        --steps 50 --batch 8 --seq 64

    # production shape (requires a real 256/512-chip backend):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        --shape train_4k [--multipod]

The production mesh is validated without its 256/512 chips by
``repro.launch.dryrun`` (compile-only); the launcher itself is the same
code path a TPU deployment runs.  The compile cache goes where
``launch.compile_cache`` says.
"""
from __future__ import annotations

import argparse

from repro.configs import ARCHS, SHAPES, get_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import place_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim.adamw import AdamWConfig
from repro.runtime.steps import pick_microbatches
from repro.runtime.train_loop import TrainLoopConfig, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on local devices")
    ap.add_argument("--shape", choices=[s for s in SHAPES
                                        if SHAPES[s].kind == "train"],
                    default="train_4k")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()
    place_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    shape = SHAPES[args.shape]
    batch = args.batch or (8 if args.smoke else shape.global_batch)
    seq = args.seq or (64 if args.smoke else shape.seq_len)

    if args.smoke:
        mesh_fn = make_host_mesh
        dp = 1
    else:
        mesh_fn = lambda: make_production_mesh(multi_pod=args.multipod)
        dp = 16 * (2 if args.multipod else 1)

    data = DataConfig(seq_len=seq, global_batch=batch, vocab=cfg.vocab)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20),
                      total_steps=args.steps)
    loop = TrainLoopConfig(
        steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, log_every=10,
        microbatches=pick_microbatches(cfg, batch, dp) if not args.smoke
        else min(2, batch))

    out = train(cfg, opt, loop, mesh_fn, data,
                on_metrics=lambda s, m: print(
                    f"step {s:5d}  loss {m['loss']:.4f}  "
                    f"gnorm {m['grad_norm']:.3f}"))
    print(f"finished: {len(out['history'])} logged steps, "
          f"{out['failures']} recovered failures")


if __name__ == "__main__":
    main()
