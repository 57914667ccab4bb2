"""Fault-tolerant training loop.

Design for 1000+ nodes:
  * step-atomic async checkpoints every ``ckpt_every`` steps;
  * on step failure (device loss / preemption / injected fault) the loop
    re-forms the mesh from the surviving devices (elastic re-mesh: the
    data axis shrinks, the model axis is preserved so no parameter shard
    is lost beyond what the checkpoint restores), re-jits, restores the
    latest checkpoint and continues — deterministic data means the
    restart replays the exact global batches;
  * bounded-staleness straggler policy: because the step is a scan of
    microbatches, a replica that exceeds ``step_timeout`` can be dropped
    for one step by shrinking the data axis (same elastic path).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.checkpoint.store import AsyncCheckpointer, latest_step, restore
from repro.data.pipeline import DataConfig, TokenDataset
from repro.distributed.sharding import batch_shardings, params_shardings, replicated
from repro.models.common import ModelConfig
from repro.models.transformer import init_model
from repro.optim.adamw import AdamWConfig, AdamWState, init_state
from repro.runtime.steps import make_train_step


@dataclasses.dataclass
class TrainLoopConfig:
    steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    log_every: int = 10
    microbatches: int = 1
    seed: int = 0
    max_failures: int = 3


class NodeFailure(RuntimeError):
    """A simulated node failure (raised by ``FaultInjector``)."""


#: what the loop recovers from: device/runtime failures and the injected
#: fault.  Trace and compile errors are raised by ``_build``, outside it.
RECOVERABLE = (NodeFailure, jax.errors.JaxRuntimeError)


class FaultInjector:
    """Test hook: raise at a chosen step to simulate a node failure."""

    def __init__(self, fail_at: Optional[int] = None):
        self.fail_at = fail_at
        self.fired = False

    def check(self, step: int) -> None:
        if self.fail_at is not None and step == self.fail_at and not self.fired:
            self.fired = True
            raise NodeFailure(f"injected node failure at step {step}")


def _build(cfg: ModelConfig, opt_cfg: AdamWConfig, loop: TrainLoopConfig,
           mesh, data_cfg: DataConfig):
    """Initial state on ``mesh`` plus the step, compiled ahead of time so
    that a trace or compile error raises here, never inside the loop's
    failure handling.  Params and optimizer state are donated to the step
    and come back with the shardings they went in with."""
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    params_shape = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(loop.seed), cfg))
    p_shard = params_shardings(cfg, params_shape, mesh)
    # moments mirror the (already FSDP/TP-sharded) params => ZeRO states
    o_shard = AdamWState(step=replicated(mesh), mu=p_shard, nu=p_shard)
    step_fn = make_train_step(cfg, opt_cfg, microbatches=loop.microbatches,
                              data_axes=daxes)
    specs = {
        "tokens": jax.ShapeDtypeStruct(
            (data_cfg.global_batch, data_cfg.seq_len), jax.numpy.int32),
        "labels": jax.ShapeDtypeStruct(
            (data_cfg.global_batch, data_cfg.seq_len), jax.numpy.int32),
    }
    b_shard = batch_shardings(cfg, specs, mesh)
    with jax.set_mesh(mesh):
        params = jax.jit(lambda: init_model(jax.random.PRNGKey(loop.seed),
                                            cfg), out_shardings=p_shard)()
        opt_state = jax.jit(init_state, out_shardings=o_shard)(params)
        step = jax.jit(step_fn, in_shardings=(p_shard, o_shard, b_shard),
                       out_shardings=(p_shard, o_shard, replicated(mesh)),
                       donate_argnums=(0, 1)
                       ).lower(params, opt_state, specs).compile()
    return params, opt_state, step, b_shard, p_shard, o_shard


def train(cfg: ModelConfig, opt_cfg: AdamWConfig, loop: TrainLoopConfig,
          mesh_fn: Callable[[], Any], data_cfg: DataConfig,
          fault: Optional[FaultInjector] = None,
          on_metrics: Optional[Callable[[int, Dict], None]] = None
          ) -> Dict[str, Any]:
    """Run the loop; returns final params and a metrics history."""
    ds = TokenDataset(data_cfg)
    ckpt = AsyncCheckpointer(loop.ckpt_dir) if loop.ckpt_dir else None
    history = []
    failures = 0
    step = 0

    mesh = mesh_fn()
    params, opt_state, step_exe, b_shard, p_shard, o_shard = _build(
        cfg, opt_cfg, loop, mesh, data_cfg)

    # resume
    def _restore_all(params, opt_state, p_shard, o_shard):
        last = latest_step(loop.ckpt_dir)
        if last is None:
            return params, opt_state, 0
        tree = restore(loop.ckpt_dir, last,
                       {"params": params, "opt": opt_state},
                       {"params": p_shard, "opt": o_shard})
        print(f"[train] resumed from step {last}")
        return tree["params"], tree["opt"], last

    if loop.ckpt_dir:
        params, opt_state, step = _restore_all(params, opt_state, p_shard,
                                               o_shard)

    while step < loop.steps:
        try:
            host = ds.global_batch_at(step)
            batch = {k: jax.device_put(v, b_shard[k])
                     for k, v in host.items()}
            if fault is not None:
                fault.check(step)
            params, opt_state, metrics = step_exe(params, opt_state, batch)
            step += 1
            if step % loop.log_every == 0 or step == loop.steps:
                m = {k: float(np.asarray(v)) for k, v in metrics.items()}
                history.append({"step": step, **m})
                if on_metrics:
                    on_metrics(step, m)
            if ckpt and step % loop.ckpt_every == 0:
                ckpt.save_async(step, {"params": params, "opt": opt_state},
                                {"model": cfg.name})
        except RECOVERABLE as e:
            failures += 1
            if failures > loop.max_failures:
                raise
            print(f"[train] step {step} failed ({e}); re-forming mesh and "
                  f"restoring (failure {failures}/{loop.max_failures})")
            if ckpt:
                ckpt.wait()
            mesh = mesh_fn()  # elastic: survivors form the new mesh
            params, opt_state, step_exe, b_shard, p_shard, o_shard = _build(
                cfg, opt_cfg, loop, mesh, data_cfg)
            if loop.ckpt_dir and latest_step(loop.ckpt_dir) is not None:
                params, opt_state, step = _restore_all(
                    params, opt_state, p_shard, o_shard)
            else:
                step = 0

    if ckpt:
        ckpt.wait()
    return {"params": params, "opt_state": opt_state, "history": history,
            "failures": failures}
