"""The system under test for serving cells, and what its ticks record.

``build`` makes the program's ``ServeEngine`` on the benchmark's seeded
weights and warms up every program a serving run calls: the decode step
and the slot wipe on admission.  ``Recorder`` drives the engine one tick
at a time and keeps, on the host clock, each tick's start and end, how
many slots ran and how many keys their queries saw, and for each request
its due time, the tick that admitted it and the time of each of its
output tokens.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench.lib import weights


def program_keys(c: dict) -> dict:
    """Program-config fields beyond the GQA ones that the file's keys fix
    (a latent rank, a count of shared experts): {field: value} from the
    layout module's ``program_keys(c)``, where it has one."""
    mod = weights.layout_module(c)
    return dict(mod.program_keys(c)) if hasattr(mod, "program_keys") else {}


def build(c: dict, layout: dict, seed: int, phases: Optional[dict] = None):
    """The program's engine on weights made in one jitted call.  Seconds
    of each phase of set-up go into ``phases`` where given."""
    phases = {} if phases is None else phases
    last = [time.perf_counter()]

    def mark(name: str) -> None:
        now = time.perf_counter()
        phases[name], last[0] = now - last[0], now
    from repro.configs import get_config
    from repro.models import init_model
    from repro.runtime.serve_loop import Request, ServeEngine

    cfg = get_config(c["arch"], smoke=c.get("smoke", False))
    expect = {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
              "n_layers": c["num_hidden_layers"],
              "n_heads": c["num_attention_heads"],
              "n_kv_heads": c["num_key_value_heads"], "hd": c["head_dim"],
              "vocab": c["vocab_size"], "rope_theta": c["rope_theta"],
              "norm_eps": c["rms_norm_eps"],
              "tie_embeddings": c["tie_word_embeddings"],
              "qkv_bias": c["attention_bias"],
              "n_experts": c.get("num_local_experts", 0),
              "top_k": c.get("num_experts_per_tok", 0),
              **program_keys(c)}
    got = {k: getattr(cfg, k, None) for k in expect}
    if got != expect:
        raise SystemExit(f"program config {c['arch']} is not the file's: "
                         f"{got} != {expect}")
    want = weights.tree_signature(jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg)))
    lo, hi = weights.seed_words(seed)
    params = jax.block_until_ready(
        weights.make_tree(layout, weights.stacks(c))(lo, hi))
    mark("weights")
    if weights.tree_signature(params) != want:
        raise SystemExit("the program's parameter tree has changed: "
                         "bench/weights no longer matches init_model")
    engine = ServeEngine(params, cfg, batch_slots=c["serve"]["slots"],
                         max_len=c["serve"]["max_len"])
    mark("engine")
    # warm-up: one tick fills every slot, the next admits into a used
    # slot (the eager wipe); nothing else is compiled in a run
    for rid in range(engine.B + 1):
        engine.submit(Request(rid=-1 - rid, prompt=[0], max_new_tokens=1))
    engine.run()
    jax.block_until_ready(engine.cache)
    engine.ticks = 0
    mark("warm_up")
    return engine


@dataclasses.dataclass
class ReqRecord:
    rid: int
    due: float                      # host clock
    request: object                 # the program's Request
    admitted: float = float("nan")  # start of the tick that admitted it
    token_times: List[float] = dataclasses.field(default_factory=list)


class Recorder:
    clock = staticmethod(time.perf_counter)

    def __init__(self, engine):
        self.engine = engine
        self.ticks: List[tuple] = []    # (t0, t1, live, decoded, keys)
        self.reqs: Dict[int, ReqRecord] = {}
        self.finished: List[ReqRecord] = []
        self._slot: Dict[int, int] = {}

    def submit(self, r, due: float) -> None:
        from repro.runtime.serve_loop import Request
        req = Request(rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new)
        self.reqs[r.rid] = ReqRecord(r.rid, due, req)
        self.engine.submit(req)

    def idle(self) -> bool:
        e = self.engine
        return not e.queue and all(r is None for r in e.active)

    def tick(self) -> None:
        e = self.engine
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench.tick"):
            done = e.step()
        t1 = self.clock()
        live = decoded = keys = 0
        for slot, req in enumerate(e.active):
            if req is not None:
                self._slot[req.rid] = slot
        for req in [r for r in e.active if r is not None] + done:
            rec = self.reqs[req.rid]
            if rec.admitted != rec.admitted:            # nan: new this tick
                rec.admitted = t0
            live += 1
            if req.rid in self._slot:
                keys += int(e.pos[self._slot[req.rid]])
            while len(rec.token_times) < len(req.output):
                rec.token_times.append(t1)
                decoded += 1
        for req in done:
            self.finished.append(self.reqs[req.rid])
            self._slot.pop(req.rid, None)
        self.ticks.append((t0, t1, live, decoded, keys))

    def tick_array(self) -> np.ndarray:
        return np.asarray(self.ticks, np.float64).reshape(-1, 5)
