"""Batched serving loop with continuous batching.

Production shape: a fixed pool of B decode slots over one shared KV cache.
Requests (prompt + max_new_tokens) queue up; a slot that finishes (EOS or
budget) is immediately refilled with the next request's prompt.  Prefill
happens in the same jitted step as decoding: each tick, the prefilling
slot admitted earliest writes a chunk of up to ``chunk`` prompt tokens
into its cache rows beside every other slot's one token (for another
prefilling slot, its next prompt token).  A model whose cache is a
recurrent state (``transformer.takes_chunks``) is fed one prompt token a
slot a tick.

Per-slot state lives in plain arrays so the whole scheduler is
host-driven; the device program is the fused serve/prefill step, in two
shapes (with and without a chunk), which takes the KV cache donated:
each tick replaces ``engine.cache``, and a reference to the cache from
before a tick is invalid after it.

Each tick is a ``serve.tick`` span (``runtime.telemetry``) holding, in
order, ``serve.refill`` (with one ``serve.wipe`` per slot wiped),
``serve.feed``, ``serve.dispatch``, ``serve.sync`` and ``serve.retire``;
each request leaves ``serve.queued``, ``serve.prefill`` and
``serve.decode`` spans under its ``rid``.
"""
from __future__ import annotations

import dataclasses
import math
import time
import warnings
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention import block_t, rows_read
from repro.models.common import ModelConfig
from repro.models.transformer import (ChunkedTokens, init_cache,
                                      reads_kv_in_place,
                                      static_layer_windows, takes_chunks,
                                      zero_cache_slot)
from repro.runtime import telemetry
from repro.runtime.steps import make_serve_step


def jit_serve_step(cfg: ModelConfig):
    """The engine's device program: the serve step, jitted with the cache
    (argument 2) donated, so that each call updates the cache in place
    and the cache passed in is invalid after it.  It compiles once for
    (B, 1) tokens and once for ``ChunkedTokens``."""
    return jax.jit(make_serve_step(cfg), donate_argnums=(2,))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # time.perf_counter() at submit, placement in a slot, first output
    # token and completion (nan until then)
    submitted_at: float = math.nan
    admitted_at: float = math.nan
    first_token_at: float = math.nan
    finished_at: float = math.nan


class ServeEngine:
    """Continuous-batching engine over a fixed slot pool.

    ``chunk``: the most prompt tokens a tick carries for one slot (0: one
    token a slot a tick).  The default, 128, keeps a tick's rows (32
    slots + 128) under a v5e's ridge point, 197 TFLOP/s over 819 GB/s,
    about 240 rows a weight read: the chunk rides on the weight reads
    the slots' tokens make anyway.  It is cut to ``max_len``, and to 0
    where the model's cache is a recurrent state."""

    def __init__(self, params, cfg: ModelConfig, batch_slots: int,
                 max_len: int, chunk: int = 128):
        if chunk < 0:
            raise ValueError(f"chunk must be >= 0, not {chunk}")
        self.params = params
        self.cfg = cfg
        self.B = batch_slots
        self.max_len = max_len
        self.chunk = min(chunk, max_len) if takes_chunks(cfg) else 0
        self.cache = init_cache(cfg, batch_slots, max_len)
        self.queue: Deque[Request] = deque()
        self.active: List[Optional[Request]] = [None] * batch_slots
        # per-slot cursors
        self.pos = np.zeros(batch_slots, np.int32)        # next cache index
        self.remaining_prompt: List[List[int]] = [[] for _ in range(batch_slots)]
        self.generated = np.zeros(batch_slots, np.int32)
        # slots that have ever held a request: their cache rows must be
        # wiped before reuse so the next occupant can't attend to them
        self._slot_dirty = np.zeros(batch_slots, bool)
        self._step = jit_serve_step(cfg)
        # the decode read's block of positions where the kernel reads the
        # cache in place, up to each slot's cursor (on a TPU); None where
        # the einsum path reads every layer's rows whole
        row_bytes = cfg.n_kv_heads * cfg.hd * jnp.dtype(cfg.dtype).itemsize
        self.read_block = (block_t(max_len, row_bytes)
                           if jax.default_backend() == "tpu"
                           and reads_kv_in_place(cfg) else None)
        self._windows = np.asarray(static_layer_windows(cfg), np.int64)
        self.ticks = 0
        self.truncated = False
        # counters, reported by stats()
        self.admitted = 0
        self.wipes = 0
        self.prompt_tokens = 0
        self.decode_tokens = 0
        self.prefill_chunks = 0
        self.chunk_tokens = 0
        self.queue_peak = 0
        self.kv_rows_read = 0
        self.kv_rows = 0

    # -- scheduling ----------------------------------------------------------
    def submit(self, req: Request) -> None:
        req.submitted_at = time.perf_counter()
        self.queue.append(req)

    def _refill(self) -> int:
        """Place queued requests in free slots; returns the slots wiped."""
        wiped = 0
        for slot in range(self.B):
            if self.active[slot] is None and self.queue:
                req = self.queue.popleft()
                if self._slot_dirty[slot]:
                    with telemetry.span("serve.wipe", req.rid):
                        self.cache = zero_cache_slot(self.cfg, self.cache,
                                                     slot)
                    self.wipes += 1
                    wiped += 1
                self._slot_dirty[slot] = True
                self.active[slot] = req
                self.remaining_prompt[slot] = list(req.prompt)
                self.pos[slot] = 0
                self.generated[slot] = 0
                self.admitted += 1
                req.admitted_at = time.perf_counter()
                telemetry.record("serve.queued", req.submitted_at,
                                 req.admitted_at, req.rid)
        return wiped

    def _chunk_slot(self) -> Optional[int]:
        """The prefilling slot admitted earliest, if any (one refill
        places requests in slot order, and ``min`` keeps the first of
        equal times)."""
        waiting = [s for s, r in enumerate(self.active)
                   if r is not None and self.remaining_prompt[s]]
        return min(waiting, key=lambda s: self.active[s].admitted_at,
                   default=None)

    def step(self) -> List[Request]:
        """One engine tick: feed each slot its next token (prompt token if
        still prefilling, else the model's own last sample), and, with
        chunks, the tick's chunk to the prefilling slot admitted
        earliest; returns any requests completed this tick."""
        with telemetry.span("serve.tick"):
            self.queue_peak = max(self.queue_peak, len(self.queue))
            with telemetry.span("serve.refill"):
                wiped = self._refill()
            self.ticks += 1
            with telemetry.span("serve.feed"):
                # a tick that wipes carries no chunk: the wipe already
                # makes it the longest tick, which sets the tail of the
                # gaps between tokens
                chunk_slot = (self._chunk_slot()
                              if self.chunk and not wiped else None)
                feed = np.zeros((self.B, 1), np.int32)
                fed = np.ones(self.B, np.int32)      # tokens each slot takes
                for slot, req in enumerate(self.active):
                    if req is None or slot == chunk_slot:
                        continue
                    if self.remaining_prompt[slot]:
                        feed[slot, 0] = self.remaining_prompt[slot].pop(0)
                    elif req.output:
                        feed[slot, 0] = req.output[-1]
                    else:
                        # empty prompt: nothing to condition on — feed
                        # token 0 (BOS convention) so generation starts
                        # from position 0
                        feed[slot, 0] = req.prompt[-1] if req.prompt else 0
                tokens = feed
                if chunk_slot is not None:
                    prompt = self.remaining_prompt[chunk_slot]
                    n = min(self.chunk, len(prompt))
                    ids = np.zeros(self.chunk, np.int32)
                    ids[:n] = prompt[:n]
                    self.remaining_prompt[chunk_slot] = prompt[n:]
                    fed[chunk_slot] = n
                    self.prefill_chunks += 1
                    self.chunk_tokens += n
                    tokens = ChunkedTokens(feed, ids, np.int32(chunk_slot),
                                           np.int32(n))
                # each slot decodes at its own cursor: the per-slot index
                # vector keeps a refilled slot's writes and causal mask at
                # *its* fill level, not the pool-wide maximum (which would
                # let a fresh request attend to the previous occupant's
                # cache rows)
                index = jnp.asarray(self.pos, jnp.int32)
                rows = self._windows.size * self.B * self.max_len
                self.kv_rows += rows
                self.kv_rows_read += (rows_read(self.pos, self._windows,
                                                self.max_len, self.read_block)
                                      if self.read_block else rows)
                tokens = jax.device_put(tokens)
            with telemetry.span("serve.dispatch"):
                nxt, self.cache = self._step(self.params, tokens, self.cache,
                                             index)
            with telemetry.span("serve.sync"):
                nxt = np.asarray(nxt)[:, 0]
            with telemetry.span("serve.retire"):
                return self._retire(nxt, fed)

    def _retire(self, nxt: np.ndarray, fed: np.ndarray) -> List[Request]:
        """Advance every live slot past the ``fed`` tokens it took this
        tick; a slot whose prompt is not yet all fed counts them as
        prompt tokens, any other yields an output token."""
        now = time.perf_counter()
        finished = []
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            self.pos[slot] += fed[slot]
            if self.remaining_prompt[slot]:
                self.prompt_tokens += int(fed[slot])
                continue                     # still prefilling
            self.prompt_tokens += int(fed[slot]) - 1
            tok = int(nxt[slot])
            if not req.output:
                req.first_token_at = now
                telemetry.record("serve.prefill", req.admitted_at, now,
                                 req.rid)
            req.output.append(tok)
            self.decode_tokens += 1
            self.generated[slot] += 1
            hit_eos = req.eos_id is not None and tok == req.eos_id
            if (self.generated[slot] >= req.max_new_tokens or hit_eos
                    or self.pos[slot] >= self.max_len - 1):
                req.done = True
                req.finished_at = now
                telemetry.record("serve.decode", req.first_token_at, now,
                                 req.rid)
                finished.append(req)
                self.active[slot] = None
        return finished

    def run(self, max_ticks: int = 10_000) -> List[Request]:
        done: List[Request] = []
        ticks = 0
        self.truncated = False
        while (self.queue or any(self.active)) and ticks < max_ticks:
            done.extend(self.step())
            ticks += 1
        if self.queue or any(r is not None for r in self.active):
            self.truncated = True
            warnings.warn(
                f"ServeEngine.run() stopped at max_ticks={max_ticks} with "
                f"{len(self.queue)} queued and "
                f"{sum(r is not None for r in self.active)} active "
                "requests unfinished; results are truncated "
                '(see stats()["truncated"])', RuntimeWarning, stacklevel=2)
        return done

    def stats(self) -> Dict[str, float]:
        """Engine counters.  ``admitted``: requests placed in a slot; ``wipes``:
        slot wipes on placement into a used slot; ``prompt_tokens``:
        prompt tokens fed that yielded no output (all of a prompt but its
        last token, whose logits give the first output token);
        ``decode_tokens``: output tokens; so the two sum to the tokens
        written into the cache.  ``prefill_chunks``: ticks that carried a
        prompt chunk; ``chunk_tokens``: the real prompt tokens they
        carried (over ``prefill_chunks`` x ``chunk``: the chunks' fill
        share).  ``queue_peak``: the longest queue at the start of a
        tick; ``kv_read_share``: the share of the cache's (layer, slot,
        position) rows that the ticks' one-token reads touched, 1.0 where
        the einsum path reads them whole; ``spans_dropped``: spans the
        telemetry ring overwrote."""
        return {
            "ticks": float(self.ticks),
            "queued": float(len(self.queue)),
            "active": float(sum(r is not None for r in self.active)),
            "truncated": float(self.truncated),
            "admitted": float(self.admitted),
            "wipes": float(self.wipes),
            "prompt_tokens": float(self.prompt_tokens),
            "decode_tokens": float(self.decode_tokens),
            "prefill_chunks": float(self.prefill_chunks),
            "chunk_tokens": float(self.chunk_tokens),
            "queue_peak": float(self.queue_peak),
            "kv_read_share": (self.kv_rows_read / self.kv_rows
                              if self.kv_rows else 0.0),
            "spans_dropped": float(telemetry.dropped()),
        }


@dataclasses.dataclass
class Lane:
    """One tenant's serving lane: its engine plus scheduling weights.

    ``share`` weights the time-multiplexed round-robin; ``priority``
    orders admission (higher first).  ``deficit`` is the weighted
    round-robin credit counter (internal).
    """
    name: str
    engine: ServeEngine
    share: float = 1.0
    priority: int = 0
    deficit: float = dataclasses.field(default=0.0, compare=False)

    def __post_init__(self) -> None:
        if self.share <= 0:
            raise ValueError("lane share must be > 0")


class AdmissionScheduler:
    """Maps bursty request streams onto tenant lanes over one substrate.

    The execution-side counterpart of ``core.multi_tenant``: a resolved
    ``MultiTenantPlan`` says *how* the tenants share the array, and this
    scheduler drives their ``ServeEngine``s accordingly —

      * ``"spatial"`` — tenants sit on disjoint column bands, so every
        lane with work ticks each round (true concurrency);
      * ``"time"`` — one lane ticks per round, chosen by share-weighted
        deficit round-robin (each round every backlogged lane earns
        ``share`` credit; the largest credit runs and pays the total
        active share), so long-term tick rates converge to the shares;
      * ``"serialized"`` — strict priority order, shortest queue first
        within a priority level; a lane runs until it drains.

    Requests enter per-lane *pending* queues (``submit``) and are
    admitted into an engine only when it has a free decode slot — the
    engine-side queue never grows beyond the slot pool, so a burst on
    one tenant cannot occupy another tenant's admission window.
    """

    MODES = ("spatial", "time", "serialized")

    def __init__(self, lanes: List[Lane], mode: str = "spatial"):
        if mode not in self.MODES:
            raise ValueError(f"unknown mode {mode!r}; one of {self.MODES}")
        names = [l.name for l in lanes]
        if len(set(names)) != len(names):
            raise ValueError(f"lane names must be unique: {names}")
        self.lanes: Dict[str, Lane] = {l.name: l for l in lanes}
        self.mode = mode
        self.pending: Dict[str, Deque[Request]] = {n: deque() for n in names}
        self.done: Dict[str, List[Request]] = {n: [] for n in names}
        self.finish_tick: Dict[int, int] = {}      # rid -> scheduler tick
        self.ticks = 0
        self.truncated = False

    @classmethod
    def from_plan(cls, plan, engines: Dict[str, ServeEngine]
                  ) -> "AdmissionScheduler":
        """Build the scheduler a resolved ``MultiTenantPlan`` prescribes:
        one lane per tenant (its share/priority) in the plan's mode.  It
        reads only ``plan.tenants`` and ``plan.mode``, so this module
        needs no planner import."""
        lanes = [Lane(t.name, engines[t.name], t.share, t.priority)
                 for t in plan.tenants]
        return cls(lanes, mode=plan.mode)

    # -- admission -----------------------------------------------------------
    def submit(self, lane: str, req: Request) -> None:
        self.pending[lane].append(req)

    def _admit(self) -> None:
        """Admit pending requests into engines with free decode slots, in
        lane priority order (higher first) so a high-priority tenant's
        burst is never starved by a lower-priority backlog."""
        for lane in sorted(self.lanes.values(),
                           key=lambda l: (-l.priority, l.name)):
            pend = self.pending[lane.name]
            eng = lane.engine
            free = (sum(r is None for r in eng.active) - len(eng.queue))
            while pend and free > 0:
                eng.submit(pend.popleft())
                free -= 1

    # -- scheduling ----------------------------------------------------------
    def _backlogged(self) -> List[Lane]:
        return [l for l in self.lanes.values()
                if self.pending[l.name] or l.engine.queue
                or any(r is not None for r in l.engine.active)]

    def _pick_time_sliced(self, ready: List[Lane]) -> Lane:
        for l in ready:
            l.deficit += l.share
        pick = max(ready, key=lambda l: (l.deficit, l.share, l.name))
        pick.deficit -= sum(l.share for l in ready)
        return pick

    def _pick_serialized(self, ready: List[Lane]) -> Lane:
        return min(ready, key=lambda l: (-l.priority, l.name))

    def step(self) -> List[Request]:
        """One scheduler round; returns requests completed this round."""
        self._admit()
        self.ticks += 1
        ready = self._backlogged()
        if not ready:
            return []
        if self.mode == "spatial":
            running = ready
        elif self.mode == "time":
            running = [self._pick_time_sliced(ready)]
        else:
            running = [self._pick_serialized(ready)]
        finished: List[Request] = []
        for lane in running:
            for req in lane.engine.step():
                self.done[lane.name].append(req)
                self.finish_tick[req.rid] = self.ticks
                finished.append(req)
        return finished

    def run(self, max_ticks: int = 100_000) -> Dict[str, List[Request]]:
        self.truncated = False
        ticks = 0
        while self._backlogged() and ticks < max_ticks:
            self.step()
            ticks += 1
        left = self._backlogged()
        if left:
            self.truncated = True
            warnings.warn(
                f"AdmissionScheduler.run() stopped at max_ticks="
                f"{max_ticks} with lanes {[l.name for l in left]} still "
                "backlogged; results are truncated "
                '(see stats()["truncated"])', RuntimeWarning, stacklevel=2)
        return self.done

    def stats(self) -> Dict[str, float]:
        out: Dict[str, float] = {
            "ticks": float(self.ticks),
            "truncated": float(self.truncated),
            "completed": float(sum(len(v) for v in self.done.values())),
        }
        for name, lane in sorted(self.lanes.items()):
            done = self.done[name]
            out[f"{name}.completed"] = float(len(done))
            out[f"{name}.pending"] = float(len(self.pending[name]))
            out[f"{name}.engine_ticks"] = float(lane.engine.ticks)
            if done:
                out[f"{name}.mean_finish_tick"] = float(
                    np.mean([self.finish_tick[r.rid] for r in done]))
        return out
