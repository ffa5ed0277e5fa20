"""Continuous-batching FT serving engine over the paged KV cache (PR 9).

`train/serve.py` is the slot-*batch* baseline: one prefill fills every slot,
decode runs until the whole batch finishes, and each slot owns a dense
(max_len, KVH, dh) cache stripe whether it uses it or not. This module is
the vLLM/Orca-style engine on top of `train/kv_cache.py`:

  * requests are admitted into *slots* as they arrive (FIFO) whenever the
    page pool has room — prefill for one request interleaves with decode
    steps for the others instead of gating a whole batch;
  * each slot's KV lives in pool pages routed by a host-authoritative page
    table, so HBM scales with tokens actually held, not n_slots × max_len;
  * every decode step is ONE jitted `transformer.paged_decode_step` call
    over all slots — per-layer flashft decode launches with the page table
    and per-slot ragged lengths scalar-prefetched, dead slots riding along
    into the reserved null page;
  * finished slots return their pages to the free list immediately, which
    is what admits the next queued request.

FT telemetry threads through exactly like `serve.generate`: with a
`tools.metrics.MetricsSink` attached, the engine opens a telemetry scope
around each jitted call and feeds the per-site/per-layer FTReport to the
sink (one sink step per prefill or decode call), so serving SDCs land in
the same JSONL stream — and the same storm detector — as training. The
engine additionally records serving-shape metrics per step: live slots,
free pages, decoded tokens, and a TTFT histogram at admission.

Host spans (`tools.trace.span`, in the process-wide ring and, under a
profiler trace, on its host track) mark each layer boundary:

  serve/step       all of `step()`
    serve/admit      one admission (``rid``), after its ``serve/queued``
                     record (submit → admission start)
      serve/prefill    the batch-1 prefill call (``rid``, ``prompt_len``)
      serve/kv_write   the prompt KV scatter into pages (``rid``)
      serve/sample     the first token's host sync
    serve/prepare    page reservation and the page-table/length/token uploads
    serve/dispatch   the decode call (``live``, ``n_slots``, ``free_pages``)
    serve/sample     the host sync of the sampled tokens
    serve/retire     the bookkeeping after sampling (``finished``)

A compile or retrace inside any of them shows as a ``jax/compile`` or
``jax/trace`` record under it. The sink's ``live_slots``/``free_pages``
gauges take the values the ``serve/dispatch`` span carries.

Length bookkeeping: `PageAllocator.ensure(slot, n)` reserves *capacity*;
the device-visible `cache["length"]` is the engine's decoded-so-far count
(`cur_len`) — ensure runs for `cur_len + 1` BEFORE each step so the page
for the incoming token exists, while the kernel masks at `cur_len`.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.core import telemetry
from repro.models import transformer as tfm
from repro.models.blocks import Ctx
from repro.tools import trace
from . import kv_cache


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int
    t_submit: float = 0.0


@dataclasses.dataclass
class Result:
    rid: int
    prompt_len: int
    tokens: List[int]             # generated tokens (eos included if hit)
    ttft_s: float                 # submit → first token (prefill) latency


@dataclasses.dataclass
class EngineConfig:
    max_len: int = 512            # prompt + generated ceiling per request
    n_slots: int = 8
    max_new_tokens: int = 32      # default per-request budget
    temperature: float = 0.0      # 0 = greedy
    eos_id: int = -1              # -1 = never stop early
    page_size: Optional[int] = None   # None = autotuned (kv_cache.plan_pages)
    slack: float = 1.0            # pool oversubscription (<1 may exhaust)
    seed: int = 0


class ServeEngine:
    """Continuous-batching serving engine for the transformer families
    (dense / moe — the architectures with a (S, KVH, dh) KV cache).

    Usage::

        eng = ServeEngine(params, cfg, run, EngineConfig(...), sink=sink)
        eng.submit(prompt_a); eng.submit(prompt_b)
        results = eng.run()           # or: while eng.step(): ...

    Per-request prefill runs unpadded at batch 1 (one retrace per distinct
    prompt length — synthetic-traffic benchmarks should draw from a few
    length buckets), writes the prompt KV into freshly allocated pages, and
    samples the first token (TTFT). Decode steps advance every live slot
    through one `paged_decode_step` call.
    """

    def __init__(self, params, cfg: ModelConfig, run: RunConfig,
                 ec: EngineConfig, *, sink=None,
                 clock=time.perf_counter):
        if cfg.family not in ("dense", "moe"):
            raise NotImplementedError(
                f"paged serving needs the transformer KV layout; family "
                f"{cfg.family!r} is a ROADMAP follow-up")
        self.params = params
        self.cfg = cfg
        self.ec = ec
        self.sink = sink
        self._clock = clock
        self.dtype = jnp.bfloat16 if run.dtype == "bfloat16" else jnp.float32
        self.ctx = Ctx(ft=run.ft, key=None, dtype=self.dtype,
                       attn_shard=run.attn_shard, attn_impl=run.attn_impl)
        self.plan = kv_cache.plan_pages(
            cfg, run.ft, n_slots=ec.n_slots, max_len=ec.max_len,
            dtype=self.dtype, page_size=ec.page_size, slack=ec.slack)
        p = self.plan
        self.alloc = kv_cache.PageAllocator(p.n_pages, p.n_slots,
                                            p.max_pages, p.page_size)
        self.cache = kv_cache.init_paged_cache(
            cfg.n_layers, p.n_pages, p.n_slots, p.max_pages, cfg.n_kv_heads,
            p.page_size, cfg.head_dim, self.dtype)
        n = ec.n_slots
        self.cur_len = np.zeros((n,), np.int32)     # prompt + decoded so far
        self.next_tok = np.zeros((n,), np.int32)    # sampled, not yet in KV
        self.n_new = np.zeros((n,), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * n
        self.gen: List[List[int]] = [[] for _ in range(n)]
        self.ttft: List[float] = [0.0] * n
        self.queue: Deque[Request] = collections.deque()
        self.results: List[Result] = []
        self._rid = 0
        self._serve_step = 0
        self._key = jax.random.PRNGKey(ec.seed)
        self._draws = 0

        with_report = sink is not None
        ctx = self.ctx

        def prefill_fn(params, tokens, dcache):
            if not with_report:
                return tfm.prefill(params, tokens, dcache, cfg, ctx)
            (logits, nc), rep = telemetry.scoped(
                lambda: tfm.prefill(params, tokens, dcache, cfg, ctx))
            return logits, nc, rep

        def decode_fn(params, tok, pcache):
            if not with_report:
                return tfm.paged_decode_step(params, tok, pcache, cfg, ctx)
            (logits, nc), rep = telemetry.scoped(
                lambda: tfm.paged_decode_step(params, tok, pcache, cfg, ctx))
            return logits, nc, rep

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn, donate_argnums=(2,))
        trace.watch_compiles()

    # -- request intake ----------------------------------------------------

    def submit(self, prompt, max_new_tokens: Optional[int] = None) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        mnt = self.ec.max_new_tokens if max_new_tokens is None \
            else max_new_tokens
        if mnt < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if len(prompt) + mnt > self.plan.max_len:
            raise ValueError(
                f"prompt_len {len(prompt)} + max_new {mnt} exceeds "
                f"max_len {self.plan.max_len}")
        rid = self._rid
        self._rid += 1
        self.queue.append(Request(rid, prompt, mnt, self._clock()))
        return rid

    # -- internals ---------------------------------------------------------

    def _sample(self, logits: jax.Array) -> np.ndarray:
        with trace.span("serve/sample"):
            if self.ec.temperature <= 0.0:
                return np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            self._draws += 1
            k = jax.random.fold_in(self._key, self._draws)
            return np.asarray(
                jax.random.categorical(k, logits / self.ec.temperature),
                np.int32)

    def _gauges(self) -> Dict[str, int]:
        """Slot and page occupancy now: what ``serve/dispatch`` carries and
        the sink's gauges report."""
        return {"live": sum(r is not None for r in self.slot_req),
                "n_slots": self.ec.n_slots, "free_pages": self.alloc.n_free}

    def _emit(self, rep, phase: str, n_tokens: int,
              gauges: Dict[str, int]) -> None:
        sink = self.sink
        sink.record_ft(rep, step=self._serve_step)
        sink.gauge("phase", phase)
        sink.gauge("live_slots", gauges["live"])
        sink.gauge("free_pages", gauges["free_pages"])
        sink.count("decoded_tokens" if phase == "decode" else "prefill_tokens",
                   n_tokens)
        sink.step_end(self._serve_step)
        self._serve_step += 1

    def _finish(self, slot: int) -> None:
        req = self.slot_req[slot]
        assert req is not None
        self.results.append(Result(req.rid, len(req.prompt),
                                   list(self.gen[slot]), self.ttft[slot]))
        self.alloc.free_slot(slot)
        self.slot_req[slot] = None
        self.gen[slot] = []
        self.cur_len[slot] = 0
        self.next_tok[slot] = 0
        self.n_new[slot] = 0

    def _admit(self) -> None:
        """FIFO-admit queued requests while a slot AND pages are free.
        Runs the request's (batch-1, unpadded) prefill, scatters the prompt
        KV into freshly allocated pages, and samples the first token."""
        while self.queue and self.alloc.can_admit(len(self.queue[0].prompt)):
            req = self.queue.popleft()
            t_ns = time.perf_counter_ns()
            waited = self._clock() - req.t_submit
            trace.record("serve/queued", t_ns - int(waited * 1e9), t_ns,
                         rid=req.rid)
            with trace.span("serve/admit", rid=req.rid):
                self._admit_one(req)

    def _admit_one(self, req: Request) -> None:
        L = len(req.prompt)
        slot, _ = self.alloc.alloc_slot(L)
        dcache = tfm.init_cache(self.cfg, 1, L, self.dtype)
        toks = jnp.asarray(req.prompt[None], jnp.int32)
        with trace.span("serve/prefill", prompt_len=L):
            if self.sink is not None:
                logits, dcache, rep = self._prefill(self.params, toks, dcache)
            else:
                logits, dcache = self._prefill(self.params, toks, dcache)
        with trace.span("serve/kv_write"):
            self.cache = kv_cache.write_prefill(
                self.cache, slot, jnp.asarray(self.alloc.page_table[slot]),
                dcache["k"][:, 0], dcache["v"][:, 0], L)
        tok = int(self._sample(logits.reshape(1, -1))[0])
        now = self._clock()
        self.slot_req[slot] = req
        self.cur_len[slot] = L
        self.next_tok[slot] = tok
        self.n_new[slot] = 1
        self.gen[slot] = [tok]
        self.ttft[slot] = now - req.t_submit
        if self.sink is not None:
            self.sink.count("requests", 1)
            self.sink.histogram("ttft_s", self.ttft[slot])
            self._emit(rep, "prefill", L, self._gauges())
        if self._done(slot, tok):
            self._finish(slot)

    def _done(self, slot: int, tok: int) -> bool:
        req = self.slot_req[slot]
        return (self.n_new[slot] >= req.max_new_tokens
                or (self.ec.eos_id >= 0 and tok == self.ec.eos_id))

    # -- the engine loop ---------------------------------------------------

    def step(self) -> bool:
        """Admit what fits, then run ONE decode step over every live slot.
        Returns False when the engine is fully drained (no live slots and
        an empty queue) — i.e. `while eng.step(): pass` serves everything."""
        with trace.span("serve/step"):
            self._admit()
            live = [s for s in range(self.ec.n_slots)
                    if self.slot_req[s] is not None]
            if not live:
                if self.queue:
                    # Idle engine (every page free) yet the head request
                    # still does not fit: it never will — fail loudly
                    # instead of spinning. Reachable only with a pool sized
                    # below one worst-case request (slack ≪ 1 or tiny
                    # max_pages).
                    raise RuntimeError(
                        f"request rid={self.queue[0].rid} (prompt_len="
                        f"{len(self.queue[0].prompt)}) cannot be admitted "
                        f"even by an idle engine: page pool too small "
                        f"({self.alloc.n_free} free pages)")
                return False
            with trace.span("serve/prepare"):
                for s in live:
                    self.alloc.ensure(s, int(self.cur_len[s]) + 1)
                self.cache["page_table"] = jnp.asarray(self.alloc.page_table)
                self.cache["length"] = jnp.asarray(self.cur_len)
                tok = jnp.asarray(self.next_tok[:, None], jnp.int32)
            gauges = self._gauges()
            with trace.span("serve/dispatch", **gauges):
                if self.sink is not None:
                    logits, self.cache, rep = self._decode(self.params, tok,
                                                           self.cache)
                else:
                    logits, self.cache = self._decode(self.params, tok,
                                                      self.cache)
            nxt = self._sample(logits.reshape(self.ec.n_slots, -1))
            if self.sink is not None:
                self._emit(rep, "decode", len(live), gauges)
            with trace.span("serve/retire") as retire:
                finished = 0
                for s in live:
                    self.cur_len[s] += 1
                    t = int(nxt[s])
                    self.next_tok[s] = t
                    self.gen[s].append(t)
                    self.n_new[s] += 1
                    if self._done(s, t):
                        self._finish(s)
                        finished += 1
                retire.attrs["finished"] = finished
            return True

    def run(self) -> List[Result]:
        """Drain the queue; returns results sorted by request id."""
        while self.step():
            pass
        self.alloc.check_invariants()
        return sorted(self.results, key=lambda r: r.rid)
