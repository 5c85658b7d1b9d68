"""Continuous-batching request scheduler over one live per-slot KV cache.

The scheduler owns the cache, a FIFO admission queue, and ``layout.batch``
slots.  Each engine step it (1) spends at most ``chunk_budget`` prompt
tokens advancing ONE admitting request through the jitted, bucketed
``ChunkedPrefill`` path (fixed-shape ``(1, C)`` chunks against the live
cache, cache donated), (2) runs ONE batched ``serve_step`` for every
DECODING slot (per-slot ``cache["pos"]`` keeps staggered requests
position-correct), and (3) evicts finished slots with
``kv_cache.reset_slot`` so the next queued request can take the row without
touching live neighbors.  Interleaving (1) and (2) bounds how long a long
prompt can stall in-flight decoders: never more than one chunk budget of
prefill tokens runs between consecutive batched decode steps.

``admission="eager"`` keeps the PR-2 behavior (one arbitrary-length B=1
forward per prompt, decode stalls until it finishes) as the reference /
benchmark baseline.

Paged layouts (``layout_for(..., layout="paged")``) add a host-side
:class:`~repro.serving.paging.PageAllocator` to the loop: pages are mapped
just ahead of every chunk/decode write, the device page table is synced
whenever the host copy changes, and eviction decrefs the slot's pages —
zeroing (on device) only those whose refcount hit zero.  When an admitted
slot first advances, its prompt is hashed against the prefix index; a hit
adopts the resident requests' full prompt pages (refcount++) and skips
straight to the first un-reused token, so shared system prompts prefill
once.  (Adoption waits for the first advance rather than assignment so a
queued-behind adopter never holds shared pages at device pos 0, where the
batched decode's garbage writes would land.)  Reuse is offered
for global-only attention stacks (sliding-window rings discard the prefix
positions a reused slot would need); everything else about paged serving —
including every logit — is bit-identical to the slot layout, which is how
the fuzz oracle checks it.

The front-door hooks (``repro.serving.server`` is the asyncio transport
over them):

  * ``cancel(rid)`` pulls a request out at ANY lifecycle state — queued
    (dequeue), PREFILLING (state-aware eviction: pages freed, no bogus
    TTFT/ITL rows recorded), or DECODING (eviction mid-stream).  Survivor
    slots are untouched: eviction is the same logical evict + page decref
    the DONE path uses, which the fuzz oracle pins bit-exact.
  * ``Request.priority`` tiers (``interactive`` > ``batch``): the
    admission queue is priority-ordered FIFO, and the chunked-prefill
    advance picks the highest-priority admitting slot each step — an
    interactive arrival preempts an in-progress batch prefill's chunk
    budget (the batch slot's ``prefill_pos`` freezes; it resumes at that
    exact offset when nothing above it is admitting).  Decodes already
    running are never killed by priority.
  * ``Request.deadline_steps``: SLO-aware admission — a request still
    queued that many steps past arrival is shed (cancelled unstarted)
    instead of admitted late.
  * ``Request.on_token`` / ``on_finish`` stream tokens and completion to
    the caller per scheduler step (the server bridges them onto asyncio
    queues); ``Request.keep_prefix_resident`` pins the finished turn's
    page-aligned history so a chat session's next turn hits the prefix
    index (release with ``unpin_pages``).

Greedy sampling by default; pass ``sample_fn`` for anything richer, or set
``Request.forced_tokens`` to teacher-force a response (serving oracles).
The scheduler is deliberately host-side python around jitted device steps —
the same split a production server uses (device graph static, scheduling
dynamic).

Speculative decoding (``spec_decode=True`` / ``REPRO_SPEC_DECODE=on``, see
``repro.serving.spec_decode``) replaces each batched decode step with one
draft → verify → accept/rollback round (:meth:`Scheduler._spec_round`):
``draft_gamma`` truncated-bit-plane serve_steps propose draft tokens per
DECODING slot, up to ``gamma + 1`` full-precision serve_steps verify them
(the scheduler's ordinary ``_pick_token`` — forced or greedy over exact
logits — is the verifier, so speculative output is BIT-identical to
non-speculative decode), and every slot rolls back to its accepted
frontier: per-slot ``pos`` rewind, allocator page invalidation
(``PageAllocator.rewind_slot`` — generation counters + prefix-index
deregistration), and a device scrub of the garbage tail rows across every
store leaf.  ``stats()["spec"]`` reports accepted-tokens/step and kv +
weight bytes per *accepted* token next to ``kv_read``/``weight_read``.
"""

from __future__ import annotations

import collections
import time
from typing import Callable, Deque, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.distributed import sharding as sh
from repro.serving import engine, kv_cache as kvc
from repro.serving import sharded as shd
from repro.serving import spec_decode as spd
from repro.serving import weights as swt
from repro.serving.paging import PageAllocator
from repro.serving.request import (Request, Slot, SlotState, priority_rank)


# Host spans that ``step()`` writes on the profiler's clock, one at each
# layer boundary, with the arguments each carries:
#   sched.step           all of step()                            step, queued
#   sched.admit          shedding and admission
#   sched.prefill_chunk  a chunk's (or eager prompt's) host padding and
#                        dispatch                  rid, slot, offset, tokens
#   sched.first_token    pull of the prompt's last logits row, pick, hook  rid
#   sched.sync_pages     page-table push, when the table changed
#   sched.decode         token feed and serve_step dispatch         decoding
#   sched.logits         last-position logits to the host as f32
#   sched.sample         per-slot pick, hooks and finishes
#   sched.spec_round     one speculative round
# While no profiler runs, a span costs one enter and exit, about 2 us.
SPAN_STEP = "sched.step"
SPAN_ADMIT = "sched.admit"
SPAN_PREFILL_CHUNK = "sched.prefill_chunk"
SPAN_FIRST_TOKEN = "sched.first_token"
SPAN_SYNC_PAGES = "sched.sync_pages"
SPAN_DECODE = "sched.decode"
SPAN_LOGITS = "sched.logits"
SPAN_SAMPLE = "sched.sample"
SPAN_SPEC_ROUND = "sched.spec_round"


def greedy_sample(logits: np.ndarray) -> np.ndarray:
    """(B, V) logits -> (B,) int32 argmax tokens."""
    return np.argmax(logits, axis=-1).astype(np.int32)


def _percentiles(samples) -> Dict[str, Optional[float]]:
    a = np.asarray(list(samples), np.float64)
    if a.size == 0:
        return {"p50": None, "p95": None}
    return {"p50": round(float(np.percentile(a, 50)), 6),
            "p95": round(float(np.percentile(a, 95)), 6)}


class Scheduler:
    """Slot-level continuous batching on top of the MCBP serving engine."""

    def __init__(
        self,
        params,
        cfg,
        layout: kvc.CacheLayout,
        rules: sh.ShardingRules = sh.ShardingRules(),
        sample_fn: Callable[[np.ndarray], np.ndarray] = greedy_sample,
        admission: str = "chunked",
        chunk_budget: int = 16,
        buckets=None,
        prefill_kw: Optional[dict] = None,
        record_logits: bool = False,
        shared_fns: Optional[dict] = None,
        param_specs=None,
        spec_decode: Optional[bool] = None,
        draft_gamma: Optional[int] = None,
        draft_planes: Optional[int] = None,
        draft_fn: Optional[Callable[[Request, int], int]] = None,
    ):
        assert cfg.family in ("dense", "moe", "vlm"), (
            "the scheduler admits via transformer prefill; ssm/hybrid/enc-dec"
            " decode through make_serve_step directly (tests/test_serving.py)"
        )
        assert admission in ("chunked", "eager"), admission
        self.params = params
        self.cfg = cfg
        self.layout = layout
        self.rules = rules
        self.sample_fn = sample_fn
        self.admission = admission
        self.chunk_budget = int(chunk_budget)
        self.prefill_kw = dict(prefill_kw or {})  # eager-path forward kwargs
        self.record_logits = record_logits

        # mesh placement: KV pools/stacks heads-parallel on "model" (slot
        # stacks also batch-parallel on "data"), made there; weights
        # tensor-parallel over "model" (serving.sharded), page table +
        # allocator host-side and replicated.  Everything below is
        # identity without a mesh.
        self.cache = shd.init_cache(cfg, layout, rules)
        self.mesh_shape = shd.mesh_shape(rules)
        shards = {}  # each projection's (row, column) shards, for pricing
        if rules.mesh is not None:
            if param_specs is None:
                # a family without a spec tree raises here: replicating the
                # weights instead would silently put the whole model on
                # every device of the mesh
                from repro.models import model_zoo
                param_specs = model_zoo.param_specs(cfg)
            self.params = shd.shard_params(params, param_specs, rules)
            shards = shd.projection_shards(params, param_specs,
                                           self.mesh_shape)
        # serve-time weight format (the once-dead knob): resolved ONCE here
        # (env > config, same contract as decode_kernel), projections
        # converted AFTER sharding so the int8/bstc records inherit the
        # raw leaves' placement (quantization is elementwise + an in-axis
        # max, both order-insensitive).  Decode steps consume
        # ``serve_params``; BOTH prefill paths keep the raw ``params``
        # tree, so admission stays bit-for-bit the bf16 path in every
        # format.  With fmt="bf16" serve_params IS params (untouched).
        self.weight_format = swt.resolve(cfg)
        self.serve_params, self.weight_plan = swt.prepare_serve_params(
            self.params, cfg, layout, self.weight_format
        )
        self.pager: Optional[PageAllocator] = None
        # a paged layout with no global stack has no pools to manage
        if layout.layout == "paged" and layout.global_layers:
            self.pager = PageAllocator(layout)
            self._page_bytes = kvc.page_bytes(
                self.cache["global"], layout.page_size
            )
            pool_specs = kvc.cache_specs(cfg, layout)["global"]
            self._zero_pages = jax.jit(
                lambda store, ids: kvc.constrain_cache(
                    kvc.zero_pages(store, ids, layout.page_size),
                    pool_specs, rules,
                ),
                donate_argnums=(0,),
            )
        self.slots: List[Slot] = [Slot(i) for i in range(layout.batch)]
        self.queue: Deque[Request] = collections.deque()
        if shared_fns is not None:
            # reuse another scheduler's compiled steps (same cfg/layout/rules)
            assert shared_fns.get("layout") in (None, layout), (
                "shared_fns were compiled for a different cache layout: "
                f"{shared_fns.get('layout')} vs {layout}"
            )
            self.serve_step = shared_fns["serve_step"]
            self.chunked = shared_fns.get("chunked")
        else:
            self.serve_step = engine.jit_serve_step(cfg, layout, rules)
            self.chunked = None
        if admission == "chunked" and self.chunked is None:
            # shared_fns came from an eager scheduler (or none given)
            self.chunked = engine.ChunkedPrefill(
                cfg, layout, rules,
                buckets=buckets or engine.default_buckets(self.chunk_budget),
            )
        # next-token feed per slot; EMPTY/PREFILLING rows decode token 0 into
        # garbage that per-slot valid masks + chunk overwrites keep invisible
        self.tokens = np.zeros((layout.batch, 1), np.int32)

        # speculative decoding (repro.serving.spec_decode): kwarg > env >
        # config, with env-driven enables soft-disabling on local-layer
        # stacks (rings are not rollback-safe) and explicit ones raising
        self.spec = spd.validate(
            cfg, layout, spd.resolve(cfg, spec_decode, draft_gamma,
                                     draft_planes)
        )
        self.draft_fn = draft_fn
        self.draft_params = None
        self._scrub_tokens = None
        if self.spec.enabled:
            if draft_fn is None and self.spec.planes < 7:
                # truncated-plane draft weights, converted through the SAME
                # weight-format path as the real ones so the compiled
                # serve_step executable is reused as the draft forward
                self.draft_params, _ = swt.prepare_serve_params(
                    spd.truncate_plane_params(self.params, self.spec.planes),
                    cfg, layout, self.weight_format,
                )
            else:
                # planes >= 7 keeps full int8 precision: the real serve
                # weights ARE the (perfect) draft model
                self.draft_params = self.serve_params
            if layout.global_layers:
                g_specs = kvc.cache_specs(cfg, layout)["global"]
                if layout.layout == "paged":
                    self._scrub_tokens = jax.jit(
                        lambda store, tpos, table: kvc.constrain_cache(
                            kvc.zero_token_range(
                                store, tpos, page_table=table,
                                page_size=layout.page_size,
                                max_seq=layout.max_seq,
                            ), g_specs, rules,
                        ),
                        donate_argnums=(0,),
                    )
                else:
                    self._scrub_tokens = jax.jit(
                        lambda store, tpos: kvc.constrain_cache(
                            kvc.zero_token_range(
                                store, tpos, max_seq=layout.max_seq,
                            ), g_specs, rules,
                        ),
                        donate_argnums=(0,),
                    )
        # spec-decode counters (stats()["spec"]): rounds run, drafts
        # proposed/accepted, physical draft/verify steps, per-slot round
        # participations (each round's first token is the free corrected
        # one), best single-round accept
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_draft_steps = 0
        self.spec_verify_steps = 0
        self.spec_slot_rounds = 0
        self.spec_max_accept = 0

        self.step_count = 0
        self.finished: List[Request] = []
        self.cancelled: List[Request] = []  # cancel() + deadline sheds
        self.preemptions = 0  # chunk budgets reclaimed by a higher tier
        # slot index whose prefill the advance loop worked on last step —
        # the reference point for counting budget preemptions
        self._advancing: Optional[int] = None
        self.occupancy: List[float] = []  # busy slots / slots, per step
        self.decoded_tokens = 0
        # KV-read accounting: host-side mirrors of the jitted steps' static
        # gather shapes (kv_cache.decode_read_bytes / chunk_read_bytes),
        # accumulated once per executed decode / chunk step.  For bgpp
        # this is the two-phase plan — bit-planes plus at most
        # ceil(keep_ratio·S) full-precision rows per (slot, layer) — the
        # counter stats()["kv_read"] and the serving benchmarks report.
        self._decode_read = kvc.decode_read_bytes(layout, cfg, self.mesh_shape,
                                                  shards)
        self._chunk_read = kvc.chunk_read_bytes(layout, cfg, self.mesh_shape,
                                                shards=shards)
        # chunk interconnect scales with the chunk's lane count; price per
        # valid lane (chunk_width=1) and multiply by tokens consumed
        self._chunk_ic_per_lane = kvc.chunk_read_bytes(
            layout, cfg, self.mesh_shape, chunk_width=1, shards=shards
        )["interconnect"]["total"]
        self.decode_steps = 0
        self.kv_bytes_read = {"decode": 0.0, "prefill": 0.0,
                              "interconnect": 0.0}
        # weight-read accounting, kv_read's mirror: the jitted serve_step
        # contracts every converted projection once per batched decode
        # step, priced from the WeightPlan's coded layout (measured BSTC
        # stream bytes for fmt="bstc"); prefill reads the raw-dtype tree
        self._weight_read = self.weight_plan.decode_read_bytes(shards)
        self.weight_bytes_read = {"decode": 0.0, "prefill": 0.0}
        # audit trail for the chunk-budget contract: valid prompt tokens
        # prefilled between this step's admission and its decode
        self.prefill_tokens_per_step: List[int] = []
        # prefix-reuse accounting (paged layouts)
        self.prompt_tokens_admitted = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0

    # ------------------------------------------------------------------
    # paged-layout page lifecycle (host allocator <-> device table)
    # ------------------------------------------------------------------

    def _sync_pages(self) -> None:
        """Push the host page table to the (replicated) device copy if it
        changed — the allocator itself never leaves the host."""
        if self.pager is not None and self.pager.dirty:
            with TraceAnnotation(SPAN_SYNC_PAGES):
                self.cache["page_table"] = shd.replicated(
                    self.pager.table, self.rules
                )
            self.pager.dirty = False

    def _ensure_pages(self, slot: int, lo: int, hi: int) -> None:
        if self.pager is not None:
            self.pager.ensure_range(slot, lo, hi)
            self._sync_pages()

    def _release_pages(self, slot: int) -> None:
        """Evict a slot's pages: decref all, zero (on device) the ones
        whose refcount hit zero — prefix sharers keep theirs."""
        if self.pager is None:
            return
        freed = self.pager.release_slot(slot)
        if freed:
            ids = np.full(self.layout.pages_per_slot, -1, np.int32)
            ids[:len(freed)] = freed
            self.cache["global"] = self._zero_pages(
                self.cache["global"], jnp.asarray(ids)
            )
        self._sync_pages()

    def _try_prefix_reuse(self, slot: Slot, req: Request) -> None:
        """Adopt resident prompt pages matching this prompt's head.  Only
        global-only stacks qualify: ring layers would need the reused
        positions' window contents, which nothing retains.

        Called at the slot's FIRST chunk advance, not at assignment: the
        batched ``serve_step`` garbage-writes every row at its device pos,
        which is harmless only while the row maps no pages (writes drop) or
        only its own (the next chunk re-covers the frontier).  A waiting
        slot holding adopted pages at pos 0 would let that garbage corrupt
        the donor's shared prompt KV.  The advancing slot always moves past
        the reused region in the same scheduler step, so its own garbage
        writes stay on private pages."""
        if self.pager is None or self.layout.local_layers:
            return
        n, ids = self.pager.lookup_prefix(req.prompt)
        if n:
            self.pager.adopt_prefix(slot.index, ids)
            slot.prefill_pos = n
            req.prefix_reused_tokens = n
            self.prefix_hits += 1
            self.prefix_hit_tokens += n

    def shared_fns(self) -> dict:
        """Compiled steps, reusable by another Scheduler on the same
        (cfg, layout, rules) — e.g. an oracle's alone-runs."""
        return {"serve_step": self.serve_step, "chunked": self.chunked,
                "layout": self.layout}

    # ------------------------------------------------------------------
    # queue / admission
    # ------------------------------------------------------------------

    def submit(self, request: Request) -> None:
        """Queue one request (FIFO among arrived; see ``Request.arrival_step``)."""
        # reject malformed prompts at the API boundary: admission would
        # otherwise die mid-loop and take every in-flight request with it
        if not 0 < request.prompt_len < self.layout.max_seq:
            raise ValueError(
                f"request {request.rid}: prompt_len {request.prompt_len} "
                f"must be >= 1 and leave at least one decode slot below "
                f"max_seq {self.layout.max_seq}"
            )
        request.submit_time = time.perf_counter()
        self.queue.append(request)

    @property
    def num_pending(self) -> int:
        """Requests not yet finished: queued plus live in a slot."""
        return len(self.queue) + sum(1 for s in self.slots if s.live)

    def _next_arrived(self) -> Optional[Request]:
        """Highest-priority arrived request, FIFO within a tier."""
        best = None
        for i, req in enumerate(self.queue):
            if req.arrival_step > self.step_count:
                continue
            if best is None or (priority_rank(req.priority)
                                < priority_rank(best[1].priority)):
                best = (i, req)
        if best is None:
            return None
        del self.queue[best[0]]
        return best[1]

    def _shed_expired(self) -> None:
        """SLO-aware admission: cancel (shed) queued requests whose
        admission deadline has passed — serving them late would only burn
        chunk budget that on-SLO requests need."""
        expired = [r for r in self.queue
                   if r.deadline_steps is not None
                   and self.step_count - r.arrival_step > r.deadline_steps]
        for req in expired:
            self.queue.remove(req)
            self._record_cancel(req, "queued", shed=True)

    def _pick_token(self, req: Request, logits_row: np.ndarray) -> int:
        """Next response token: forced (teacher-forced oracles) or sampled."""
        t = len(req.generated)
        if req.forced_tokens is not None and t < len(req.forced_tokens):
            tok = int(req.forced_tokens[t])
        else:
            tok = int(self.sample_fn(logits_row[None])[0])
        if self.record_logits:
            if req.logit_rows is None:
                req.logit_rows = []
            req.logit_rows.append(np.asarray(logits_row, np.float32))
        return tok

    def _emit_first_token(self, slot: Slot, logits_row: np.ndarray) -> None:
        req = slot.request
        first = self._pick_token(req, logits_row)
        req.generated.append(first)
        now = time.perf_counter()
        req.first_token_step = self.step_count
        req.first_token_time = now
        req.token_times.append(now)
        self.tokens[slot.index, 0] = first
        slot.state = SlotState.DECODING
        if req.on_token is not None:
            req.on_token(req, first)
        if self._hit_limit(slot, req):
            self._finish(slot)

    def admit(self) -> List[Request]:
        """Eagerly fill EMPTY slots from the queue (FIFO among arrived):
        one whole-prompt B=1 forward per request (``admission="eager"``)."""
        admitted = []
        for slot in self.slots:
            if slot.state is not SlotState.EMPTY:
                continue
            req = self._next_arrived()
            if req is None:
                break
            slot.state = SlotState.PREFILLING
            slot.request = req
            req.admitted_step = self.step_count
            req.admit_time = time.perf_counter()
            self.prompt_tokens_admitted += req.prompt_len
            self._ensure_pages(slot.index, 0, req.prompt_len)
            with TraceAnnotation(SPAN_PREFILL_CHUNK, rid=req.rid,
                                 slot=slot.index, offset=0,
                                 tokens=req.prompt_len):
                logits, self.cache = engine.prefill_into_slot(
                    self.params, self.cfg, self.layout, self.cache,
                    slot.index, jnp.asarray(req.prompt, jnp.int32),
                    self.rules, **self.prefill_kw,
                )
            with TraceAnnotation(SPAN_FIRST_TOKEN, rid=req.rid):
                self._emit_first_token(
                    slot, np.asarray(logits[0, -1], np.float32))
            self.weight_bytes_read["prefill"] += self.weight_plan.bf16_bytes
            admitted.append(req)
        return admitted

    def _advance_admission(self) -> int:
        """Chunked admission: assign arrived requests to every EMPTY slot
        (reserve the row + reset it — cheap, no token work), then spend at
        most ``chunk_budget`` prompt tokens advancing the OLDEST admitting
        request.  Exactly one prompt advances per step, so the budget is
        also the bound on prefill tokens between consecutive batched decode
        steps — the contract the chunk tests audit.  Returns the number of
        prompt tokens consumed."""
        for s in self.slots:
            if s.state is not SlotState.EMPTY:
                continue
            req = self._next_arrived()
            if req is None:
                break
            s.state = SlotState.PREFILLING
            s.request = req
            s.prefill_pos = 0
            req.admitted_step = self.step_count
            req.admit_time = time.perf_counter()
            self.cache = self.chunked.reset(self.cache, s.index)
            self.prompt_tokens_admitted += req.prompt_len
        admitting = [s for s in self.slots if s.state is SlotState.PREFILLING]
        if not admitting:
            self._advancing = None
            return 0
        # highest tier first, then oldest admission: an interactive
        # arrival preempts an in-progress batch prefill's chunk budget
        slot = min(admitting, key=lambda s: (
            priority_rank(s.request.priority), s.request.admitted_step,
            s.index,
        ))
        prev = self._advancing
        if prev is not None and prev != slot.index:
            ps = self.slots[prev]
            if (ps.state is SlotState.PREFILLING
                    and priority_rank(ps.request.priority)
                    > priority_rank(slot.request.priority)):
                # the budget that would have advanced ps goes to slot;
                # ps.prefill_pos freezes and resumes at the same offset
                ps.request.preemptions += 1
                self.preemptions += 1
        req = slot.request
        if slot.prefill_pos == 0:
            # first advance of this slot: safe point for prefix adoption
            # (see _try_prefix_reuse on why assignment time is not)
            self._try_prefix_reuse(slot, req)
        spent = 0
        logits, n = None, 0
        while spent < self.chunk_budget and slot.prefill_pos < req.prompt_len:
            take = min(req.prompt_len - slot.prefill_pos,
                       self.chunk_budget - spent,
                       self.chunked.buckets[-1])  # custom buckets < budget
            self._ensure_pages(slot.index, slot.prefill_pos,
                               slot.prefill_pos + take)
            with TraceAnnotation(SPAN_PREFILL_CHUNK, rid=req.rid,
                                 slot=slot.index, offset=slot.prefill_pos,
                                 tokens=take):
                logits, self.cache, n = self.chunked.run_chunk(
                    self.params, self.cache, slot.index,
                    req.prompt[slot.prefill_pos:slot.prefill_pos + take],
                    slot.prefill_pos,
                )
            self.kv_bytes_read["prefill"] += self._chunk_read["total"]
            self.kv_bytes_read["interconnect"] += self._chunk_ic_per_lane * n
            # chunk forwards read the raw-dtype tree once per chunk step
            self.weight_bytes_read["prefill"] += self.weight_plan.bf16_bytes
            slot.prefill_pos += n
            spent += n
        if self.pager is not None and not self.layout.local_layers:
            # every page-aligned prompt prefix now fully written becomes a
            # reuse candidate for later admissions
            self.pager.register_prefix(slot.index, req.prompt,
                                       slot.prefill_pos)
        if slot.prefill_pos >= req.prompt_len:
            with TraceAnnotation(SPAN_FIRST_TOKEN, rid=req.rid):
                self._emit_first_token(
                    slot, np.asarray(logits[0, n - 1], np.float32))
            self._advancing = None
        else:
            self._advancing = slot.index
        return spent

    # ------------------------------------------------------------------
    # decode / eviction
    # ------------------------------------------------------------------

    def _hit_limit(self, slot: Slot, req: Request) -> bool:
        if len(req.generated) >= req.max_new_tokens:
            return True
        # the next decode step writes its KV at index prompt_len + decode
        # steps so far (== device pos[slot], tracked host-side to avoid a
        # sync); at max_seq the slot is out of cache room
        if req.prompt_len + len(req.generated) - 1 >= self.layout.max_seq:
            return True
        return (req.eos_id is not None and bool(req.generated)
                and req.generated[-1] == req.eos_id)

    def _evict(self, slot: Slot) -> None:
        """State-agnostic slot release, safe at ANY lifecycle state:
        decref the slot's pages (zeroing on device only those no sharer
        or pin still holds), reset the token feed, return the row to
        EMPTY.  Bookkeeping that depends on how far the request got —
        finish timestamps, TTFT/ITL rows — is the caller's job: the DONE
        path records them, the cancel path records only cancel fields (a
        PREFILLING cancel has produced no tokens, so writing the DONE
        fields would fabricate latency rows).

        Eviction of the KV row itself is logical only: the physical reset
        (an O(cache) copy) happens once, at the next admission — both
        admission paths always reset_slot first, and per-slot valid masks
        keep the stale row invisible to live neighbors in the meantime."""
        self._release_pages(slot.index)
        self.tokens[slot.index, 0] = 0
        if self._advancing == slot.index:
            # the in-progress prefill reference must not dangle into a
            # row that now holds a different (or no) request
            self._advancing = None
        slot.request = None
        slot.prefill_pos = 0
        slot.state = SlotState.EMPTY

    def _finish(self, slot: Slot) -> None:
        req = slot.request
        req.finished_step = self.step_count
        req.finish_time = time.perf_counter()
        slot.state = SlotState.DONE
        self.finished.append(req)
        # chat sessions: pin the written history's page-aligned prefix
        # BEFORE eviction decrefs it, so the next turn finds it resident
        self._pin_history(slot, req)
        self._evict(slot)
        if req.on_finish is not None:
            req.on_finish(req)

    def _record_cancel(self, req: Request, state: str,
                       shed: bool = False) -> None:
        req.cancelled = True
        req.shed = shed
        req.cancel_state = state
        req.cancel_step = self.step_count
        req.cancel_time = time.perf_counter()
        self.cancelled.append(req)
        if req.on_finish is not None:
            req.on_finish(req)

    def cancel(self, rid: int) -> bool:
        """Cancel request ``rid`` wherever it is in its lifecycle.

        * still queued — removed from the admission queue;
        * PREFILLING — evicted mid-chunked-prefill: pages mapped so far
          (including any adopted prefix pages) are decrefed, shared pages
          survive for their other holders, and NO first-token/ITL
          bookkeeping is recorded (the state-aware-eviction contract);
        * DECODING — evicted mid-stream, same page discipline.

        Safe to call between scheduler steps at any time (the async
        server calls it on client disconnect).  Returns True if the
        request was found live/queued, False if it already finished,
        was already cancelled, or is unknown.
        """
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                self._record_cancel(req, "queued")
                return True
        for slot in self.slots:
            if slot.live and slot.request.rid == rid:
                req = slot.request
                state = ("prefilling" if slot.state is SlotState.PREFILLING
                         else "decoding")
                self._evict(slot)
                self._record_cancel(req, state)
                return True
        return False

    def _pin_history(self, slot: Slot, req: Request) -> None:
        """``keep_prefix_resident``: index + pin the page-aligned prefix
        of this request's *written* history (prompt + generated tokens
        whose KV landed — everything but the final sampled token) so a
        chat session's next turn can adopt it via the prefix index.  The
        pin ids land in ``req.pinned_pages``; release them with
        :meth:`unpin_pages` when the session moves on."""
        if (self.pager is None or self.layout.local_layers
                or not req.keep_prefix_resident):
            return
        written = req.prompt_len + len(req.generated) - 1
        hist = np.concatenate([
            np.asarray(req.prompt, np.int32),
            np.asarray(req.generated[:-1], np.int32),
        ])
        self.pager.register_prefix(slot.index, hist, written)
        npages = written // self.layout.page_size
        ids = tuple(int(p) for p in self.pager.table[slot.index, :npages]
                    if p >= 0)
        if npages > 0 and len(ids) == npages:
            self.pager.pin_pages(ids)
            req.pinned_pages = ids

    def unpin_pages(self, ids) -> None:
        """Release session pins taken by ``keep_prefix_resident``: decref
        each page, zero + free (on device) the ones no slot or other pin
        still holds — the same hygiene eviction applies."""
        if self.pager is None or not ids:
            return
        freed = self.pager.unpin_pages(ids)
        cap = self.layout.pages_per_slot
        for lo in range(0, len(freed), cap):
            buf = np.full(cap, -1, np.int32)
            chunk = freed[lo:lo + cap]
            buf[:len(chunk)] = chunk
            self.cache["global"] = self._zero_pages(
                self.cache["global"], jnp.asarray(buf)
            )
        self._sync_pages()

    # ------------------------------------------------------------------
    # speculative decoding (draft -> verify -> accept/rollback)
    # ------------------------------------------------------------------

    def _count_decode_step(self) -> None:
        """Account one physical serve_step: the kv/weight byte prices are
        static per-step totals, so draft, verify, and plain decode steps
        all pay the same — which is exactly what keeps the accounting laws
        (``decode_bytes == decode_steps * decode_bytes_per_step``) format-
        and speculation-invariant.  The speculative *win* shows up in the
        per-accepted-token columns, not by discounting the counter."""
        self.decode_steps += 1
        self.kv_bytes_read["decode"] += self._decode_read["total"]
        self.kv_bytes_read["interconnect"] += \
            self._decode_read["interconnect"]["total"]
        self.weight_bytes_read["decode"] += self._weight_read["total"]

    def _spec_round(self, live: List[Slot]) -> None:
        """One draft -> verify -> accept/rollback round for every DECODING
        slot (replaces the single batched decode step when spec decode is
        on).

        Drafts come from ``draft_fn(request, token_index)`` when given
        (the oracles' perfect/adversarial injection point) or from a
        ``gamma``-step chain of the compiled serve_step over the
        truncated-plane ``draft_params``.  Verification feeds the draft
        tokens through the REAL serve_step and picks each slot's true
        token from the exact logits (``_pick_token`` — forced or greedy),
        so every accepted token is bit-identical to what non-speculative
        decode would have produced; a slot leaves the chain at its first
        draft mismatch, after its corrected token.  Rollback then (1)
        rewinds every row's ``pos`` (live slots to their accepted
        frontier, every other row to its pre-round position), (2) invali-
        dates paged pages past the frontier (``PageAllocator.rewind_slot``
        + device page zeroing), and (3) zeroes the garbage tail rows
        across every store leaf, so no speculative write survives
        anywhere a later step could observe it."""
        gamma = self.spec.gamma
        B = self.layout.batch
        # pre-round frontier P: this round's first write position per slot
        P = {s.index: s.request.prompt_len + len(s.request.generated) - 1
             for s in live}
        reqs = {s.index: s.request for s in live}
        if self.pager is not None:
            for slot in live:
                p = P[slot.index]
                self.pager.ensure_range(
                    slot.index, p, min(p + gamma + 1, self.layout.max_seq)
                )
            self._sync_pages()
        # ---- draft: gamma proposed tokens per live slot --------------
        drafts: Dict[int, List[int]] = {i: [] for i in P}
        draft_steps = 0
        if self.draft_fn is not None:
            for slot in live:
                req = reqs[slot.index]
                n0 = len(req.generated)
                drafts[slot.index] = [
                    int(self.draft_fn(req, n0 + j)) for j in range(gamma)
                ]
        else:
            # draft chain on the live cache: greedy argmax fed forward;
            # its writes land past every frontier and are rolled back with
            # the rest of the round's speculation
            feed = self.tokens.copy()
            for _ in range(gamma):
                dlogits, self.cache = self.serve_step(
                    self.draft_params, self.cache, jnp.asarray(feed)
                )
                drows = np.asarray(dlogits[:, -1], np.float32)
                draft_steps += 1
                self._count_decode_step()
                for slot in live:
                    tok = int(np.argmax(drows[slot.index]))
                    drafts[slot.index].append(tok)
                    feed[slot.index, 0] = tok
            # undo the draft chain's pos drift before verification: the
            # verify chain must write/attend at the same positions a
            # non-speculative decode would
            self.cache["pos"] = self.cache["pos"] - jnp.asarray(
                gamma, self.cache["pos"].dtype
            )
        # ---- verify: feed drafts, accept while they match ------------
        active = {slot.index: slot for slot in live}
        accepted = {slot.index: 0 for slot in live}
        finishes: List[Slot] = []
        C = 0
        while active and C < gamma + 1:
            logits, self.cache = self.serve_step(
                self.serve_params, self.cache, jnp.asarray(self.tokens)
            )
            rows = np.asarray(logits[:, -1], np.float32)
            j, C = C, C + 1
            self._count_decode_step()
            self.spec_verify_steps += 1
            self.decoded_tokens += len(active)
            now = time.perf_counter()
            for idx in list(active):
                slot = active[idx]
                req = reqs[idx]
                tok = self._pick_token(req, rows[idx])
                req.generated.append(tok)
                req.token_times.append(now)
                accepted[idx] += 1
                # while the drafts match, the next feed IS the draft — the
                # chain teacher-forces the speculation through serve_step
                self.tokens[idx, 0] = tok
                if req.on_token is not None:
                    req.on_token(req, tok)
                if self._hit_limit(slot, req):
                    # finish AFTER rollback: _pin_history must only ever
                    # see pages the rewind kept
                    finishes.append(slot)
                    del active[idx]
                elif j < gamma and tok != drafts[idx][j]:
                    del active[idx]  # draft diverged; corrected token kept
        # ---- rollback -----------------------------------------------
        # live rows rewind to their accepted frontier P + a; every other
        # row (EMPTY garbage rows, mid-prefill slots) returns to its
        # pre-round position
        delta = np.full(B, C, np.int32)
        for slot in live:
            delta[slot.index] = C - accepted[slot.index]
        self.cache["pos"] = self.cache["pos"] - jnp.asarray(
            delta, self.cache["pos"].dtype
        )
        if self.pager is not None:
            freed: List[int] = []
            for slot in live:
                freed += self.pager.rewind_slot(
                    slot.index, P[slot.index] + accepted[slot.index]
                )
            cap = self.layout.pages_per_slot
            for lo in range(0, len(freed), cap):
                buf = np.full(cap, -1, np.int32)
                chunk = freed[lo:lo + cap]
                buf[:len(chunk)] = chunk
                self.cache["global"] = self._zero_pages(
                    self.cache["global"], jnp.asarray(buf)
                )
            self._sync_pages()
        # zero the garbage tail rows [P+a, P+extent) across every leaf —
        # pages the allocator freed were scrubbed wholesale above; this
        # covers the slot layout and the paged frontier page's tail
        extent = max(C, gamma if draft_steps else 0)
        tpos = np.full((B, gamma + 1), kvc.OOB_INDEX, np.int32)
        dirty = False
        for slot in live:
            lo = P[slot.index] + accepted[slot.index]
            hi = min(P[slot.index] + extent, self.layout.max_seq)
            if hi > lo:
                tpos[slot.index, :hi - lo] = np.arange(lo, hi)
                dirty = True
        if dirty and self._scrub_tokens is not None:
            if self.layout.layout == "paged":
                self.cache["global"] = self._scrub_tokens(
                    self.cache["global"], jnp.asarray(tpos),
                    self.cache["page_table"],
                )
            else:
                self.cache["global"] = self._scrub_tokens(
                    self.cache["global"], jnp.asarray(tpos)
                )
        # ---- bookkeeping + deferred finishes -------------------------
        self.spec_rounds += 1
        self.spec_draft_steps += draft_steps
        for slot in live:
            a = accepted[slot.index]
            req = reqs[slot.index]
            self.spec_accepted += a
            self.spec_drafted += gamma
            self.spec_slot_rounds += 1
            self.spec_max_accept = max(self.spec_max_accept, a)
            req.spec_accepts.append(a)
            req.spec_drafted += gamma
        for slot in finishes:
            self._finish(slot)

    def step(self) -> bool:
        """Admit/advance prefill, run one batched decode step, harvest,
        evict.

        Returns False when there was nothing to do (no live slot and no
        admissible request) — the caller's idle/termination signal.
        """
        with TraceAnnotation(SPAN_STEP, step=self.step_count,
                             queued=len(self.queue)):
            return self._step()

    def _step(self) -> bool:
        with TraceAnnotation(SPAN_ADMIT):
            self._shed_expired()
            if self.admission == "chunked":
                spent = self._advance_admission()
            else:
                spent = sum(r.prompt_len for r in self.admit())
        self.prefill_tokens_per_step.append(spent)
        busy = [s for s in self.slots if s.live]
        live = [s for s in self.slots if s.state is SlotState.DECODING]
        self.occupancy.append(len(busy) / len(self.slots))
        if not live:
            self.step_count += 1
            return bool(busy)  # prefill progress still counts as work
        if self.spec.enabled:
            # one draft -> verify -> accept/rollback round replaces the
            # single batched decode step (same harvesting, same eviction)
            self.step_count += 1
            with TraceAnnotation(SPAN_SPEC_ROUND):
                self._spec_round(live)
            return True
        if self.pager is not None:
            for slot in live:
                # this decode step writes slot KV at the device pos
                # (tracked host-side): prompt_len + generated - 1
                r = slot.request
                p = r.prompt_len + len(r.generated) - 1
                self.pager.ensure_range(slot.index, p, p + 1)
            self._sync_pages()
        with TraceAnnotation(SPAN_DECODE, decoding=len(live)):
            logits, self.cache = self.serve_step(
                self.serve_params, self.cache, jnp.asarray(self.tokens)
            )
        with TraceAnnotation(SPAN_LOGITS):
            rows = np.asarray(logits[:, -1], np.float32)
        self.step_count += 1
        self.decode_steps += 1
        self.kv_bytes_read["decode"] += self._decode_read["total"]
        self.kv_bytes_read["interconnect"] += \
            self._decode_read["interconnect"]["total"]
        self.weight_bytes_read["decode"] += self._weight_read["total"]
        self.decoded_tokens += len(live)
        with TraceAnnotation(SPAN_SAMPLE):
            now = time.perf_counter()
            for slot in live:
                req = slot.request
                tok = self._pick_token(req, rows[slot.index])
                req.generated.append(tok)
                req.token_times.append(now)
                self.tokens[slot.index, 0] = tok
                if req.on_token is not None:
                    req.on_token(req, tok)
                if self._hit_limit(slot, req):
                    self._finish(slot)
        return True

    def run(self, max_steps: Optional[int] = None) -> Dict:
        """Drive steps until every submitted request finished (or the step
        budget runs out); returns :meth:`stats`."""
        t0 = time.perf_counter()
        while self.num_pending:
            if max_steps is not None and self.step_count >= max_steps:
                break
            self.step()
        return self.stats(time.perf_counter() - t0)

    def _tier_stats(self) -> Dict[str, Dict]:
        """Per-priority-tier SLO columns: finished/cancelled counts,
        preemptions suffered, and TTFT/ITL percentiles — the numbers an
        SLO dashboard keys on (interactive tail vs batch tail)."""
        tiers: Dict[str, Dict] = {}
        present = ({r.priority for r in self.finished}
                   | {r.priority for r in self.cancelled})
        for tier in sorted(present, key=priority_rank):
            fin = [r for r in self.finished if r.priority == tier]
            gaps = np.concatenate(
                [r.itl_gaps_s() for r in fin]
            ) if fin else np.asarray([])
            tiers[tier] = {
                "finished": len(fin),
                "cancelled": sum(
                    1 for r in self.cancelled if r.priority == tier
                ),
                "shed": sum(
                    1 for r in self.cancelled
                    if r.priority == tier and r.shed
                ),
                "preemptions": sum(r.preemptions for r in fin),
                "ttft_s": _percentiles(
                    r.ttft_s for r in fin if r.first_token_time > 0
                ),
                "itl_s": _percentiles(gaps),
            }
        return tiers

    def stats(self, wall_s: Optional[float] = None) -> Dict:
        """Aggregate serving metrics: throughput/occupancy, TTFT/ITL
        percentiles, per-request traces, paged-pool accounting (paged
        layouts), the ``kv_read`` counter — KV bytes the executed decode /
        chunk steps gathered, with the bgpp two-phase breakdown and the
        bf16-equivalent denominator — and its mirror ``weight_read`` —
        projection-weight bytes priced from the resolved
        ``weight_format``'s coded layout."""
        occ = [o for o in self.occupancy if o > 0] or self.occupancy
        gaps = np.concatenate(
            [r.itl_gaps_s() for r in self.finished]
        ) if self.finished else np.asarray([])
        out = {
            "admission": self.admission,
            "kv_layout": self.layout.layout,
            "finished_requests": len(self.finished),
            "decoded_tokens": self.decoded_tokens,
            "steps": self.step_count,
            "mean_occupancy": float(np.mean(occ)) if occ else 0.0,
            "max_prefill_tokens_per_step":
                max(self.prefill_tokens_per_step, default=0),
            "ttft_s": _percentiles(
                r.ttft_s for r in self.finished if r.first_token_time > 0
            ),
            "itl_s": _percentiles(gaps),
            "requests": [r.trace_record() for r in self.finished],
            # front-door columns: cancellation / preemption / per-tier SLO
            "cancelled_requests": len(self.cancelled),
            "shed_requests": sum(1 for r in self.cancelled if r.shed),
            "preemptions": self.preemptions,
            "tiers": self._tier_stats(),
            "cancelled": [r.cancel_record() for r in self.cancelled],
        }
        dr = self._decode_read
        out["kv_read"] = {
            "decode_bytes": round(self.kv_bytes_read["decode"]),
            "prefill_bytes": round(self.kv_bytes_read["prefill"]),
            "decode_steps": self.decode_steps,
            "decode_bytes_per_step": round(dr["total"]),
            "decode_bf16_equiv_bytes_per_step": round(dr["bf16_equiv"]),
            "decode_bytes_reduction_vs_bf16": round(
                dr["bf16_equiv"] / dr["total"], 3) if dr["total"] else None,
            # mesh columns: each device's share of the gathers, plus the
            # priced collectives (the two tensor-parallel all-reduces of
            # each layer, the paged write broadcast) of one decode step,
            # and of every executed decode step and chunk together —
            # zero / equal-to-total at mesh 1x1
            "mesh": {"data": self.mesh_shape[0], "model": self.mesh_shape[1]},
            "kv_shards": dr["per_device"]["shards"],
            "decode_bytes_per_device_per_step": round(
                dr["per_device"]["total"]),
            "interconnect_bytes_per_step": round(dr["interconnect"]["total"]),
            "interconnect_bytes": round(self.kv_bytes_read["interconnect"]),
            "interconnect": {
                n: round(v) for n, v in dr["interconnect"].items()
            },
        }
        wr = self._weight_read
        out["weight_read"] = {
            "weight_format": self.weight_format,
            "decode_bytes": round(self.weight_bytes_read["decode"]),
            "prefill_bytes": round(self.weight_bytes_read["prefill"]),
            "decode_steps": self.decode_steps,
            "decode_bytes_per_step": round(wr["total"]),
            "decode_bf16_equiv_bytes_per_step": round(wr["bf16_equiv"]),
            "decode_bytes_reduction_vs_bf16": round(
                wr["bf16_equiv"] / wr["total"], 3) if wr["total"] else None,
            # closed-form reconciliation (roofline.bstc_weight_traffic on
            # the measured per-plane column sparsities): the bench gates
            # measured/modeled at 1.0 ± 10%
            "modeled_bytes_per_step": round(wr["modeled"]),
            "measured_over_modeled": round(
                wr["total"] / wr["modeled"], 4) if wr["modeled"] else None,
            "per_projection": {
                n: round(v) for n, v in wr["per_projection"].items()
            },
            "mesh": {"data": self.mesh_shape[0], "model": self.mesh_shape[1]},
            "weight_shards": wr["per_device"]["shards"],
            "decode_bytes_per_device_per_step": round(
                wr["per_device"]["total"]),
        }
        if self.spec.enabled:
            acc = self.spec_accepted
            kvb = self.kv_bytes_read["decode"]
            wb = self.weight_bytes_read["decode"]
            wr_step = wr["total"]
            # what drafting at planes/8 of the weight bytes would cost: a
            # truncated-plane draft step streams only the kept MSB planes,
            # verify steps pay full freight.  With callback drafts there
            # are zero draft steps, so modeled == measured.
            modeled = (self.spec_draft_steps * wr_step
                       * self.spec.planes / 8.0
                       + self.spec_verify_steps * wr_step)
            out["spec"] = {
                "enabled": True,
                "gamma": self.spec.gamma,
                "draft_planes": self.spec.planes,
                "draft_source": ("callback" if self.draft_fn is not None
                                 else "planes"),
                "rounds": self.spec_rounds,
                "drafted_tokens": self.spec_drafted,
                "accepted_tokens": acc,
                "draft_steps": self.spec_draft_steps,
                "verify_steps": self.spec_verify_steps,
                "max_accepted_in_round": self.spec_max_accept,
                # THE acceptance rate: true tokens per physical serve_step
                # (draft + verify); 1.0 is the non-speculative baseline
                "accepted_tokens_per_step": round(
                    acc / self.decode_steps, 4) if self.decode_steps else None,
                "accepted_tokens_per_round": round(
                    acc / self.spec_slot_rounds, 4
                ) if self.spec_slot_rounds else None,
                # drafts that survived verification (each slot-round's
                # first accepted token is the free corrected one)
                "draft_hit_rate": round(
                    (acc - self.spec_slot_rounds) / self.spec_drafted, 4
                ) if self.spec_drafted else None,
                # the ISSUE's headline columns: decode-path bytes per
                # ACCEPTED token, next to kv_read/weight_read's per-step
                # prices (bytes/accepted == bytes/step / acceptance-rate)
                "kv_bytes_per_accepted_token": round(kvb / acc)
                if acc else None,
                "weight_bytes_per_accepted_token": round(wb / acc)
                if acc else None,
                "modeled_weight_bytes_per_accepted_token": round(modeled / acc)
                if acc else None,
            }
        if "bgpp" in dr:
            out["kv_read"]["bgpp"] = {
                n: round(v) if isinstance(v, float) else v
                for n, v in dr["bgpp"].items()
            }
        if self.pager is not None:
            pb = self._page_bytes
            out["paged"] = {
                "page_size": self.layout.page_size,
                "num_pages": self.layout.num_pages,
                "pages_allocated_total": self.pager.alloc_count,
                "pages_in_use": self.pager.pages_in_use,
                "prefix_hits": self.prefix_hits,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prefix_hit_rate": round(
                    self.prefix_hit_tokens
                    / max(1, self.prompt_tokens_admitted), 4
                ),
                "resident_kv_bytes_peak": self.pager.peak_pages * pb,
                # what the slot layout pins resident for the same traffic:
                # every slot's full (S_max,) row, hit or miss
                "slot_resident_kv_bytes":
                    self.layout.batch * self.layout.pages_per_slot * pb,
            }
        if wall_s is not None:
            out["wall_s"] = round(wall_s, 3)
            out["tokens_per_s"] = round(self.decoded_tokens / wall_s, 2) \
                if wall_s > 0 else None
        return out
