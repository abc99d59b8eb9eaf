"""Chain engine: the data plane of one composed server chain.

On a TPU deployment each engine's stage programs run on the chain's TP
groups with activation handoff between hops; here (CPU container, 1 device)
the whole model executes in-process while the chain structure — capacity,
per-hop block counts, service-time accounting — is preserved, so the
control-plane behaviour (the paper's contribution) is exercised end to end.

Prefill lengths are bucketed to powers of two (bounded jit cache); decode
runs one batched step over the active slots (row count bucketed to a power
of two), each over the power-of-two span of positions its pages would
cover (``kv_span``).

An engine keeps counts (``counts``: prefilled and padding positions,
preemptions, jit-cache clears) and, when its orchestrator holds a
``repro.obs.SpanRecorder``, records ``engine.admit`` and ``engine.step``
spans with their phases.  Inside the step programs the page gather, the
model's decode and the write-back run under the named scopes ``kv_gather``,
``model_decode`` and ``kv_scatter``, which a device trace shows on each
operation.

``PagedChainEngine`` is the continuously-batched variant over a
``PagedCache``: admission scatters O(prompt) pages instead of copying the
whole cache, decode gathers only the active slots into a dense batch
(bucketed batch size and page count bound the jit cache), and page
exhaustion preempts the youngest request instead of corrupting state.  Its
greedy token streams are bit-identical to ``ChainEngine``'s — masked cache
positions contribute exact float zeros to attention, and both engines run
the same prefill and decode programs on batches of the same shapes — which
the parity tests and the CI gate hold as a contract.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chains import Chain
from repro.models import Model
from .kv_cache import PAGE_SIZE, PagedCache, SlotCache, kv_span
from .request import Request, State


def _pow2(n: int) -> int:
    return max(1, 1 << (n - 1).bit_length())


# Live jit specializations an engine may hold before clearing its trace
# caches (prefill buckets / decode batch shapes).  Power-of-two bucketing
# already bounds growth to log2(max_seq) shapes; this is the backstop.
PREFILL_BUCKET_LIMIT = 8
DECODE_SHAPE_LIMIT = 16

#: what an engine counts (``counts``), published as ``engine.<name>``
COUNTS = ("prefill_tokens", "prefill_pad_tokens", "preemptions",
          "jit_clears")


class ChainEngine:
    #: a ``repro.obs.SpanRecorder``, handed in by the orchestrator
    recorder = None
    #: this engine's index among its orchestrator's engines (span args)
    engine_id = 0

    def __init__(self, model: Model, params, chain: Chain, capacity: int,
                 max_seq: int, page_size: int = PAGE_SIZE):
        self.model = model
        self.params = params
        self.chain = chain
        self.capacity = capacity
        self.max_seq = max_seq
        self.page_size = page_size
        self.slots = SlotCache(model, capacity, max_seq)
        self.requests: Dict[int, Request] = {}      # slot -> request
        self._init_kv_programs(self.slots, jax.jit(
            self._step_impl, static_argnums=(3,), donate_argnums=(1,)))

    def _init_kv_programs(self, kv, step_jit) -> None:
        # Both KV layouts hand the model's prefill and decode the same
        # batches: a kv_span-sized batch-1 buffer at admission, and the same
        # active rows at the same power-of-two row count and kv_span
        # positions each decode step.  On a TPU a row's logits change with
        # the batch's row count and, at small row counts, with its span; so
        # equal shapes are what keeps the layouts' greedy streams identical.
        self._kv = kv
        self._prefill_buffer = kv.prefill_buffer
        self._prefill_jit = jax.jit(self.model.prefill)
        self._decode_jit = jax.jit(self.model.decode_step, donate_argnums=(1,))
        self._step_jit = step_jit
        self._prefill_shapes: set = set()
        self._step_shapes: set = set()
        self.counts: Dict[str, int] = dict.fromkeys(COUNTS, 0)

    # -- jit-cache hygiene -------------------------------------------------------
    @property
    def prefill_bucket_count(self) -> int:
        """Live prefill-length specializations (gauged by the orchestrator)."""
        return len(self._prefill_shapes)

    def _prefill(self, cache_one, padded: np.ndarray):
        """model.prefill with a bounded trace cache: when a new length bucket
        would exceed PREFILL_BUCKET_LIMIT live specializations, drop them all
        and retrace (rare — buckets are powers of two)."""
        key = padded.shape
        if key not in self._prefill_shapes \
                and len(self._prefill_shapes) >= PREFILL_BUCKET_LIMIT:
            self._prefill_jit.clear_cache()
            self._prefill_shapes.clear()
            self.counts["jit_clears"] += 1
        self._prefill_shapes.add(key)
        return self._prefill_jit(self.params, cache_one,
                                 {"tokens": jnp.asarray(padded)})

    def _step_cache_guard(self, key) -> None:
        """The decode-side twin of the prefill bound (DECODE_SHAPE_LIMIT)."""
        if key not in self._step_shapes \
                and len(self._step_shapes) >= DECODE_SHAPE_LIMIT:
            self._step_jit.clear_cache()
            self._step_shapes.clear()
            self.counts["jit_clears"] += 1
        self._step_shapes.add(key)

    # -- admission --------------------------------------------------------------
    @property
    def has_free_slot(self) -> bool:
        return bool(self.slots.free)

    @property
    def num_active(self) -> int:
        return self.capacity - len(self.slots.free)

    def prefill(self, tokens) -> Tuple[jnp.ndarray, object]:
        """Batch-1 prefill of ``tokens`` into a ``kv_span``-sized buffer, as
        admission runs it: returns the first step's ``(1, vocab)`` logits
        and the filled buffer (no slot or page is taken)."""
        true_len = len(tokens)
        # Right-pad to a power-of-two bucket (bounded jit cache); positions
        # beyond true_len hold garbage keys but decode masks by length, and
        # each future decode overwrites its slot before attending.
        pad_to = kv_span(true_len, self.page_size, self.max_seq)
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :true_len] = tokens
        buf = self._prefill_buffer(pad_to)
        logits, buf = self._prefill(buf, padded)
        if true_len < pad_to:
            # Prefill's last-position logits sit at a padded position; re-feed
            # the true last token at its own position (identical k/v
            # rewritten) to get the correct boundary distribution.
            last = jnp.asarray([int(tokens[-1])], jnp.int32)
            lpos = jnp.asarray([true_len - 1], jnp.int32)
            logits, buf = self._decode_jit(self.params, buf, last, lpos)
        return logits, buf

    def _acquire(self, true_len: int) -> Optional[int]:
        return self.slots.acquire()

    def _release(self, slot: int) -> None:
        del self.requests[slot]
        self.slots.release(slot)

    def admit(self, req: Request, now: float = 0.0) -> bool:
        """Prefill ``req`` into a free slot and take its first token; False
        when no slot (or, paged, no pages for the prompt) is free."""
        tokens = req.context_tokens
        true_len = len(tokens)
        slot = self._acquire(true_len)
        if slot is None:
            return False
        pad = kv_span(true_len, self.page_size, self.max_seq) - true_len
        self.counts["prefill_tokens"] += true_len
        self.counts["prefill_pad_tokens"] += pad
        rec = self.recorder
        if rec is not None:
            rec.dequeued(req.rid)
            top = rec.begin("engine.admit", "engine", engine=self.engine_id,
                            rid=req.rid, prompt=true_len, pad=pad)
            rec.begin("engine.prefill", "engine")
        logits, buf = self.prefill(tokens)
        if rec is not None:
            rec.phase("engine.fetch", "engine")
        next_tok = int(jnp.argmax(logits[0]))
        if rec is not None:
            rec.phase("engine.write", "engine")
        self._kv.write_prefill(slot, buf, true_len)
        if rec is not None:
            rec.end(top)
        req.slot = slot
        req.state = State.RUNNING
        if req.start_time is None:
            req.start_time = now
        self.requests[slot] = req
        req.output.append(next_tok)
        if req.done:                                  # e.g. max_new_tokens == 1
            req.state = State.DONE
            req.finish_time = now
            self._release(slot)
        return True

    # -- decode ----------------------------------------------------------------
    def _step_impl(self, params, cache, rows, span, tokens, lengths):
        # gather the rows' first span positions, decode, write back
        with jax.named_scope("kv_gather"):
            sub = self.slots.gather(cache, rows, span)
        with jax.named_scope("model_decode"):
            logits, sub = self.model.decode_step(params, sub, tokens, lengths)
        with jax.named_scope("kv_scatter"):
            return logits, self.slots.scatter(cache, rows, sub)

    def _plan(self):
        """The active slots, and the step's batch: row count bucketed to a
        power of two, positions cut to the ``kv_span`` the rows' pages
        would cover (the paged engine's shapes, see ``_init_kv_programs``)."""
        active = sorted(self.requests)
        n = len(active)
        rows = np.asarray(active + [active[0]] * (_pow2(n) - n), np.int32)
        tokens = np.asarray([self.requests[s].output[-1] for s in rows],
                            np.int32)
        # slots.lengths[slot] == number of positions already in the cache;
        # this step writes the pending token there and advances it.
        lengths = self.slots.lengths[rows]
        span = kv_span(int(lengths.max()) + 1, self.page_size, self.max_seq)
        return active, (rows, span, tokens, lengths)

    def _launch(self, batch):
        rows, span, tokens, lengths = batch
        self._step_cache_guard((rows.shape, span))
        logits, self.slots.cache = self._step_jit(
            self.params, self.slots.cache, jnp.asarray(rows), span,
            jnp.asarray(tokens), jnp.asarray(lengths))
        return logits

    def step(self, now: float = 0.0) -> List[Request]:
        """One batched decode step over the active slots; returns the
        requests that completed."""
        if not self.requests:
            return []
        rec = self.recorder
        if rec is not None:
            top = rec.begin("engine.step", "engine", engine=self.engine_id)
            rec.begin("engine.plan", "engine")
        active, batch = self._plan()
        n = len(active)
        if not active:
            if rec is not None:
                rec.end(top, rows=0, pad=0)
            return []
        if rec is not None:
            rec.phase("engine.launch", "engine")
        logits = self._launch(batch)
        if rec is not None:
            rec.phase("engine.fetch", "engine")
        next_tokens = np.asarray(jnp.argmax(logits[:n], axis=-1))
        if rec is not None:
            rec.phase("engine.commit", "engine")
        finished = []
        for i, slot in enumerate(active):
            self._kv.lengths[slot] += 1
            req = self.requests[slot]
            req.output.append(int(next_tokens[i]))
            if req.done:
                req.state = State.DONE
                req.finish_time = now
                finished.append(req)
                self._release(slot)
        if rec is not None:
            rec.end(top, rows=n, pad=_pow2(n) - n)
        return finished

    # -- failover ----------------------------------------------------------------
    def evict_all(self) -> List[Request]:
        """Return all in-flight requests (for re-queue) and clear state."""
        out = []
        for slot, req in list(self.requests.items()):
            req.state = State.QUEUED
            req.slot = None
            req.chain_idx = None
            req.retries += 1
            out.append(req)
            self.slots.release(slot)
        self.requests.clear()
        return out


class PagedChainEngine(ChainEngine):
    """Chain engine over a :class:`PagedCache` with continuous batching.

    Differences from the slotted base:
      * ``admit`` prefills into a right-sized batch-1 buffer and scatters
        O(prompt) pages (donated pool buffers), instead of the
        O(capacity * max_seq) whole-cache copy;
      * ``step`` gathers only the active slots into a dense batch — batch
        size and per-row page count are bucketed to powers of two so the
        decode trace cache stays bounded — and scatters exactly one written
        position per row back into the pool;
      * page exhaustion during decode preempts the youngest request (pages
        freed, request requeued with its generated tokens preserved — the
        orchestrator drains :meth:`take_preempted` each round); exhaustion
        at admission refuses the request (JFFC falls through to the next
        chain or queues).

    ``oversubscribe > 1`` grants more slots than the page budget can hold at
    full length — the paging win: short sequences pack into the same s_c
    grant.  The page budget itself stays ``capacity * pages_per_slot``, i.e.
    exactly the memory GCA allocated for ``capacity`` slots.
    """

    def __init__(self, model: Model, params, chain: Chain, capacity: int,
                 max_seq: int, page_size: int = PAGE_SIZE,
                 oversubscribe: float = 1.0):
        self.model = model
        self.params = params
        self.chain = chain
        self.capacity = capacity
        self.max_seq = max_seq
        self.page_size = page_size
        num_slots = max(1, int(capacity * oversubscribe))
        pages_per_slot = -(-max_seq // page_size)
        self.cache = PagedCache(model, num_slots, max_seq,
                                page_size=page_size,
                                total_pages=capacity * pages_per_slot)
        self.requests: Dict[int, Request] = {}      # slot -> request
        self.preempted: List[Request] = []
        self._admit_seq: Dict[int, int] = {}        # slot -> admission counter
        self._seq = 0
        self._init_kv_programs(self.cache, jax.jit(self._step_impl,
                                                   donate_argnums=(1,)))

    # -- admission --------------------------------------------------------------
    @property
    def has_free_slot(self) -> bool:
        return bool(self.cache.free)

    @property
    def num_active(self) -> int:
        return len(self.requests)

    @property
    def free_pages(self) -> int:
        return self.cache.free_pages

    @property
    def pages_in_use(self) -> int:
        return self.cache.total_pages - self.cache.free_pages

    def _acquire(self, true_len: int) -> Optional[int]:
        slot = self.cache.acquire(true_len)
        if slot is not None:             # else no slot, or no pages left
            self._admit_seq[slot] = self._seq
            self._seq += 1
        return slot

    def _release(self, slot: int) -> None:
        self.requests.pop(slot, None)
        self._admit_seq.pop(slot, None)
        self.cache.release(slot)

    def _preempt(self, slot: int) -> None:
        req = self.requests[slot]
        req.state = State.QUEUED
        req.slot = None
        req.chain_idx = None
        req.retries += 1
        self.preempted.append(req)
        self._release(slot)
        self.counts["preemptions"] += 1

    def take_preempted(self) -> List[Request]:
        """Drain requests preempted by page exhaustion (orchestrator
        resubmits them; generated tokens ride along in context_tokens)."""
        out, self.preempted = self.preempted, []
        return out

    # -- decode ----------------------------------------------------------------
    def _step_impl(self, params, leaves, page_ids, slot_idx, tokens, lengths,
                   write_page, write_off):
        # gather the rows' pages, decode, write back one position per row
        with jax.named_scope("kv_gather"):
            cache = self.cache.gather(leaves, page_ids, slot_idx)
        with jax.named_scope("model_decode"):
            logits, cache = self.model.decode_step(params, cache, tokens,
                                                   lengths)
        with jax.named_scope("kv_scatter"):
            return logits, self.cache.scatter(leaves, cache, slot_idx,
                                              lengths, write_page, write_off)

    def _plan(self):
        """Guarantee a write page for every active row, preempting the
        youngest request when the pool runs dry (its pages free the rest);
        then the surviving slots and their dense batch (``decode_view``)."""
        alive = sorted(self.requests, key=lambda s: self._admit_seq[s])
        for slot in list(alive):
            if slot not in alive:
                continue
            while slot in alive and not self.cache.ensure_decode_write(slot):
                self._preempt(alive.pop())
        if not alive:
            return [], None
        active = sorted(alive)
        n = len(active)
        nb = _pow2(n)
        view = self.cache.decode_view(active, nb)
        tokens = np.zeros((nb,), np.int32)
        for i, slot in enumerate(active):
            tokens[i] = self.requests[slot].output[-1]
        tokens[n:] = tokens[0]                     # pad rows mirror row 0
        view["tokens"] = tokens
        return active, view

    def _launch(self, view):
        self._step_cache_guard((view["page_ids"].shape,
                                view["slot_idx"].shape))
        logits, self.cache.leaves = self._step_jit(
            self.params, self.cache.leaves, *(jnp.asarray(view[k]) for k in (
                "page_ids", "slot_idx", "tokens", "lengths", "write_page",
                "write_off")))
        return logits

    # -- failover ----------------------------------------------------------------
    def evict_all(self) -> List[Request]:
        """All in-flight requests (for re-queue), including any preempted
        ones not yet drained, and clear state + pages."""
        out = []
        for slot, req in list(self.requests.items()):
            req.state = State.QUEUED
            req.slot = None
            req.chain_idx = None
            req.retries += 1
            out.append(req)
            self.cache.release(slot)
        self.requests.clear()
        self._admit_seq.clear()
        out.extend(self.take_preempted())
        return out
