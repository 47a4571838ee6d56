# Continuous batching. The classic serving mistake is batch-synchronous
# decode: admit a batch, run it to completion, admit the next — short
# requests wait on the longest one and freed capacity idles. Continuous
# batching retires each request the moment it finishes (EOS or length
# budget) and prefills the next queued request into the freed slot while
# decode keeps streaming for everyone else. The queue is FIFO (arrival
# order == admission order — the fairness the tests assert) with a hard
# depth cap: `submit()` past it raises QueueFull, the backpressure
# signal a front-end turns into HTTP 429 / retry-after.
"""ContinuousBatchingScheduler: FIFO admission into engine slots."""
import collections
import dataclasses
import itertools
import logging
import time
import typing as tp

import numpy as np

from ..observability import span
from ..resilience import chaos
from .engine import DecodeEngine, StepHandle
from .metrics import SPAN_STEP, ServeMetrics, gc_totals
from .paged import PoolExhausted

logger = logging.getLogger(__name__)

# One scheduler step as a span tree on the profiler's clock (the
# engine's spans nest inside: serve/prefill_chunk, serve/table_upload,
# serve/decode and its dispatch child; the read-backs of the step before
# it, serve/decode/readback and serve/prefill_chunk/readback, follow).
# SPAN_STEP is serve/metrics.py's: the step's record is judged there.
SPAN_ADMISSION = "serve/admission"
SPAN_GAUGES = "serve/gauges"
SPAN_RETIRE = "serve/retire"
# the slots given back by count as their budget's last step is launched
# (`rows`: how many), and the bookkeeping of a first token just read
SPAN_LAUNCH_OUT = "serve/launch_out"
SPAN_FIRST_TOKEN = "serve/first_token"


class QueueFull(RuntimeError):
    """Raised by `submit()` when the admission queue is at capacity.

    This IS the backpressure mechanism: the caller sheds or retries;
    the scheduler never buffers unboundedly.
    """


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle record.

    States: queued -> (prefilling ->) running -> done ('prefilling'
    only exists on chunked-prefill engines, where a slot is occupied
    for several ticks before the first token). `generated` grows one
    token per engine step — or up to `k+1` per step under speculative
    decoding; `output` is prompt + generated (the EOS, when one fired,
    is included — it is the terminator the model actually emitted,
    matching `generate(eos_token=...)`).
    """
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_token: tp.Optional[int] = None
    tenant: str = "default"
    priority: int = 0
    state: str = "queued"
    slot: tp.Optional[int] = None
    generated: tp.List[int] = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    deadline: tp.Optional[float] = None  # absolute; None = no TTL
    admitted_at: tp.Optional[float] = None
    first_token_at: tp.Optional[float] = None
    finished_at: tp.Optional[float] = None
    finish_reason: tp.Optional[str] = None  # 'eos' | 'length' | 'expired'
    preemptions: int = 0  # times this request was evicted mid-flight
    in_flight: int = 0  # tokens launched on the device, not yet delivered

    @property
    def done(self) -> bool:
        return self.state == "done"

    @property
    def output(self) -> np.ndarray:
        """prompt + generated tokens, as one int32 array."""
        return np.concatenate([np.asarray(self.prompt, np.int32),
                               np.asarray(self.generated, np.int32)])

    @property
    def resume_prompt(self) -> np.ndarray:
        """What admission must prefill: the original prompt plus any
        tokens already generated before a preemption / engine death put
        this request back in a queue. Re-prefilling the retained
        output re-derives the exact K/V state the evicted slot held
        (K/V rows are pure functions of (token, position, params)), so
        a resumed request's remaining tokens are token-exact."""
        return self.output if self.generated \
            else np.asarray(self.prompt, np.int32)

    @property
    def remaining_budget(self) -> int:
        """max_new_tokens net of tokens already generated — the decode
        budget a resumed admission still owes this request."""
        return self.max_new_tokens - len(self.generated)

    @property
    def launched_out(self) -> bool:
        """Every token of the budget is delivered or on its way: known
        by COUNT when the last step is dispatched, its values unread."""
        return len(self.generated) + self.in_flight >= self.max_new_tokens


@dataclasses.dataclass
class _InFlight:
    """One launched step whose tokens nobody has read: the engine's
    handles and the host's record of who owned which row AT LAUNCH — a
    slot may have been handed to another request since."""
    step: int  # the `step` stat of the serve/step that launched it
    # (slot, request, the final slice's handle): first tokens, in order
    firsts: tp.List[tp.Tuple[int, Request, StepHandle]] = dataclasses.field(
        default_factory=list)
    decode: tp.Optional[StepHandle] = None
    # (slot, request) of every row the decode step advanced
    rows: tp.List[tp.Tuple[int, Request]] = dataclasses.field(
        default_factory=list)


class ContinuousBatchingScheduler:
    """FIFO request queue feeding a DecodeEngine's slots.

    One `step()` = admit (prefill queued requests into free slots) +
    one engine decode over all S slots + retire finished requests.
    Decode never waits for admission and admission never waits for a
    batch boundary — capacity freed mid-stream is refilled on the next
    step while the other slots keep generating.

    ONE STEP STAYS IN FLIGHT. A step launches its prefill slice and its
    decode run on the device and only THEN reads the step before it
    back (`engine.collect`): that one's device work ended while the
    host admitted, planned and dispatched, and the device already has
    its successor queued when it ends. So the tokens of step k reach
    their requests during step k + 1 — to the requests that owned the
    rows when step k was LAUNCHED (`_InFlight`), whoever holds the
    slots by then. A request that ends by its budget is known to end by
    count when its last step is launched: its slot is parked and freed
    right then (`Request.launched_out`), no step later than in
    lock-step. One that ends by EOS is seen a step late: its row
    decodes once more, inside its own reservation, and that token is
    dropped (`late_rows`). `flush()` reads what is in flight NOW, for a
    caller that needs a request's tokens up to date between steps;
    `preempt()`, `drain_for_reroute()` and `run()`'s end do it
    themselves, and `idle` is False until it is done. With a `draft`
    attached the step is lock-step instead: a draft proposes from the
    last tokens' VALUES, so that step cannot be launched before they
    are read.

    On a paged engine (`DecodeEngine(cache_layout='paged')`) admission
    additionally gates on BLOCK-POOL headroom: the queue head waits
    (FIFO) until the pool can reserve its whole prompt + output
    budget, `engine.admit()` walks the prefix cache (its return is
    where chunked prefill resumes — shared prompt tokens are never
    recomputed), and an injected/raced `PoolExhausted` re-queues the
    request instead of crashing. Pool occupancy and prefix-hit samples
    flow to the metrics each step.

    On a chunked-prefill engine (`DecodeEngine(chunk=...)`) admission
    assigns the slot immediately but the prompt is prefilled in fixed
    `chunk` slices, at most `prefill_chunks_per_step` slices per
    `step()` — so a long prompt never monopolizes a step, and the
    inter-token stall it can impose on live slots is bounded by one
    chunk's compute instead of one full bucket's.

    With a `draft` provider attached, each step verifies the draft's k
    proposed tokens per slot in ONE `[S, k+1]` engine call and emits
    `accepted + 1` tokens per live slot (see serve/draft.py); greedy
    output is token-exact vs `generate()` whatever the draft proposes.

    Args:
        engine: the DecodeEngine supplying slots and compiled steps.
        max_queue: admission-queue depth; `submit()` past it raises
            QueueFull (backpressure).
        metrics: a ServeMetrics; one is created (sharing the engine's
            tracer) when not given.
        draft: optional DraftProvider enabling speculative decoding.
            Its `k` must match `engine.spec_k` when that is set (the
            warm-up covered exactly that verify shape).
        prefill_chunks_per_step: chunked-prefill slices advanced per
            scheduler step (the prefill/decode interleave ratio).
        tracing: optional `serve.tracing.RequestTracer`; every request
            lifecycle transition is mirrored to it (async Perfetto
            spans + requests.jsonl), subject to its sampling policy.
        uid_source: an iterator yielding request uids; by default each
            scheduler counts privately from 0. A fleet passes ONE
            shared `itertools.count` to every member scheduler so uids
            stay unique across engines (routing and re-routing key on
            them).

    Priority classes: admission picks the highest-`priority` queued
    request first (FIFO among equals, so the default all-zero workload
    keeps the arrival-order fairness the tests assert), and a blocked
    high-priority request PREEMPTS the lowest-priority strictly-lower
    running request: the victim's blocks are evicted
    (`BlockPool.evict_slot` — prefix-cached prompt blocks stay
    resident), the victim re-queues with its generated tokens
    retained, and its eventual re-admission prefills prompt+generated
    so the remaining tokens are token-exact (K/V purity).
    """

    def __init__(self, engine: DecodeEngine, max_queue: int = 128,
                 metrics: tp.Optional[ServeMetrics] = None,
                 draft: tp.Optional[tp.Any] = None,
                 prefill_chunks_per_step: int = 1,
                 tracing: tp.Optional[tp.Any] = None,
                 uid_source: tp.Optional[tp.Iterator[int]] = None):
        self.engine = engine
        self.max_queue = max_queue
        self.metrics = metrics or ServeMetrics(tracer=engine.tracer)
        self.tracing = tracing
        self.metrics.static_info.setdefault("cache_layout",
                                            engine.cache_layout)
        self.metrics.static_info.setdefault("kv_dtype", engine.kv_dtype)
        # capacity math as a printed number: decode-state bytes one slot
        # reserves under this engine's layout (constant in max_seq_len
        # on the SSD layout — the O(1)-cache contract made observable)
        self.metrics.static_info.setdefault("state_bytes_per_slot",
                                            engine.state_bytes_per_slot())
        self.draft = draft
        if draft is not None and engine.spec_k is not None \
                and draft.k != engine.spec_k:
            raise ValueError(
                f"draft proposes k={draft.k} tokens but the engine "
                f"warmed its verify step for spec_k={engine.spec_k}; "
                f"a mismatch would compile post-warm-up")
        if prefill_chunks_per_step < 1:
            raise ValueError(f"prefill_chunks_per_step must be >= 1, "
                             f"got {prefill_chunks_per_step}")
        self.prefill_chunks_per_step = prefill_chunks_per_step
        self._queue: tp.Deque[Request] = collections.deque()
        self._running: tp.Dict[int, Request] = {}  # slot -> request
        # slot -> [request, next chunk start, prompt being prefilled
        # (resume_prompt at admission)]; insertion order == FIFO
        self._prefilling: tp.Dict[int, tp.List[tp.Any]] = {}
        self._draft_slots: tp.Set[int] = set()  # slots the draft tracks
        self._uid = uid_source if uid_source is not None \
            else itertools.count()
        self.admitted_order: tp.List[int] = []  # uids, admission sequence
        # prompt tokens prefilled in the latest step / the max over the
        # run — the demo asserts max <= chunk (the stall bound).
        self.prefill_tokens_last_step = 0
        self.max_prefill_tokens_per_step = 0
        self.steps = 0  # scheduler steps taken (the `step` stat of SPAN_STEP)
        self._in_flight: tp.Optional[_InFlight] = None  # launched, unread
        self._step_start = time.perf_counter()  # top of the latest step
        self._emitted = 0  # decode tokens delivered, all steps
        self._late_rows = 0  # rows found late since the last step opened

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def live_count(self) -> int:
        return len(self._running)

    @property
    def idle(self) -> bool:
        return (not self._queue and not self._running
                and not self._prefilling and self._in_flight is None)

    def submit(self, prompt: tp.Any, max_new_tokens: int,
               eos_token: tp.Optional[int] = None,
               ttl: tp.Optional[float] = None,
               tenant: str = "default",
               priority: int = 0) -> Request:
        """Queue one request; returns its Request handle.

        Raises QueueFull at the depth cap and ValueError for requests
        that could never fit the cache — a prompt longer than the
        largest prefill bucket, or `prompt + max_new_tokens` beyond
        `max_seq_len` — so an impossible request fails at the door, not
        mid-decode after queueing behind everyone else and occupying a
        slot. `ttl` (seconds) is an optional queue-wait budget: a
        request still queued past its deadline is shed with
        `finish_reason='expired'` instead of being prefilled after the
        client stopped waiting for it. `tenant` labels the request's
        per-tenant metric rollups (and quota accounting at the fleet
        door); `priority` picks its admission class — higher admits
        first and may preempt strictly-lower running requests.
        """
        if not isinstance(tenant, str) or not tenant:
            raise ValueError(f"tenant must be a non-empty string, "
                             f"got {tenant!r}")
        if not isinstance(priority, int) or isinstance(priority, bool):
            raise ValueError(f"priority must be an int, got {priority!r}")
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(f"prompt must be 1-D non-empty, got {prompt.shape}")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        unbounded = getattr(self.engine, "unbounded", False)
        if not unbounded or self.engine.chunk is None:
            # bucketed prefill caps prompts at the largest bucket even
            # on an unbounded engine (the bucket IS the compiled shape)
            largest_bucket = self.engine.bucket_for(self.engine.max_seq_len)
            if prompt.size > largest_bucket:
                raise ValueError(
                    f"prompt length {prompt.size} exceeds the largest "
                    f"prefill bucket ({largest_bucket}); it can never be "
                    f"prefilled")
        if not unbounded:
            # an unbounded (pure-SSD) engine has no per-slot tensor
            # that grows with context — no length ceiling to enforce
            total = prompt.size + max_new_tokens
            if total > self.engine.max_seq_len:
                raise ValueError(
                    f"prompt + max_new_tokens = {total} exceeds the "
                    f"engine's max_seq_len {self.engine.max_seq_len}")
        if ttl is not None and ttl <= 0:
            raise ValueError(f"ttl must be positive (seconds), got {ttl}")
        if len(self._queue) >= self.max_queue:
            self.metrics.on_reject(tenant=tenant)
            if self.tracing is not None:
                self.tracing.on_reject(len(self._queue))
            raise QueueFull(
                f"admission queue is at capacity ({self.max_queue}); "
                f"retry after in-flight requests drain")
        now = time.perf_counter()
        request = Request(uid=next(self._uid), prompt=prompt,
                          max_new_tokens=max_new_tokens, eos_token=eos_token,
                          tenant=tenant, priority=priority,
                          submitted_at=now,
                          deadline=now + ttl if ttl is not None else None)
        self._queue.append(request)
        self.metrics.on_submit(tenant=tenant)
        if self.tracing is not None:
            self.tracing.on_submit(request)
        return request

    def _shed_expired(self, now: tp.Optional[float] = None) -> int:
        """Drop queued requests whose TTL deadline passed; returns #shed.

        Expired requests finish as 'expired' without ever touching a
        slot — prefilling work the client already abandoned would only
        delay the requests still waiting.
        """
        if not any(r.deadline is not None for r in self._queue):
            return 0
        now = time.perf_counter() if now is None else now
        kept: tp.Deque[Request] = collections.deque()
        shed = 0
        for request in self._queue:
            if request.deadline is not None and now >= request.deadline:
                request.state = "done"
                request.finish_reason = "expired"
                request.finished_at = now
                self.metrics.on_expired(tenant=request.tenant)
                if self.tracing is not None:
                    self.tracing.on_finish(request, "expired")
                shed += 1
                logger.debug("request %d expired after %.3fs in queue",
                             request.uid, now - request.submitted_at)
            else:
                kept.append(request)
        self._queue = kept
        return shed

    def _first_token(self, slot: int, request: Request,
                     first: int) -> None:
        """A first token reached its request: record TTFT, seed the
        draft, and retire the request if this ends it (EOS / budget of
        1). The caller has put the request in `_running` if it still
        holds its slot. A RESUMED request (preempted / re-routed after
        engine death) lands here again when its prompt+generated
        re-prefill finishes; its TTFT was already recorded, so only the
        token counts."""
        now = time.perf_counter()
        request.state = "running"
        request.generated.append(first)
        if request.first_token_at is None:
            request.first_token_at = now
            self.metrics.on_first_token(now - request.submitted_at)
        if self.tracing is not None:
            # on resume too: the tracer re-opened the queued span at
            # preemption, and this transition closes its prefill phase
            self.tracing.on_first_token(request)
        if request.eos_token is not None and first == request.eos_token:
            self._finish(request, "eos")
        elif len(request.generated) >= request.max_new_tokens:
            self._finish(request, "length")
        elif self.draft is not None:
            self.draft.begin(slot, request.prompt, first)
            self._draft_slots.add(slot)

    def _read_first(self, slot: int, request: Request,
                    handle: StepHandle) -> None:
        """Read a final slice's first token (the engine's read-back
        span), then do its bookkeeping under a span of its own."""
        first = int(self.engine.collect(handle)[0])
        with span(SPAN_FIRST_TOKEN, self.engine.tracer, category="serve",
                  slot=slot):
            self._first_token(slot, request, first)

    def _pop_next(self) -> Request:
        """Remove and return the next request to admit: the highest
        `priority`, earliest-queued among equals — so an all-default
        workload admits in pure arrival order (the FIFO fairness the
        tests assert) and priority only ever reorders ACROSS classes."""
        best = 0
        for i in range(1, len(self._queue)):
            if self._queue[i].priority > self._queue[best].priority:
                best = i
        request = self._queue[best]
        del self._queue[best]
        return request

    def _try_preempt(self, priority: int) -> bool:
        """Evict ONE running request of strictly lower priority to make
        room for a blocked admission; returns whether a victim existed.
        The victim is the lowest-priority running request (most recent
        uid among ties — least sunk decode work by FIFO admission)."""
        if all(r.priority >= priority for r in self._running.values()):
            return False
        self.flush()  # a candidate may just have finished
        victim: tp.Optional[Request] = None
        for request in self._running.values():
            if request.priority >= priority:
                continue
            if victim is None \
                    or (request.priority, -request.uid) \
                    < (victim.priority, -victim.uid):
                victim = request
        if victim is None:
            return False
        self.preempt(victim.slot)
        return True

    def preempt(self, slot: int) -> Request:
        """Evict the running request in `slot` and re-queue it with its
        generated tokens retained; returns the victim.

        The engine tears the slot down through `BlockPool.evict_slot`
        (prompt blocks the prefix index caches stay resident, so the
        re-admission re-matches them); the victim re-enters the queue
        at the front of its priority class and its next admission
        prefills `resume_prompt` with `remaining_budget` — token-exact
        continuation, since K/V rows are pure functions of
        (token, position, params). What is in flight is read first, so
        the victim keeps every token the device made for it; a slot
        whose request that read just finished raises KeyError, like a
        slot that runs nothing.
        """
        self.flush()
        request = self._running.pop(slot)
        if slot in self._draft_slots:
            self._draft_slots.discard(slot)
            self.draft.retire(slot)
        self.engine.preempt_slot(slot)
        request.state = "queued"
        request.slot = None
        request.preemptions += 1
        self._queue.appendleft(request)
        self.metrics.on_preempt(tenant=request.tenant)
        if self.tracing is not None:
            self.tracing.on_preempt(request)
        logger.debug("request %d preempted with %d tokens generated",
                     request.uid, len(request.generated))
        return request

    def enqueue(self, request: Request, front: bool = False) -> None:
        """Re-inject an existing Request (no new uid, no submit
        metrics) — the re-route path after an engine death: the fleet
        drains the dead scheduler and enqueues each survivor here. The
        depth cap is NOT applied: these requests were already admitted
        once and must not be dropped by the door."""
        request.state = "queued"
        request.slot = None
        if front:
            self._queue.appendleft(request)
        else:
            self._queue.append(request)

    def cancel_queued(self, uid: int) -> Request:
        """Remove a still-queued request by uid; returns it.

        The admission-rollback path: the fleet door accepts a request
        into a member queue FIRST and only then journals it to the
        durable WAL — if that append exhausts its retries, the request
        was never acknowledged durable and must leave the queue (and
        return its quota credit) rather than run un-logged. Only legal
        while the request is still 'queued'; once prefill starts the
        WAL record already exists, so there is nothing to roll back.
        """
        for i, request in enumerate(self._queue):
            if request.uid == uid:
                del self._queue[i]
                return request
        raise ValueError(f"request {uid} is not in the admission queue "
                         f"(already admitted, finished, or never here)")

    def advance_uids(self, beyond: int) -> None:
        """Fast-forward the uid source past `beyond` (inclusive).

        WAL recovery re-admits requests with their ORIGINAL uids (dedup
        keys on them), so the shared counter of a freshly built fleet
        must skip everything the WAL already issued — otherwise the
        first new submit would collide with a replayed uid. Draws and
        discards values; a gap in the uid sequence is fine (uniqueness,
        not density, is the contract).
        """
        while next(self._uid) < beyond:
            pass

    def drain_for_reroute(self) -> tp.List[Request]:
        """Pull EVERY unfinished request out of this scheduler without
        touching the engine — the engine is presumed dead, so no
        retire/release calls are issued against it. Requests come back
        reset to 'queued' with generated tokens retained (running and
        prefilling first, by uid, then the queue in order); re-
        admission elsewhere prefills `resume_prompt`, which re-derives
        the lost K/V exactly. Tokens the engine had already made when it
        was declared dead are read first (`flush()`: a request they
        complete is done, not drained)."""
        self.flush()
        in_flight = sorted(
            list(self._running.values())
            + [entry[0] for entry in self._prefilling.values()],
            key=lambda r: r.uid)
        requests = in_flight + list(self._queue)
        self._queue.clear()
        self._running.clear()
        self._prefilling.clear()
        self._draft_slots.clear()
        for request in requests:
            request.state = "queued"
            request.slot = None
        return requests

    def _admit(self) -> int:
        """Assign queued requests to free slots; returns #admitted
        (slots assigned this step).

        Monolithic engines prefill the whole (bucketed) prompt at
        assignment; chunked engines only reserve here and prefill in
        `_advance_prefill`. A resumed request (preempted earlier)
        prefills its `resume_prompt` under `remaining_budget`.
        """
        admitted = 0
        while self._queue:
            request = self._pop_next()
            if (request.deadline is not None
                    and time.perf_counter() >= request.deadline):
                # expired while earlier admissions in this very step were
                # prefilling: shed at the door, never occupy the slot.
                request.state = "done"
                request.finish_reason = "expired"
                request.finished_at = time.perf_counter()
                self.metrics.on_expired(tenant=request.tenant)
                if self.tracing is not None:
                    self.tracing.on_finish(request, "expired")
                continue
            prompt = request.resume_prompt
            budget = request.remaining_budget
            if not self.engine.free_count \
                    or not self.engine.can_admit(prompt, budget):
                # No free slot, or (paged layout) the block pool lacks
                # headroom for the head's whole budget. A higher-
                # priority head may PREEMPT a strictly-lower running
                # request and retry; otherwise admission stays FIFO —
                # the head waits at the front for retirements to free
                # capacity, and the queue filling up surfaces as
                # QueueFull at the submit door (backpressure, by
                # design never an over-committed pool).
                self._queue.appendleft(request)
                if self._try_preempt(request.priority):
                    continue  # capacity freed; re-check the same head
                break
            slot = self.engine.acquire_slot()
            assert slot is not None
            try:
                start = self.engine.admit(slot, prompt, budget)
            except PoolExhausted as exc:
                # an injected allocation failure (chaos drill,
                # `serve.pool` fault site) or headroom lost since the
                # check: release the slot, keep the request queued.
                # The scheduler sheds via backpressure — QueueFull at
                # the door, TTL expiry in the queue — never a crash.
                logger.warning("admission of request %d shed: %s",
                               request.uid, exc)
                self.engine.allocator.release(slot)
                self._queue.appendleft(request)
                break
            if self.engine.cache_layout == "paged":
                self.metrics.on_prefix(start, int(prompt.size))
            request.slot = slot
            request.admitted_at = time.perf_counter()
            self.metrics.on_queue_wait(
                request.admitted_at - request.submitted_at)
            if self.tracing is not None:
                self.tracing.on_admit(request, slot, start)
            self.admitted_order.append(request.uid)
            admitted += 1
            if self.engine.chunk is None:
                # the monolithic prefill waits for its own token
                first = self.engine.prefill(slot, prompt)
                self._running[slot] = request
                self._first_token(slot, request, first)
            else:
                # prefill resumes where the prefix cache left off
                # (start > 0 is a prefix hit: those tokens' K/V are
                # shared by reference, never recomputed)
                request.state = "prefilling"
                self._prefilling[slot] = [request, start, prompt]
        return admitted

    def _advance_prefill(self, launched: _InFlight) -> None:
        """Advance chunked prefills by at most `prefill_chunks_per_step`
        slices across the in-progress prefills, oldest first (FIFO down
        to the tick) — the bound on the stall a long prompt imposes. A
        prompt's last slice joins `launched`: its first token is read
        with the rest of this step, during the next."""
        self.prefill_tokens_last_step = 0
        budget = self.prefill_chunks_per_step
        for slot in list(self._prefilling):
            if budget <= 0:
                break
            request, start, prompt = self._prefilling[slot]
            new_start, handle = self.engine.dispatch_prefill_chunk(
                slot, prompt, start, uid=request.uid, step=launched.step)
            budget -= 1
            if self.tracing is not None:
                self.tracing.on_prefill_chunk(request, start, new_start)
            self.prefill_tokens_last_step += new_start - start
            if handle is None:
                self._prefilling[slot][1] = new_start
                continue
            del self._prefilling[slot]
            if self.draft is not None:  # lock-step: the draft needs it
                self._running[slot] = request
                self._read_first(slot, request, handle)
                continue
            # the slice put the row live on the device: the decode run
            # launched next carries it, unless its budget is this token
            request.in_flight += 1
            launched.firsts.append((slot, request, handle))
            if request.launched_out:
                with span(SPAN_LAUNCH_OUT, self.engine.tracer,
                          category="serve", rows=1):
                    self.engine.retire(slot)
            else:
                self._running[slot] = request
        self.max_prefill_tokens_per_step = max(
            self.max_prefill_tokens_per_step, self.prefill_tokens_last_step)

    # ------------------------------------------------------------------
    # decode + retirement
    # ------------------------------------------------------------------
    def _finish(self, request: Request, reason: str) -> None:
        request.state = "done"
        request.finish_reason = reason
        request.finished_at = time.perf_counter()
        if self._running.get(request.slot) is request:
            # it still holds its slot (one launched out to its budget
            # gave it back when its last step was dispatched)
            del self._running[request.slot]
            self.engine.retire(request.slot)
            if request.slot in self._draft_slots:
                self._draft_slots.discard(request.slot)
                self.draft.retire(request.slot)
        self.metrics.on_done(request.finished_at - request.submitted_at,
                             reason, tenant=request.tenant,
                             tokens=len(request.generated))
        if self.tracing is not None:
            self.tracing.on_finish(request, reason)
        logger.debug("request %d done (%s): %d prompt + %d generated",
                     request.uid, reason, request.prompt.size,
                     len(request.generated))

    def _feed(self, request: Request, tokens: tp.Sequence[int],
              gap: float) -> tp.Tuple[int, bool]:
        """Append emitted tokens to a running request, stopping at EOS
        or the length budget; returns (#kept, finished). The first
        token of the batch carries the step's latency as its ITL, the
        rest arrive in the same burst (ITL 0) — literal inter-token
        arrival times, so spec-on p95 still reflects step cost."""
        kept = 0
        for token in tokens:
            token = int(token)
            request.generated.append(token)
            kept += 1
            self.metrics.on_token(gap if kept == 1 else 0.0)
            if request.eos_token is not None and token == request.eos_token:
                self._finish(request, "eos")
                return kept, True
            if len(request.generated) >= request.max_new_tokens:
                self._finish(request, "length")
                return kept, True
        return kept, False

    def step(self) -> int:
        """Shed expired + admit/advance prefill + launch one decode step
        + read the step before it back and retire what it finished (or,
        with a draft, one lock-step speculative verify step); returns
        the decode tokens it delivered to their requests.

        A crash anywhere in the step closes every in-flight request
        span first (`tracing.finalize('crashed')` — the finalize
        convention: the trace stays loadable and the journal records
        how far each request got) and then propagates.
        """
        try:
            return self._step()
        except Exception:
            if self.tracing is not None:
                self.tracing.finalize("crashed")
            raise

    def flush(self) -> int:
        """Read back the step in flight, if any, and deliver its tokens;
        returns the decode tokens delivered. After it every request's
        `generated` holds all the device has made for it and nothing is
        in flight: the lock-step a caller gets by `step(); flush()`."""
        before = self._emitted
        self._collect()
        return self._emitted - before

    def _step(self) -> int:
        # the ITL clock starts here: a token's gap is the whole step
        # that delivered it, the prefill slice the step carried included
        self._step_start = time.perf_counter()
        # `in_flight`: a step is unread as this one starts; `late_rows`:
        # rows found late (decoded for a request that had ended) by the
        # read-backs since the last step opened — a span's stats are
        # fixed when it opens, and a read-back comes last in its step
        state = {"in_flight": int(self._in_flight is not None),
                 "queued": len(self._queue),
                 "prefilling": len(self._prefilling),
                 "running": len(self._running)}
        late, self._late_rows = self._late_rows, 0
        self.metrics.on_step(state["in_flight"])
        launched = _InFlight(step=self.steps)
        # what the step's span and its children take, by name, from the
        # span primitive's own clock reads; beside it the thread's CPU
        # time and the collector's: the record of a slow step
        phases: tp.Dict[str, float] = {}
        cpu, collected = time.thread_time(), gc_totals()
        with span(SPAN_STEP, self.engine.tracer, category="serve",
                  phases=phases, step=launched.step, late_rows=late, **state):
            self.steps += 1
            emitted = self._run_step(launched)
        cpu, now = time.thread_time() - cpu, gc_totals()
        self.metrics.on_step_end(launched.step, phases, cpu,
                                 now[0] - collected[0], now[1] - collected[1],
                                 **state)
        return emitted

    def _run_step(self, launched: _InFlight) -> int:
        """The inside of `serve/step`: every phase a child span."""
        before = self._emitted
        tracer = self.engine.tracer
        with span(SPAN_ADMISSION, tracer, category="serve",
                  queued=len(self._queue)):
            self._shed_expired()
            self._admit()
        self._advance_prefill(launched)
        with span(SPAN_GAUGES, tracer, category="serve"):
            self.metrics.on_gauges(queue_depth=len(self._queue),
                                   live=self.engine.live_count,
                                   capacity=self.engine.slots)
            pool = self.engine.pool_stats()
            if pool is not None:
                self.metrics.on_pool(occupancy=pool["occupancy"],
                                     in_use=int(pool["in_use"]),
                                     capacity=int(pool["capacity"]),
                                     cached=int(pool["cached"]))
        if self._running:
            # inside the ITL-measured region on purpose: an injected
            # delay here lands in the per-token `gap` the SLO engine
            # samples, and an injected raise still unwinds through
            # step()'s finalize
            chaos.fault_point("serve.step", queue_depth=len(self._queue),
                              live=len(self._running))
            if self.draft is not None:
                return self._speculative_step(launched.step)
            self._launch_decode(launched)
        # the step before this one ended on the device while the host
        # did the above: read it, then leave this one in flight
        self._collect()
        if launched.firsts or launched.decode is not None:
            self._in_flight = launched
        return self._emitted - before

    def _launch_decode(self, launched: _InFlight) -> None:
        """Dispatch one decode step over the running rows and note who
        owns them. A request whose budget this step completes gives its
        slot back NOW, values unread: the parking is enqueued behind the
        step, ahead of whatever the slot's next owner dispatches."""
        launched.decode = self.engine.dispatch_decode(step=launched.step)
        launched.rows = list(self._running.items())
        out = []
        for slot, request in launched.rows:
            request.in_flight += 1
            if request.launched_out:
                out.append(slot)
        if out:
            with span(SPAN_LAUNCH_OUT, self.engine.tracer, category="serve",
                      rows=len(out)):
                for slot in out:
                    del self._running[slot]
                    self.engine.retire(slot)

    def _collect(self) -> None:
        """Read the step in flight back, if any, and deliver its tokens
        to the requests that owned its rows at launch."""
        landed, self._in_flight = self._in_flight, None
        if landed is None:
            return
        for slot, request, handle in landed.firsts:
            request.in_flight -= 1
            self._read_first(slot, request, handle)
        if landed.decode is None:
            return
        tokens = self.engine.collect(landed.decode)
        gap = time.perf_counter() - self._step_start
        late = 0
        with span(SPAN_RETIRE, self.engine.tracer, category="serve"):
            for slot, request in landed.rows:
                request.in_flight -= 1
                if request.done:
                    # it ended by EOS a step ago, seen after this row
                    # was launched: the token is nobody's
                    late += 1
                    continue
                kept, finished = self._feed(request, [int(tokens[slot])],
                                            gap)
                self._emitted += kept
                if not finished and self.tracing is not None:
                    self.tracing.on_step_tokens(request, kept)
        self._late_rows += late
        self.metrics.on_rows(len(landed.rows), late)

    def _speculative_step(self, step: int) -> int:
        """k drafted tokens per slot verified in ONE [S, k+1] call, read
        back at once; each live slot emits accepted+1 tokens (EOS /
        budget may truncate the span — the engine slot is retired then,
        so the overshoot never lands anywhere)."""
        drafts = self.draft.propose()
        out, accepted = self.engine.decode_speculative(drafts, step=step)
        gap = time.perf_counter() - self._step_start
        with span(SPAN_RETIRE, self.engine.tracer, category="serve"):
            return self._retire_verified(drafts, out, accepted, gap)

    def _retire_verified(self, drafts: np.ndarray, out: np.ndarray,
                         accepted: np.ndarray, gap: float) -> int:
        emitted = 0
        accepted_counts: tp.List[int] = []
        for slot, request in list(self._running.items()):
            tokens = out[slot, :int(accepted[slot]) + 1]
            accepted_counts.append(int(accepted[slot]))
            kept, finished = self._feed(request, tokens, gap)
            emitted += kept
            if not finished:
                if self.tracing is not None:
                    self.tracing.on_step_tokens(
                        request, kept, accepted=int(accepted[slot]))
                self.draft.observe(slot, tokens[:kept],
                                   self.engine.slot_length(slot))
        self.metrics.on_spec_step(drafted=int(drafts.shape[1]),
                                  accepted=accepted_counts,
                                  emitted=emitted)
        return emitted

    def run(self, max_steps: int = 1_000_000) -> None:
        """Step until every queued/running request finished.

        `max_steps` is a watchdog against scheduler bugs (a request that
        can never retire); hitting it raises instead of spinning.
        """
        for _ in range(max_steps):
            if self.idle:
                return
            self.step()
        raise RuntimeError(
            f"scheduler did not drain in {max_steps} steps: "
            f"{len(self._queue)} queued, {len(self._running)} running")
