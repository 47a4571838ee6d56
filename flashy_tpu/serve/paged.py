# Paged KV cache management. The dense serving cache (engine.py) buys
# its ONE-executable-per-shape invariant by reserving every slot's
# worst case — [S, max_seq_len] rows whether a request uses them or
# not — so HBM, not the MXU, caps concurrency, and identical prompt
# prefixes (system prompts, few-shot headers) are re-prefilled per
# request. This module is the host-side half of the paged layout that
# fixes both:
#
#  * BlockPool — a free-list + refcount manager over the global block
#    pool (ops/paged_attention.py holds the device arrays). Admission
#    RESERVES a request's whole budget (prompt + output tokens, plus
#    the speculative verify overshoot) up front, so a request that was
#    admitted can never OOM the pool mid-decode; requests that do not
#    fit stay queued (QueueFull backpressure at the submit door).
#    Block 0 is the sentinel: never handed out, the landing zone for
#    parked/overshoot writes and the padding of every unassigned table
#    entry.
#  * PrefixIndex — a block-granular prefix cache keyed by token
#    content. A cached K/V row is a pure function of (token, position,
#    params), so any block whose (tokens, positions) match a cached
#    block can be shared by reference: admission walks the longest
#    chain of matching FULL blocks (refcount bump instead of
#    re-prefill), then copy-on-write forks the first PARTIALLY
#    matching block — one device block copy replaces up to
#    block_size - 1 prefill tokens — and the fork is private, so the
#    writer can never mutate rows another slot still reads. Retired
#    requests' prompt blocks stay cached (refcount 0, index-held)
#    until LRU eviction hands them back to the free list.
#
# The matched prefix is capped at len(prompt) - 1: the last prompt
# token is always re-prefilled so the engine gets its first-token
# logits from a real forward. When that single re-written row lands in
# a still-shared block it is bit-identical by the purity argument
# (same token, same position, same params, same executable), so the
# rewrite is exact — the one deliberate exception to never-write-
# shared-blocks.
"""BlockPool + PrefixIndex + the paged model step for DecodeEngine."""
import dataclasses
import heapq
import logging
import typing as tp

import numpy as np

logger = logging.getLogger(__name__)

SENTINEL = 0  # physical block 0: never allocated, absorbs parked writes

# Site consulted before every block allocation batch; the chaos drill
# (flashy_tpu.resilience) injects failures here to prove the scheduler
# sheds via backpressure instead of crashing mid-admission.
POOL_FAULT_SITE = "serve.pool"


class PoolExhausted(RuntimeError):
    """Raised when an admission cannot reserve its blocks.

    The paged counterpart of a full slot table: the scheduler treats it
    as no-capacity-right-now (the request stays queued; QueueFull at
    the submit door is the client-visible backpressure), never as a
    crash.
    """


class CacheBox:
    """One level of indirection over the device pool pytree so MULTIPLE
    engines can read and write the SAME K/V blocks.

    Every compiled step returns a fresh pytree (functional update, with
    donation on accelerators), so an engine rebinds its cache reference
    after each call; two engines sharing plain attributes would diverge
    at the first step. Both instead hold one CacheBox and go through
    `value` — the disaggregated prefill->decode pair in
    `flashy_tpu.serve.fleet` is the user: the prefill engine fills
    blocks, rebinding `value`, and the decode engine's next step reads
    the very same arrays through its own block tables. Safe because the
    scheduler/fleet loop is host-sequential: only one engine's step is
    in flight at a time, and after a donated step the stale buffers are
    unreachable (the box was rebound before anyone else reads it).
    """

    __slots__ = ("value",)

    def __init__(self, value: tp.Any = None):
        self.value = value


_ROOT = ("root",)


@dataclasses.dataclass
class _IndexEntry:
    """One cached full block: its chain key, tokens, and pool block."""
    key: tp.Tuple
    tokens: np.ndarray            # [block_size] int32, this block's tokens
    block: int                    # pool block id holding its K/V
    parent_key: tp.Tuple          # _ROOT or another entry's key
    children: int = 0             # cached entries chaining off this one
    last_use: int = 0             # LRU clock (bumped on every match)


class PrefixIndex:
    """Chain-hash index of cached full blocks.

    Keys are `(parent_key, tokens.tobytes())` — the exact token content
    of the block appended to its parent's chain — so a hit means the
    whole prefix up to and including this block is token-identical, and
    the cached K/V can be shared by reference (rows are pure functions
    of token + position). Partial matches (for copy-on-write forks)
    scan the parent's children for the longest common token prefix.
    """

    def __init__(self):
        self._entries: tp.Dict[tp.Tuple, _IndexEntry] = {}
        self._children: tp.Dict[tp.Tuple, tp.List[_IndexEntry]] = {}
        self._clock = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def blocks(self) -> tp.Set[int]:
        """Pool blocks currently held by the index."""
        return {e.block for e in self._entries.values()}

    def _tick(self, entry: _IndexEntry) -> None:
        self._clock += 1
        entry.last_use = self._clock

    def match(self, prompt: np.ndarray, block_size: int
              ) -> tp.Tuple[tp.List[_IndexEntry],
                            tp.Optional[tp.Tuple[_IndexEntry, int]]]:
        """Longest cached walk of `prompt`.

        Returns `(full, partial)`: `full` is the chain of fully
        matching block entries (block i covers prompt tokens
        [i*bs, (i+1)*bs)); `partial` is the child entry sharing the
        longest non-empty token prefix with the REMAINING prompt (the
        copy-on-write fork source), or None.
        """
        full: tp.List[_IndexEntry] = []
        parent = _ROOT
        n_full = len(prompt) // block_size
        i = 0
        while i < n_full:
            tokens = np.ascontiguousarray(prompt[i * block_size:
                                                 (i + 1) * block_size])
            entry = self._entries.get((parent, tokens.tobytes()))
            if entry is None:
                break
            self._tick(entry)
            full.append(entry)
            parent = entry.key
            i += 1
        rest = prompt[i * block_size:]
        best: tp.Optional[tp.Tuple[_IndexEntry, int]] = None
        if len(rest):
            for child in self._children.get(parent, ()):
                n = int(np.argmin(np.concatenate([
                    child.tokens[:len(rest)] == rest[:len(child.tokens)],
                    [False]])))
                if n > 0 and (best is None or n > best[1]):
                    best = (child, n)
            if best is not None:
                self._tick(best[0])
        return full, best

    def register(self, prompt: np.ndarray, blocks: tp.Sequence[int],
                 block_size: int) -> tp.List[int]:
        """Index the prompt's full blocks; returns the block ids NEWLY
        held by the index (their pool blocks must survive slot
        retirement until evicted). Chains that already exist keep their
        existing entry — the caller's twin block stays private."""
        added: tp.List[int] = []
        parent = _ROOT
        for i in range(len(prompt) // block_size):
            tokens = np.ascontiguousarray(prompt[i * block_size:
                                                 (i + 1) * block_size])
            key = (parent, tokens.tobytes())
            entry = self._entries.get(key)
            if entry is None:
                entry = _IndexEntry(key=key, tokens=tokens.copy(),
                                    block=int(blocks[i]), parent_key=parent)
                self._entries[key] = entry
                self._children.setdefault(parent, []).append(entry)
                if parent is not _ROOT:
                    self._entries[parent].children += 1
                self._tick(entry)
                added.append(entry.block)
            parent = key
        return added

    def evictable(self, refcount: np.ndarray) -> tp.List[_IndexEntry]:
        """Leaf entries whose block no slot references, LRU-first."""
        leaves = [e for e in self._entries.values()
                  if e.children == 0 and refcount[e.block] == 0]
        return sorted(leaves, key=lambda e: e.last_use)

    def parent(self, entry: _IndexEntry) -> tp.Optional[_IndexEntry]:
        """The entry one block up `entry`'s chain (None at the root)."""
        if entry.parent_key is _ROOT:
            return None
        return self._entries[entry.parent_key]

    def evict(self, entry: _IndexEntry) -> int:
        """Drop a (leaf) entry; returns its freed pool block id."""
        assert entry.children == 0, "evict leaves first"
        del self._entries[entry.key]
        self._children[entry.parent_key].remove(entry)
        if entry.parent_key is not _ROOT:
            self._entries[entry.parent_key].children -= 1
        return entry.block


@dataclasses.dataclass
class AdmissionPlan:
    """One admission's block accounting, computed before committing."""
    prompt: np.ndarray
    reserve_blocks: int                 # table entries the slot will own
    full: tp.List[_IndexEntry]          # shared full-block chain
    partial: tp.Optional[tp.Tuple[_IndexEntry, int]]  # COW source, n tokens
    matched_tokens: int                 # capped at len(prompt) - 1
    fresh_needed: int                   # blocks to allocate (incl. COW dst)


class BlockPool:
    """Host-side bookkeeping of the global K/V block pool.

    Owns WHICH pool block belongs to whom — free list, per-block slot
    refcounts, per-slot reservations, and the PrefixIndex — while the
    device arrays live in the engine's cache pytree. All methods are
    host-synchronous (the scheduler is single-threaded); `check()`
    asserts the conservation invariant the paged demo gates on: every
    block is exactly one of {sentinel, free, slot-referenced,
    index-cached} and the pool never over-commits.

    Args:
        num_blocks: pool size INCLUDING the sentinel (capacity is
            num_blocks - 1).
        block_size: tokens per block; must divide max_seq_len.
        max_seq_len: per-slot logical cap (table width derives from it).
        spec_overshoot: extra reserved tokens per request covering the
            speculative verify's write/query overshoot (engine.spec_k).
        prefix_cache: enable the PrefixIndex (sharing + COW); off, every
            admission allocates fresh blocks and retirement frees them
            all.
        window, step_rows: a model with window layers (models/gqa.py)
            keeps, beside this pool's blocks, a static ring a slot a
            window layer of `ring` blocks (`ops.paged_attention.
            ring_blocks`: the `window - 1` rows a query sees behind it
            and the `step_rows` of the largest step) that nothing here
            allocates or frees: the blocks counted here are the
            full-attention layers' alone, and `check()` holds the ring
            to its size. `plan` finds no prefix for such a model
            (`prefix_cache` is off): a hit would need the window layers'
            rows of the prefix's last `window - 1` tokens, which a ring
            has overwritten.
        state_slots: a model with recurrent layers (models/mamba2.py)
            keeps, beside this pool's blocks, one fixed entry a slot a
            layer (a state and a conv tail, `1 + state_slots` entries
            with the sentinel), indexed by slot: nothing here allocates
            or frees them, a reservation's key is its entry, and
            `check()` holds every key inside the entries. `plan` finds
            no prefix for such a model either: the state after a prefix
            is no block's content, and a slot's first slice starts from
            zeros.
    """

    def __init__(self, *, num_blocks: int, block_size: int,
                 max_seq_len: int, spec_overshoot: int = 0,
                 prefix_cache: bool = True, window: int = 0,
                 step_rows: int = 0, state_slots: int = 0):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (sentinel + 1 real), "
                             f"got {num_blocks}")
        if block_size < 1 or max_seq_len % block_size != 0:
            raise ValueError(f"block_size must divide max_seq_len "
                             f"({max_seq_len}), got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_blocks = max_seq_len // block_size  # per-slot table entries
        self.max_seq_len = max_seq_len
        self.spec_overshoot = int(spec_overshoot)
        self.prefix_cache = prefix_cache and not window and not state_slots
        self.window, self.step_rows = int(window), int(step_rows)
        self.state_slots = int(state_slots)
        from ..ops.paged_attention import ring_blocks
        self.ring = (ring_blocks(self.window, self.step_rows, block_size)
                     if window else 0)
        self.capacity = num_blocks - 1
        # min-heap: allocation pops the lowest free block (deterministic
        # tables for tests/traces) in O(log N), not via list sorts
        self._free = list(range(SENTINEL + 1, num_blocks))
        self.refcount = np.zeros(num_blocks, np.int64)
        self.index = PrefixIndex()
        # incrementally maintained mirror of index.blocks, so the
        # per-step accounting views never rebuild a set over the index
        self._cached: tp.Set[int] = set()
        # slot -> (prompt, ordered owned/shared block ids, reserve count)
        self._slots: tp.Dict[int, tp.Tuple[np.ndarray, tp.List[int], int]] = {}
        # counters for metrics / the demo gates
        self.peak_in_use = 0
        self.allocated_total = 0
        self.evictions = 0
        self.cow_forks = 0
        self.prefix_matched_tokens = 0
        self.prefix_total_tokens = 0
        self.preemptions = 0
        self.handoffs = 0

    # ------------------------------------------------------------------
    # accounting views
    # ------------------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def in_use_blocks(self) -> int:
        """Blocks neither free nor sentinel (slot-held or index-cached)."""
        return self.capacity - len(self._free)

    @property
    def cached_blocks(self) -> int:
        """Index-held blocks no live slot references (evictable)."""
        return sum(1 for b in self._cached if self.refcount[b] == 0)

    @property
    def headroom(self) -> int:
        """Blocks an admission could obtain: free + evictable cached."""
        return self.free_blocks + self.cached_blocks

    @property
    def prefix_hit_rate(self) -> float:
        """Cumulative prompt tokens served from the index / submitted.

        ENGINE-lifetime scope, the number the paged demo gates on.
        `ServeMetrics.on_prefix` keeps the same tally per SCHEDULER
        (one serving phase) — same formula, different window; the demo
        runs two schedulers over one engine, so both exist on purpose.
        """
        return (self.prefix_matched_tokens / self.prefix_total_tokens
                if self.prefix_total_tokens else 0.0)

    def reserve_blocks_for(self, prompt_tokens: int,
                           max_new_tokens: int) -> int:
        """Table entries a request must own: prompt + output budget +
        verify overshoot, rounded up to blocks, capped at the table
        width (positions past max_seq_len clamp into the sentinel, the
        dense path's mode='drop')."""
        tokens = prompt_tokens + max_new_tokens + self.spec_overshoot
        return min(-(-tokens // self.block_size), self.max_blocks)

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def plan(self, prompt: np.ndarray,
             max_new_tokens: int) -> AdmissionPlan:
        """Price one admission: prefix walk + blocks still needed."""
        prompt = np.asarray(prompt, np.int32)
        reserve = self.reserve_blocks_for(len(prompt), max_new_tokens)
        full: tp.List[_IndexEntry] = []
        partial = None
        if self.prefix_cache:
            full, partial = self.index.match(prompt, self.block_size)
        matched = len(full) * self.block_size
        if partial is not None:
            matched += partial[1]
        # always leave >= 1 token to prefill (the first-token logits
        # come from a real forward); a partial match shrunk to zero by
        # the cap is no fork at all.
        matched = min(matched, len(prompt) - 1)
        if partial is not None and matched <= len(full) * self.block_size:
            partial = None
        return AdmissionPlan(prompt=prompt, reserve_blocks=reserve,
                             full=full, partial=partial,
                             matched_tokens=matched,
                             fresh_needed=reserve - len(full))

    def _plan_protect(self, plan: AdmissionPlan) -> tp.Set[int]:
        """Blocks this plan references that eviction must not free: the
        matched full chain (their refcount bump happens at commit, so a
        cached-only matched block still LOOKS evictable) and the COW
        fork source (copied from right after commit)."""
        protect = {e.block for e in plan.full}
        if plan.partial is not None:
            protect.add(plan.partial[0].block)
        return protect

    def _headroom_for(self, plan: AdmissionPlan) -> int:
        """Free + evictable blocks NET of the plan's protected set."""
        protect = self._plan_protect(plan)
        evictable = sum(1 for b in self._cached if self.refcount[b] == 0
                        and b not in protect)
        return self.free_blocks + evictable

    def can_admit(self, prompt: np.ndarray, max_new_tokens: int) -> bool:
        """Whether `commit(plan(...))` would succeed right now."""
        plan = self.plan(prompt, max_new_tokens)
        return plan.fresh_needed <= self._headroom_for(plan)

    def _evict_for(self, need: int, protect: tp.Set[int]) -> None:
        """Free cached blocks (LRU leaves first) until `need` are free.

        One scan of the index, then a heap: evicting a leaf may make its
        parent the next leaf, which joins the heap by its own last use —
        the same victims in the same order as picking the globally
        least recently used evictable leaf afresh each time (a retired
        4,000-token prompt is ONE chain of 250 entries whose leaves
        appear one at a time: rescanning the index per block cost
        0.1-1.6 s an admission once the pool was full)."""
        if len(self._free) >= need:
            return

        def usable(entry: _IndexEntry) -> bool:
            return (entry.children == 0 and self.refcount[entry.block] == 0
                    and entry.block not in protect)

        # last_use values are unique (one clock tick each): no ties
        heap = [(e.last_use, e) for e in self.index.evictable(self.refcount)
                if e.block not in protect]
        heapq.heapify(heap)
        while len(self._free) < need:
            if not heap:
                raise PoolExhausted(
                    f"pool over-committed: need {need} free blocks, have "
                    f"{len(self._free)} free + "
                    f"{self.cached_blocks} evictable")
            _, entry = heapq.heappop(heap)
            parent = self.index.parent(entry)
            block = self.index.evict(entry)
            self.evictions += 1
            self._cached.discard(block)
            heapq.heappush(self._free, block)
            if parent is not None and usable(parent):
                heapq.heappush(heap, (parent.last_use, parent))

    def commit(self, plan: AdmissionPlan, slot: int
               ) -> tp.Tuple[np.ndarray, int,
                             tp.Optional[tp.Tuple[int, int]]]:
        """Reserve `plan`'s blocks for `slot`.

        Returns `(table_row, prefill_start, cow)`: a `[max_blocks]`
        int32 table row (sentinel-padded), the position prefill resumes
        at (== matched tokens), and the `(src, dst)` pool blocks the
        engine must device-copy for a COW fork (None when no partial
        match). Atomic: on PoolExhausted nothing changed. Consults the
        `serve.pool` fault point first, so the chaos drill can fail
        admissions deterministically.
        """
        from ..resilience import InjectedFault, fault_point
        if slot in self._slots:
            raise ValueError(f"slot {slot} already holds a reservation")
        try:
            fault_point(POOL_FAULT_SITE, slot=slot,
                        need=plan.fresh_needed)
        except InjectedFault as exc:
            raise PoolExhausted(f"injected allocation failure: {exc}") \
                from exc
        if plan.fresh_needed > self._headroom_for(plan):
            raise PoolExhausted(
                f"admission needs {plan.fresh_needed} blocks, pool has "
                f"{self._headroom_for(plan)} (free {self.free_blocks} + "
                f"evictable cached net of this plan's own matched "
                f"blocks)")
        self._evict_for(plan.fresh_needed, self._plan_protect(plan))
        fresh = [heapq.heappop(self._free)
                 for _ in range(plan.fresh_needed)]
        self.allocated_total += len(fresh)
        blocks = [e.block for e in plan.full] + fresh
        for b in blocks:
            self.refcount[b] += 1
        row = np.full(self.max_blocks, SENTINEL, np.int32)
        row[:len(blocks)] = blocks
        self._slots[slot] = (plan.prompt, blocks, plan.reserve_blocks)
        self.prefix_matched_tokens += plan.matched_tokens
        self.prefix_total_tokens += len(plan.prompt)
        cow = None
        if plan.partial is not None:
            # the first fresh block sits right after the shared chain —
            # exactly the table entry the partial match covers
            cow = (plan.partial[0].block, fresh[0])
            self.cow_forks += 1
        self.peak_in_use = max(self.peak_in_use, self.in_use_blocks)
        # O(touched) sanity inline; the full O(pool) check() stays for
        # demos/tests/fault paths, off the per-admission hot path
        assert SENTINEL not in blocks and len(set(blocks)) == len(blocks)
        return row, plan.matched_tokens, cow

    def on_live(self, slot: int) -> None:
        """Prefill finished: index the slot's full prompt blocks so
        later admissions can share them (no-op without prefix_cache)."""
        if not self.prefix_cache:
            return
        prompt, blocks, _ = self._slots[slot]
        self._cached.update(
            self.index.register(prompt, blocks, self.block_size))

    def release(self, slot: int) -> tp.List[int]:
        """Retire a slot's reservation; returns the blocks actually
        freed (index-cached blocks stay resident at refcount 0 until
        evicted — that IS the prefix cache)."""
        prompt, blocks, _ = self._slots.pop(slot)
        freed: tp.List[int] = []
        for b in blocks:
            self.refcount[b] -= 1
            assert self.refcount[b] >= 0, f"double release of block {b}"
            if self.refcount[b] == 0 and b not in self._cached:
                heapq.heappush(self._free, b)
                freed.append(b)
        return freed

    def evict_slot(self, slot: int) -> tp.List[int]:
        """Preempt a live slot: atomically tear down its reservation
        mid-flight and return the blocks actually freed.

        The preemption primitive (`flashy_tpu.serve.fleet` quota /
        priority classes): every block the slot references drops one
        refcount, and blocks nothing else holds return to the free list
        — EXCEPT prompt blocks the prefix index still caches, which
        stay resident at refcount 0. That is what makes preemption
        rollback cheap: the preempted request's re-admission re-matches
        its own prompt chain, so the re-prefill shrinks to the uncached
        suffix plus whatever it had generated. No K/V cleanup is needed
        for rows the request wrote past its prompt: once the engine
        parks the slot's position they sit beyond every causal horizon
        until a later reservation overwrites them — the same
        rollback-is-free argument as speculative rejection.

        Identical conservation outcome to `release()` (the invariant
        `check()` asserts holds across either), kept as a distinct
        verb so preemptions are separately counted and auditable.
        Raises KeyError for a slot holding no reservation.
        """
        if slot not in self._slots:
            raise KeyError(f"slot {slot} holds no reservation to evict")
        self.preemptions += 1
        return self.release(slot)

    def transfer_slot(self, src: int, dst: int) -> tp.List[int]:
        """Re-key a reservation from slot `src` to slot `dst` (the
        disaggregated prefill->decode handoff).

        Refcounts, the prefix index, and the device blocks themselves
        are untouched — ownership of the SAME block list moves between
        slot keys, which is the whole point of paged disaggregation:
        the transfer unit is a block id list, never a K/V slab. Returns
        the ordered block list now keyed to `dst`. Raises KeyError when
        `src` holds no reservation and ValueError when `dst` already
        holds one.
        """
        if src not in self._slots:
            raise KeyError(f"slot {src} holds no reservation to transfer")
        if dst in self._slots:
            raise ValueError(f"slot {dst} already holds a reservation")
        self._slots[dst] = self._slots.pop(src)
        self.handoffs += 1
        return list(self._slots[dst][1])

    def holds(self, slot: int) -> bool:
        """Whether `slot` currently holds a reservation."""
        return slot in self._slots

    def slot_blocks(self, slot: int) -> tp.List[int]:
        """The ordered pool blocks backing a live slot's table."""
        return list(self._slots[slot][1])

    # ------------------------------------------------------------------
    # invariants + stats
    # ------------------------------------------------------------------
    def check(self) -> None:
        """Conservation invariant: sentinel + free + referenced/cached
        partition the pool; refcounts match the live reservations.

        O(pool): the demo/test/fault-path gate, not called per step —
        mutations keep O(touched) asserts inline instead."""
        if np.any(self.refcount < 0):
            raise AssertionError("negative block refcount")
        if self.window:
            # the window layers' half: nothing to leak (a ring is a
            # slot's for good), but the ring must hold what the largest
            # step writes behind a full window, and no prefix may have
            # been shared past it
            rows = max(self.step_rows, 1 + self.spec_overshoot)
            if self.ring * self.block_size < self.window - 1 + rows:
                raise AssertionError(
                    f"a window layer's ring of {self.ring} blocks is "
                    f"smaller than the window's {self.window - 1} rows "
                    f"and a step's {rows}")
            if len(self.index) or self.prefix_matched_tokens:
                raise AssertionError("a prefix was shared under window "
                                     "layers")
        if self.state_slots:
            # the recurrent layers' half: an entry a slot, for good
            if len(self.index) or self.prefix_matched_tokens:
                raise AssertionError("a prefix was shared under recurrent "
                                     "layers")
            outside = [s for s in self._slots
                       if not 0 <= s < self.state_slots]
            if outside:
                raise AssertionError(
                    f"reservations {outside} lie outside the "
                    f"{self.state_slots} state entries")
        if self._cached != self.index.blocks:
            raise AssertionError("cached-block mirror drifted from the "
                                 "index")
        want = np.zeros(self.num_blocks, np.int64)
        for _, blocks, _ in self._slots.values():
            for b in blocks:
                want[b] += 1
        if not np.array_equal(want, self.refcount):
            raise AssertionError("refcounts drifted from reservations")
        free = set(self._free)
        if SENTINEL in free:
            raise AssertionError("sentinel block on the free list")
        held = {b for _, blocks, _ in self._slots.values() for b in blocks}
        held |= self.index.blocks
        if free & held:
            raise AssertionError(f"blocks both free and held: {free & held}")
        if len(free) + len(held) != self.capacity:
            raise AssertionError(
                f"pool leak: {len(free)} free + {len(held)} held != "
                f"capacity {self.capacity}")

    def stats(self) -> tp.Dict[str, float]:
        """Occupancy + prefix counters for ServeMetrics/the demo."""
        return {
            "capacity": self.capacity,
            "free": self.free_blocks,
            "in_use": self.in_use_blocks,
            "cached": self.cached_blocks,
            "occupancy": self.in_use_blocks / self.capacity,
            "peak_in_use": self.peak_in_use,
            "evictions": self.evictions,
            "cow_forks": self.cow_forks,
            "allocated_total": self.allocated_total,
            "prefix_hit_rate": self.prefix_hit_rate,
            "preemptions": self.preemptions,
            "handoffs": self.handoffs,
            # blocks a slot of a window layer's ring: static, in none
            # of the counts above (0: no window layer)
            "window_ring_blocks": self.ring,
        }


# ----------------------------------------------------------------------
# the paged model step (device side)
# ----------------------------------------------------------------------
def paged_apply_step(model, params, cfg, tokens, positions, cache, table,
                     kernel: str = "gather", stats=None, slots=None,
                     used=None, fresh=None):
    """Forward `tokens` [B, T] at `positions` [B, T] against the pool.

    The paged twin of models/decoding._apply_step: same embed, MLP/MoE,
    norms and head (imported, not copied), with the dense slab
    read/write swapped for table-driven pool gathers/scatters
    (ops/paged_attention). `table` is [B, max_blocks] int32; every
    row's write lands at its own (block, offset), so decode, verify and
    chunked prefill share this one implementation. `kernel` picks the
    pool READ: 'gather' is the XLA reference (and the interpret-mode
    oracle), 'fused' the Pallas paged-decode kernel
    (ops/paged_decode.py) — legal here because every engine read path
    queries consecutive positions per row, the fused kernel's one
    extra contract. The write stays `paged_write` either way (a
    per-row scatter XLA already fuses). A latent block
    (`attn_kind='mla'`) writes its latent pool entry and attends in the
    cached form (models/mla.py) by the same choice: 'gather' is
    `latent_paged_attention`, 'fused' the latent walk of the same
    kernel module (`fused_latent_attention`). A grouped-attention
    block (`attn_kind='gqa'`, models/gqa.py) goes by its LAYER's kind:
    a full-attention layer writes and reads its own entry through the
    table (scope `attn/global`: 'gather' is the XLA gather of the
    table's view and `gqa.attend` in query tiles, 'fused' the grouped
    walk of the kernel module, `fused_grouped_attention`), a window
    layer the ring of each row's slot (`slots` [B], the engine slot a
    row belongs to, -1 for a parked row; scope `attn/window`: the
    masked dense form over the ring's view, whose row c holds the
    position `ring_positions` says, whichever `kernel`), which the
    table does not know. Under a `layer_pattern` a layer is its one
    mixer: an expert layer holds no entry, an attention layer is the
    full-attention grouped layer without an MLP, and a Mamba-2 layer
    advances entry `1 + slots[i]` of its `state` / `conv` tables (entry
    0, the sentinel, for a parked row): a decode run (`used` None) one
    token a row in place, a prefill slice (`used` [B], the real tokens
    of each right-padded row) through the chunked form with the slot's
    state and conv tail going in and coming out, from zeros where
    `fresh` [B] says the row begins a sequence. Each expert
    layer of an unstacked model appends its (assignments, experts hit)
    counts to `stats` when a list is given.
    """
    import jax
    import jax.numpy as jnp

    from ..models.decoding import (_attn_residual, _embed_tokens,
                                   _head_logits, _mlp_residual, _qkv_heads,
                                   grouped_projections, grouped_residual,
                                   latent_projections, latent_residual,
                                   mamba_residual)
    from ..ops.paged_attention import (_physical, grouped_table_view,
                                       grouped_write, latent_paged_attention,
                                       latent_paged_write, paged_attention,
                                       paged_write, ring_address, ring_view)
    from ..ops.paged_decode import (fused_grouped_attention,
                                    fused_latent_attention,
                                    fused_paged_attention)

    if kernel not in ("gather", "fused"):
        raise ValueError(f"kernel must be 'gather' or 'fused', "
                         f"got {kernel!r}")
    attend, attend_latent = (
        (fused_paged_attention, fused_latent_attention) if kernel == "fused"
        else (paged_attention, latent_paged_attention))

    def latent_layer(bp, x, entry):
        (q_lat, q_rope), (c_kv, k_rope) = latent_projections(cfg, bp, x,
                                                             positions)
        with jax.named_scope("kv_write"):
            entry = latent_paged_write(entry, c_kv, k_rope, table,
                                       positions)
        with jax.named_scope("attn"):
            o_lat = attend_latent(cfg, q_lat, q_rope, entry, table,
                                  positions)
        x = latent_residual(cfg, bp, x, o_lat)
        return _mlp_residual(cfg, bp, x, stats), entry

    def kv_layer(bp, x, entry):
        # the dense step's scopes (models/decoding.py), same names
        q, k, v = _qkv_heads(cfg, bp, x, positions)
        with jax.named_scope("kv_write"):
            entry = paged_write(entry, k, v, table, positions)
        with jax.named_scope("attn"):
            attn = attend(q, entry, table, positions,
                          head_dim=cfg.head_dim, dtype=cfg.dtype)
        x = _attn_residual(cfg, bp, x, attn)
        return _mlp_residual(cfg, bp, x, stats), entry

    def mamba_layer(bp, x, entry):
        if slots is None:
            raise ValueError("a Mamba-2 layer's state is addressed by "
                             "slot: paged_apply_step(slots=)")
        rows = slots + 1  # a parked row (-1) advances the sentinel
        state, tail = entry["state"], entry["conv"]
        if used is None:  # a decode run: the tables, in place
            x, state, tail = mamba_residual(cfg, bp, x, state, tail,
                                            rows=rows)
            return x, {"state": state, "conv": tail}
        own, own_tail = state[rows], tail[rows]
        if fresh is not None:
            own = jnp.where(fresh[:, None, None, None], 0.0, own)
            own_tail = jnp.where(fresh[:, None, None],
                                 jnp.zeros((), own_tail.dtype), own_tail)
        x, own, own_tail = mamba_residual(cfg, bp, x, own, own_tail,
                                          used=used)
        return x, {"state": state.at[rows].set(own),
                   "conv": tail.at[rows].set(own_tail)}

    def grouped_layer(kind, mlp=True):
        from ..models import gqa

        def layer(bp, x, entry):
            q, k, v = grouped_projections(cfg, kind, bp, x, positions)
            if kind.window and slots is None:
                raise ValueError("a window layer's ring is addressed by "
                                 "slot: paged_apply_step(slots=)")
            with jax.named_scope("kv_write"):
                if kind.window:
                    at = ring_address(slots, positions, entry["k"].shape[1])
                else:
                    at = _physical(table, positions, entry["k"].shape[1])
                entry = grouped_write(entry, k, v, *at)
            with jax.named_scope("attn"), jax.named_scope(kind.scope):
                if kernel == "fused" and not kind.window:
                    out = fused_grouped_attention(cfg, kind, q, entry,
                                                  table, positions)
                else:
                    view = (ring_view(entry, slots, positions)
                            if kind.window
                            else grouped_table_view(entry, table))
                    out = gqa.attend(cfg, kind, bp["attn"], q, *view,
                                     positions)
            x = grouped_residual(cfg, bp, x, out)
            return (_mlp_residual(cfg, bp, x, stats) if mlp else x), entry

        return layer

    # one layer body for the stack, or one a layer kind
    if cfg.layer_pattern:
        from ..models.gqa import layer_kinds
        from ..models.transformer import pattern_kinds
        pattern = pattern_kinds(cfg)
        kinds = layer_kinds(cfg) if "*" in pattern else None

        def expert_only(bp, x, entry):
            return _mlp_residual(cfg, bp, x, stats), entry

        layers = [mamba_layer if kind == "M" else expert_only if kind == "E"
                  else grouped_layer(kinds[i], mlp=False)
                  for i, kind in enumerate(pattern)]
    elif cfg.attn_kind == "gqa":
        from ..models.gqa import layer_kinds
        layers = [grouped_layer(kind) for kind in layer_kinds(cfg)]
    else:
        layers = [latent_layer if cfg.attn_kind == "mla" else kv_layer
                  ] * cfg.num_layers
    p = params["params"]
    x = _embed_tokens(p, tokens, cfg.dtype)
    if cfg.scan_layers:
        stacked = p["blocks"]["block"]  # every leaf has leading [L]

        def body(x, layer_in):
            bp, entry = layer_in
            x, entry = layers[0](bp, x, entry)
            return x, entry

        x, new_cache = jax.lax.scan(body, x, (stacked, cache))
    else:
        new_cache = {}
        for i in range(cfg.num_layers):
            name = f"block_{i}"
            x, new_cache[name] = layers[i](p[name], x, cache[name])

    return _head_logits(p, x, cfg), new_cache


def copy_block_fn(cfg, kv_dtype: str) -> tp.Callable:
    """Build the COW device copy: `(cache, src, dst) -> cache` with
    block `src`'s rows duplicated onto block `dst` across every layer
    and leaf (K/V payloads and their scales, or a latent pool's `c` and
    `kr`). The block axis of a leaf follows the pool's spec
    (`ops.paged_attention.layer_pool_specs`): a layer-stacked leaf has it
    one further in. A window layer's rings and a Mamba-2 layer's state
    entries are no blocks of the pool and pass through. One fixed-shape
    executable per engine — warmed with
    the decode/verify steps so a fork never compiles mid-traffic."""
    import jax.numpy as jnp

    from ..ops.paged_attention import cfg_pool_spec, layer_pool_specs

    def copy_entry(entry, spec, src, dst):
        out = {}
        for name, leaf in entry.items():
            axis = leaf.ndim - len(spec[name][0])
            row = jnp.take(leaf, src, axis=axis)
            idx = (slice(None),) * axis + (dst,)
            out[name] = leaf.at[idx].set(row)
        return out

    if cfg.scan_layers:
        spec = cfg_pool_spec(cfg, 1, 1, kv_dtype)
        return lambda entry, src, dst: copy_entry(entry, spec, src, dst)
    specs = layer_pool_specs(cfg, 1, 1, kv_dtype)
    paged = [True] * cfg.num_layers
    if cfg.attn_kind == "gqa":
        from ..models.gqa import layer_kinds
        paged = [not kind.window for kind in layer_kinds(cfg)]
    if cfg.layer_pattern:
        paged = [kind == "*" and own for kind, own in
                 zip(cfg.layer_pattern, paged)]

    def copy(cache, src, dst):
        return {f"block_{i}": (
                    copy_entry(cache[f"block_{i}"], specs[i], src, dst)
                    if paged[i] else cache[f"block_{i}"])
                for i in range(cfg.num_layers)}

    return copy

