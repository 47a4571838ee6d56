# Slot-based decode engine. One KV cache of static shape
# [S, max_seq_len] is partitioned into S per-request slots; ONE compiled
# decode step of shape [S, 1] advances every live slot together, however
# many are live (an active mask, not a shape change, expresses liveness
# — so the executable never recompiles as requests come and go, the
# compiler-first caching discipline of the SSD/O(1)-cache line of work).
# Prefill writes a new request's prompt K/V into its slot through
# per-power-of-two-bucket executables — or, in chunked mode, through
# fixed [1, chunk] slices the scheduler interleaves with decode ticks —
# and speculative decoding adds ONE [S, k+1] verify step that scores k
# drafted tokens per slot per call (accepted counts are data, never
# shapes), so the whole serving lifetime still touches a fixed,
# pre-warmable set of compiled shapes.
"""DecodeEngine: fixed-slot KV cache + static-shape decode/verify steps."""
import gc
import logging
import typing as tp

import numpy as np

from ..observability import Tracer, span
from .compile_cache import CompileCache, bucket_length

logger = logging.getLogger(__name__)

# Tracer span/counter kinds for the serving path (category "serve").
SPAN_PREFILL = "serve/prefill"
SPAN_PREFILL_CHUNK = "serve/prefill_chunk"
SPAN_DECODE = "serve/decode"
SPAN_VERIFY = "serve/verify"
SPAN_ADMIT = "serve/admit"
# children of the spans above: the host's share of a step, split by who
# makes the device wait (readers: benchmarks/readers/program_spans.py)
SPAN_TABLE_UPLOAD = "serve/table_upload"
SPAN_DISPATCH = "/dispatch"   # suffixes under decode / verify /
SPAN_READBACK = "/readback"   # prefill_chunk
# a span's stats are fixed when it opens, and the expert layers' counts
# come back with the step's tokens: they ride on this empty child,
# opened after the read-back, last inside its parent
SPAN_MOE = "/moe"


class StepHandle(tp.NamedTuple):
    """What a dispatched step left on the device until `collect()`
    reads it: nothing in it has been waited for."""
    span: str     # SPAN_DECODE | SPAN_PREFILL_CHUNK: whose children read it
    read: tp.Any  # int32 device array: the tokens, the expert counts behind
    tap: tp.Any   # the float32 logits they were taken from, or None
    # the `step` stat of the serve/step that dispatched it (None by
    # hand): its read-back, a scheduler step later, carries the same
    step: tp.Optional[int] = None


def _step_stat(step: tp.Optional[int]) -> tp.Dict[str, int]:
    """The `step` stat of a dispatch span and of the read-back that
    belongs to it: the scheduler's step number, nothing by hand."""
    return {} if step is None else {"step": step}


def _zero_ssd_leaves(cache: tp.Any, fresh: tp.Any) -> tp.Any:
    """Zero the SSD state leaves of a cache pytree when `fresh` (a
    traced bool scalar) is set; attention K/V leaves pass through.
    Trace-safe: a select, never a shape change."""
    import jax
    import jax.numpy as jnp

    def leaf(path, x):
        if any(getattr(p, "key", None) == "ssd" for p in path):
            return jnp.where(fresh, jnp.zeros_like(x), x)
        return x

    return jax.tree_util.tree_map_with_path(leaf, cache)


def state_bytes_per_slot(cfg: tp.Any, max_seq_len: int, cache_layout: str,
                         *, kv_dtype: str = "model",
                         block_size: int = 16, ring: int = 0) -> int:
    """Decode-state bytes ONE slot reserves at `max_seq_len`, by layout.

    Host arithmetic only (no allocation) — the capacity number
    `ServeMetrics.static_info` prints and the O(1)-state gate measures:

      dense:  per-layer [max_seq_len, H, Dh] K+V slabs (a grouped
              config's by its layer's kind, a window layer's whole
              too: the dense layout bounds nothing);
      paged:  the slot's full block budget (max_seq_len / block_size
              blocks) at `block_bytes` — int8 pools count payload +
              scales, exactly what admission reserves — and, where the
              config has window layers, their rings of `ring` blocks
              (the engine's; by default the least a paged engine has,
              `ring_blocks` for a step of `block_size` rows), which do
              NOT grow with `max_seq_len`, and, where it has Mamba-2
              layers (`layer_pattern`), their state and conv tail, which
              do not either (only its attention layers page);
      ssd:    SSD layers contribute the fixed [H, Dh, Dstate] f32
              state — NO max_seq_len term, the O(1) contract — while
              any attention layers in a hybrid stack keep their dense
              slabs (hybrid cache accounting: the sum is dominated by
              whichever layers still scale with context).
    """
    import jax.numpy as jnp
    from ..models.transformer import mixer_pattern
    pattern = mixer_pattern(cfg)
    act_itemsize = jnp.dtype(cfg.dtype).itemsize
    if cfg.attn_kind == "gqa":
        from ..models import gqa
        widths = gqa.key_dim(cfg) + gqa.value_dim(cfg)
        kv_slabs = [max_seq_len * kind.kv_heads * widths * act_itemsize
                    for kind in gqa.layer_kinds(cfg)]
    elif cfg.attn_kind == "mla":  # one latent row a token, not K and V
        from ..models.mla import latent_width
        kv_slabs = [max_seq_len * latent_width(cfg) * act_itemsize
                    ] * cfg.num_layers
    else:
        kv_slabs = [2 * max_seq_len * cfg.num_heads * cfg.head_dim
                    * act_itemsize] * cfg.num_layers
    ssd_state = cfg.num_heads * cfg.head_dim * cfg.ssd_state_dim * 4
    if cache_layout == "dense":
        return sum(kv_slabs)
    if cache_layout == "paged":
        from ..models.gqa import has_window
        from ..ops.paged_attention import (block_bytes, ring_blocks,
                                           state_bytes, token_bytes)
        if max_seq_len % block_size:
            raise ValueError(f"block_size {block_size} must divide "
                             f"max_seq_len {max_seq_len}")
        rings = 0
        if has_window(cfg):
            ring = ring or ring_blocks(cfg.window, block_size, block_size)
            rings = ring * block_size * token_bytes(cfg, kv_dtype)[1]
        return rings + state_bytes(cfg, 0) + (
            max_seq_len // block_size) * block_bytes(cfg, block_size,
                                                     kv_dtype)
    if cache_layout == "ssd":
        return sum(ssd_state if m == "ssd" else slab
                   for m, slab in zip(pattern, kv_slabs))
    raise ValueError(f"unknown cache_layout {cache_layout!r}")


class SlotAllocator:
    """Free-list over the S cache slots.

    `acquire()` hands out the lowest free slot (deterministic, so tests
    and traces are reproducible) or None when every slot is live;
    `release()` returns a slot to the pool. Double-release and
    out-of-range slots raise — both are scheduler bugs worth failing
    loudly on, not states to paper over.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"need at least one slot, got {capacity}")
        self.capacity = capacity
        self._free = list(range(capacity - 1, -1, -1))  # pop() -> lowest
        self._live: tp.Set[int] = set()

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def live_count(self) -> int:
        return len(self._live)

    @property
    def live(self) -> tp.FrozenSet[int]:
        return frozenset(self._live)

    def acquire(self, slot: tp.Optional[int] = None) -> tp.Optional[int]:
        """Claim the lowest free slot, or a SPECIFIC free slot.

        The specific form exists for mirrored allocators (a draft
        model's engine must hold exactly the slots the target engine
        assigned — see serve/draft.py); asking for a live or
        out-of-range slot raises, since a mirror drifting from its
        target is a bug to fail loudly on."""
        if slot is None:
            if not self._free:
                return None
            slot = self._free.pop()
            self._live.add(slot)
            return slot
        if slot not in self._free:
            raise ValueError(f"slot {slot} is not free (live: "
                             f"{sorted(self._live)})")
        self._free.remove(slot)
        self._live.add(slot)
        return slot

    def release(self, slot: int) -> None:
        if slot not in self._live:
            raise ValueError(f"slot {slot} is not live (free: double "
                             f"release?) — live set: {sorted(self._live)}")
        self._live.discard(slot)
        self._free.append(slot)
        self._free.sort(reverse=True)  # keep lowest-first hand-out


class DecodeEngine:
    """S-slot KV cache + compiled prefill/decode steps over it.

    Purely tensor-level: it owns the cache, the per-slot device-visible
    state (last token, length, active mask) and the CompileCache of
    executables; request semantics (queueing, retirement, metrics) live
    in the scheduler. A step is two halves: `dispatch_decode()` /
    `dispatch_prefill_chunk()` enqueue it and return a `StepHandle` at
    once — the compiled steps advance the per-slot state on the device,
    so the next one can be dispatched behind it — and `collect()` waits
    for a handle and reads its tokens; `decode()` / `prefill_chunk()`
    are one after the other. The host mirrors move at dispatch: they
    describe every step dispatched so far. Greedy by default;
    `temperature > 0` samples with a per-step split of `rng`.

    Args:
        model: a TransformerLM (its config drives shapes/dtype).
        params: the model variables ({'params': ...}).
        slots: S, the number of concurrent requests.
        max_seq_len: per-slot cache length; defaults to (and is capped
            by) the model's `config.max_seq_len`.
        temperature: 0 -> greedy (bit-identical to `generate()`);
            > 0 -> categorical sampling.
        rng: PRNG key for sampling (required when temperature > 0).
        pad_token: token id emitted for inactive slots and used to pad
            prompts up to their bucket (never attended: causal mask).
        chunk: when set, prompts prefill in fixed `[1, chunk]` slices
            driven by `prefill_chunk()` instead of one monolithic
            power-of-two bucket — the compiled prefill set shrinks to
            {chunk} plus one `tail_bucket`, and a long prompt costs
            many cheap ticks instead of one step-monopolizing call.
            Must divide `max_seq_len` (keeps every slice inside the
            cache without index clamping).
        tail_bucket: the small second executable chunked prefill uses
            when the remaining prompt fits it (defaults to
            `min_bucket`); must be <= chunk.
        spec_k: when set, `warmup()` also pre-compiles the `[S, k+1]`
            speculative verify step for this draft length (the step
            itself compiles on demand for any k — spec_k only moves
            the compile to warm-up).
        cache_layout: 'dense' (default) keeps the `[S, max_seq_len]`
            slab — the reference implementation exactness checks
            compare against. 'paged' stores K/V in a global block pool
            `[num_blocks, block_size, H, Dh]` with per-slot block
            tables (serve/paged.py + ops/paged_attention.py):
            admission reserves a request's whole block budget up
            front, identical prompt prefixes are shared by refcount
            through a content-hash index (with copy-on-write forks for
            partially shared blocks), and the same ONE-executable-per-
            shape discipline holds — tables and liveness are inputs,
            never shapes. Paged engines always prefill in chunks
            (`chunk` defaults to `block_size`). 'ssd' is REQUIRED (and
            only valid) when the model's mixer pattern contains SSD
            layers: each such layer's slot state is one resident
            [H, Dh, Dstate] f32 tensor in the pooled cache — constant
            bytes per slot whatever the context length — prefill runs
            the chunked dual form and carries the emitted state, decode
            advances the recurrence. Hybrid stacks keep their attention
            layers' dense [S, max_seq_len] slabs beside the SSD states
            in the same cache pytree; a PURE-SSD stack sets
            `self.unbounded` and may stream sessions past max_seq_len.
        block_size: tokens per pool block (paged only); must divide
            `max_seq_len`.
        num_blocks: pool size including the sentinel block (paged
            only); defaults to worst case (every slot at max_seq_len)
            — size it DOWN to serve more slots than HBM could hold
            densely, admission backpressure keeps it safe.
        kv_dtype: 'model' stores pool K/V in the compute dtype; 'int8'
            quantizes cache writes (per-row/per-head absmax scales
            stored beside the pool — models/quantize.quantize_kv),
            halving-or-better cache bytes and decode read bandwidth.
        kernel: the paged pool READ implementation (paged only).
            'gather' is the XLA reference path (and the interpret-mode
            oracle the fused kernel is tested against); 'fused' routes
            decode, speculative verify and chunked prefill through the
            Pallas paged-decode kernel (ops/paged_decode.py — block
            iteration straight off the table, in-kernel int8 dequant
            under the FT203 scale fold, online softmax; a latent pool
            through the same module's latent walk, a grouped pool's
            full-attention layers through its grouped walk — the
            window layers' rings keep the masked dense read). 'auto'
            (the default) resolves to 'fused' on TPU and 'gather'
            elsewhere, and to 'gather' for a latent or grouped pool
            whose blocks the kernel cannot copy
            (`ops.paged_decode.default_kernel`);
            on CPU an explicit kernel='fused' runs in interpret mode
            (what the demo and the parity tests do).
        prefix_cache: enable cross-request prefix sharing (paged only).
            A model with window layers shares none, whatever is asked
            (`BlockPool`: a hit would need rows a ring has overwritten).
        cache_scope: prefix for this engine's compile-cache keys (and
            therefore its RecompileWatchdog entry names). REQUIRED
            whenever two engines coexist in one process — different
            models produce different executables under otherwise
            identical keys, and even with separate caches the default
            telemetry path shares one watchdog, where colliding names
            would misreport a second engine's first compile as the
            first engine's recompile. `ModelDraft` scopes its mirror
            as "draft".
        compile_cache: a CompileCache to share; by default one is built
            against the active telemetry's watchdog/tracer
            (`observability.get_telemetry()`), falling back to a
            private watchdog so recompile accounting always works.
            Only share a cache between engines whose `cache_scope`s
            differ.
        pool: an existing `BlockPool` to SHARE with other engines
            (paged only) — the disaggregated-serving seam: a prefill-
            role engine fills blocks, then hands the slot off to a
            decode-role engine as a block id list
            (`serve.fleet.handoff`). Its `block_size`, `max_seq_len`
            and (when `spec_k` is set) `spec_overshoot` must cover this
            engine's shapes. By default each engine builds a private
            pool.
        cache_box: a `serve.paged.CacheBox` holding the device pool
            pytree to share between engines over one `pool` (paged
            only). An empty box is filled by this engine; co-resident
            engines then read/write the SAME blocks through their own
            tables. Requires `pool` to be shared too.
        pool_slot_base: offset added to this engine's slot ids when
            keying `pool` reservations. Engines sharing one pool MUST
            use disjoint `[base, base + slots)` ranges — otherwise two
            engines' slot 0 would collide on one reservation key.
        keep_logits: a tap for a check that compares this engine's own
            logits with a reference (paged only). The paged decode and
            prefill-slice executables then also return the float32
            logits they sampled from ([S, V]; [1, V] of a final slice's
            last used row), which stay on the device: in the step's
            handle while it is in flight, then, from `collect()` on, in
            `tapped['decode']` / `tapped['prefill_chunk']` until the
            next collected step replaces them — the logits of the
            tokens most recently READ; the engine never reads them. The
            verify step is not tapped. Off (the default), every
            executable is the one it always was.
    """

    # every compiled step takes the cache as operand 1, donated so XLA
    # updates it in place; the step returns the new cache and the engine
    # rebinds it
    _donate = (1,)

    def __init__(self, model, params, *, slots: int,
                 max_seq_len: tp.Optional[int] = None,
                 temperature: float = 0.0,
                 rng: tp.Optional[tp.Any] = None,
                 pad_token: int = 0,
                 min_bucket: int = 4,
                 chunk: tp.Optional[int] = None,
                 tail_bucket: tp.Optional[int] = None,
                 spec_k: tp.Optional[int] = None,
                 cache_layout: str = "dense",
                 block_size: int = 16,
                 num_blocks: tp.Optional[int] = None,
                 kv_dtype: str = "model",
                 kernel: str = "auto",
                 prefix_cache: bool = True,
                 cache_scope: str = "",
                 compile_cache: tp.Optional[CompileCache] = None,
                 tracer: tp.Optional[Tracer] = None,
                 pool: tp.Optional[tp.Any] = None,
                 cache_box: tp.Optional[tp.Any] = None,
                 pool_slot_base: int = 0,
                 keep_logits: bool = False):
        import jax
        import jax.numpy as jnp
        from ..models.decoding import init_cache

        if keep_logits and cache_layout != "paged":
            raise ValueError("keep_logits taps the paged executables only; "
                             f"got cache_layout={cache_layout!r}")
        self.keep_logits = bool(keep_logits)
        self.tapped: tp.Dict[str, tp.Any] = {}
        self._model = model
        self._params = params
        self._cfg = model.config
        self.slots = slots
        self.max_seq_len = min(max_seq_len or self._cfg.max_seq_len,
                               self._cfg.max_seq_len)
        if cache_layout not in ("dense", "paged", "ssd"):
            raise ValueError(f"cache_layout must be 'dense', 'paged' or "
                             f"'ssd', got {cache_layout!r}")
        from ..models.transformer import mixer_pattern
        pattern = mixer_pattern(self._cfg)
        if "ssd" in pattern and cache_layout != "ssd":
            raise ValueError(
                f"the model's mixer pattern {pattern} contains SSD "
                f"layers, whose decode state is a resident per-slot "
                f"tensor, not positioned K/V rows — serve it with "
                f"cache_layout='ssd' (got {cache_layout!r})")
        if cache_layout == "ssd":
            if "ssd" not in pattern:
                raise ValueError(
                    "cache_layout='ssd' needs at least one SSD layer in "
                    f"the model's mixer pattern, got {pattern}")
            if spec_k is not None:
                raise ValueError(
                    "speculative decoding is not supported with SSD "
                    "layers: the recurrence state is cumulative, so "
                    "rejected draft tokens cannot be rolled back for "
                    "free the way position-indexed K/V rows can")
        # A pure-SSD stack has NO per-slot tensor that grows with
        # context, so sessions may stream past max_seq_len (which then
        # only sizes prefill chunking); one attention layer's dense
        # slab reinstates the ceiling.
        self.unbounded = (cache_layout == "ssd"
                          and "attention" not in pattern)
        if kv_dtype not in ("model", "int8"):
            raise ValueError(f"kv_dtype must be 'model' or 'int8', "
                             f"got {kv_dtype!r}")
        if kv_dtype == "int8" and cache_layout != "paged":
            raise ValueError("kv_dtype='int8' requires the paged cache "
                             "layout (scales live beside the block pool)")
        # (a latent pool refuses int8 where its spec is made:
        # ops.paged_attention.cfg_pool_spec)
        self._latent = self._cfg.attn_kind == "mla"
        # grouped attention by layer kind (models/gqa.py): full layers
        # page through the pool, window layers keep a ring a slot
        from ..models.gqa import has_window
        self._grouped = self._cfg.attn_kind == "gqa"
        self._windowed = has_window(self._cfg)
        # one mixer a layer (`layer_pattern`): its Mamba-2 layers keep a
        # state and a conv tail a slot beside the pool, the third kind
        # of per-slot state (ops.paged_attention.layer_pool_specs)
        self._recurrent = "M" in self._cfg.layer_pattern
        if self._cfg.layer_pattern and cache_layout != "paged":
            raise ValueError(
                f"a layer_pattern's layers keep their state a slot beside "
                f"the block pool (attention layers page, Mamba-2 layers "
                f"hold one entry a slot): serve it with "
                f"cache_layout='paged' (got {cache_layout!r})")
        if self._recurrent and spec_k is not None:
            raise ValueError(
                "speculative decoding is not supported under recurrent "
                "layers: a verify step would have to roll a state back, "
                "and a state is cumulative (spec_k=None)")
        if kernel not in ("auto", "gather", "fused"):
            raise ValueError(f"kernel must be 'auto', 'gather' or "
                             f"'fused', got {kernel!r}")
        if kernel == "fused" and cache_layout != "paged":
            raise ValueError("kernel='fused' is the paged pool read "
                             "(ops/paged_decode.py); the dense layout "
                             "has no block tables to iterate")
        if kernel == "fused":
            # an explicit 'fused' must actually RUN the kernel: where
            # it cannot (no pallas, GPU backend), the silent gather
            # fallback would let every fused gate/label false-pass
            from ..ops.paged_decode import fused_kernel_unsupported_reason
            reason = fused_kernel_unsupported_reason(self._cfg, block_size)
            if reason is not None:
                raise ValueError(f"kernel='fused' cannot run here: "
                                 f"{reason}; use kernel='gather' (or "
                                 f"'auto')")
        if kernel == "auto":
            if cache_layout == "paged":
                from ..ops.paged_decode import default_kernel
                kernel = default_kernel(self._cfg, block_size)
            else:
                kernel = "gather"
        self.kernel = kernel
        self.cache_layout = cache_layout
        self.kv_dtype = kv_dtype
        self.block_size = int(block_size)
        if cache_layout == "paged" and chunk is None:
            # paged engines always prefill in chunks: chunked prefill
            # attends earlier (possibly shared) blocks through the
            # table and can resume at any prefix-matched offset.
            chunk = self.block_size
        self.temperature = float(temperature)
        if self.temperature > 0.0 and rng is None:
            raise ValueError("DecodeEngine(temperature>0) samples and needs "
                             "an explicit `rng` key (greedy needs none).")
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.pad_token = int(pad_token)
        self.min_bucket = int(min_bucket)
        self.chunk = int(chunk) if chunk is not None else None
        if self.chunk is not None:
            if self.chunk < 1 or self.max_seq_len % self.chunk != 0:
                raise ValueError(
                    f"chunk must divide max_seq_len "
                    f"({self.max_seq_len}), got {self.chunk}: a slice "
                    f"start past max_seq_len - chunk would clamp its "
                    f"dynamic-update-slice and shift the K/V writes")
            self.tail_bucket = int(tail_bucket if tail_bucket is not None
                                   else min(self.min_bucket, self.chunk))
            if not 1 <= self.tail_bucket <= self.chunk:
                raise ValueError(f"tail_bucket must be in [1, chunk], got "
                                 f"{self.tail_bucket} (chunk {self.chunk})")
        else:
            self.tail_bucket = None
        self.spec_k = int(spec_k) if spec_k is not None else None
        if self.spec_k is not None and self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        self.cache_scope = str(cache_scope)
        self.allocator = SlotAllocator(slots)

        if tracer is None or compile_cache is None:
            from ..observability import get_telemetry
            telemetry = get_telemetry()
            if tracer is None and telemetry is not None:
                tracer = telemetry.tracer
            if compile_cache is None:
                compile_cache = CompileCache(
                    watchdog=telemetry.watchdog if telemetry else None,
                    tracer=tracer)
        self.tracer = tracer
        self.compile_cache = compile_cache

        # Device-side per-slot state. Inactive slots park at position
        # `max_seq_len`: their decode writes fall out of range and are
        # dropped (mode="drop" in the dense cache scatter; clamped into
        # the sentinel block in the paged layout), so a freed slot can
        # never corrupt a neighbour.
        if pool_slot_base < 0:
            raise ValueError(f"pool_slot_base must be >= 0, "
                             f"got {pool_slot_base}")
        if cache_layout != "paged" and (pool is not None
                                        or cache_box is not None
                                        or pool_slot_base):
            raise ValueError("pool / cache_box / pool_slot_base are "
                             "paged-layout sharing hooks; the dense "
                             "layout has no block pool to share")
        self._pool_base = int(pool_slot_base)
        self.ring = 0  # blocks of a window layer's ring a slot
        if cache_layout == "paged":
            from ..ops.paged_attention import (block_bytes, init_pool,
                                               state_bytes, token_bytes,
                                               window_bytes)
            from .paged import BlockPool, CacheBox
            if (self._windowed or self._recurrent) and (
                    pool is not None or cache_box is not None
                    or (self._recurrent and pool_slot_base)):
                raise ValueError(
                    "a model with window or recurrent layers keeps a ring "
                    "or a state a slot beside the pool, which a block list "
                    "does not hand over: no shared pool / cache_box / "
                    "pool_slot_base (disaggregated hand-off) for it")
            if pool is not None:
                if pool.block_size != self.block_size:
                    raise ValueError(
                        f"shared pool has block_size {pool.block_size}, "
                        f"engine wants {self.block_size}")
                if pool.max_seq_len != self.max_seq_len:
                    raise ValueError(
                        f"shared pool has max_seq_len {pool.max_seq_len}, "
                        f"engine wants {self.max_seq_len} — table widths "
                        f"would disagree")
                if self.spec_k and pool.spec_overshoot < self.spec_k:
                    raise ValueError(
                        f"shared pool reserves spec_overshoot="
                        f"{pool.spec_overshoot} < this engine's spec_k="
                        f"{self.spec_k}: verify writes would overrun "
                        f"reservations")
                if num_blocks is not None \
                        and int(num_blocks) != pool.num_blocks:
                    raise ValueError(
                        f"num_blocks={num_blocks} contradicts the shared "
                        f"pool's {pool.num_blocks}")
                self._pool = pool
                self.num_blocks = pool.num_blocks
            else:
                if cache_box is not None:
                    raise ValueError("cache_box sharing requires a shared "
                                     "pool (the box holds that pool's "
                                     "device blocks)")
                if num_blocks is None:
                    # worst case: every slot reserves its full budget
                    num_blocks = 1 + slots * (self.max_seq_len
                                              // self.block_size)
                self.num_blocks = int(num_blocks)
                self._pool = BlockPool(
                    num_blocks=self.num_blocks, block_size=self.block_size,
                    max_seq_len=self.max_seq_len,
                    spec_overshoot=self.spec_k or 0,
                    prefix_cache=prefix_cache,
                    # a ring holds the largest step's rows behind a window
                    window=self._cfg.window if self._windowed else 0,
                    step_rows=max(self.chunk, (self.spec_k or 0) + 1),
                    state_slots=slots if self._recurrent else 0)
            self.ring = self._pool.ring
            self._cache_box = cache_box if cache_box is not None \
                else CacheBox()
            if self._cache_box.value is None:
                self._cache_box.value = init_pool(
                    self._cfg, self.num_blocks, self.block_size,
                    self.kv_dtype, slots=slots, ring=self.ring)
            self._block_bytes = block_bytes(self._cfg, self.block_size,
                                            self.kv_dtype)
            # bytes a cached token costs the layers that grow | the
            # window layers; the rings' bytes, fixed
            self._token_bytes = token_bytes(self._cfg, self.kv_dtype)
            self._window_bytes = window_bytes(
                self._cfg, self.block_size, slots=slots, ring=self.ring)
            # one slot's recurrent entries over all layers: what a row
            # that advances reads, and writes again
            self._state_row_bytes = state_bytes(self._cfg, 0)
            self._table_host = np.zeros(
                (slots, self._pool.max_blocks), np.int32)
            self._table_dev = jnp.array(self._table_host)  # as `_table`
            self._table_dirty = False
        else:
            from .paged import CacheBox
            self.num_blocks = 0
            self._pool = None
            self._cache_box = CacheBox(
                init_cache(self._cfg, slots, self.max_seq_len))
        self._tokens = jnp.full((slots,), self.pad_token, jnp.int32)
        self._positions = jnp.full((slots,), self.max_seq_len, jnp.int32)
        self._active = jnp.zeros((slots,), bool)
        # Host snapshot of positions/liveness. Every transition that
        # moves a position (prefill, decode, verify, retire) is
        # host-driven, so the mirror stays exact without ever reading
        # the device arrays back — `slot_length()` used to cost one
        # device->host sync per call, S syncs per scheduler step.
        self._positions_host = np.full((slots,), self.max_seq_len, np.int64)
        self._active_host = np.zeros((slots,), bool)
        self._kv_walks: tp.Dict[int, tp.Any] = {}  # queries -> Walk
        # the paged steps of a model with expert layers also return the
        # layers' summed (assignments, experts hit), packed behind what
        # the host reads back anyway (a scanned stack cannot hand them out)
        self._moe_stats = (cache_layout == "paged"
                           and not self._cfg.scan_layers
                           and (self._cfg.n_routed > 0
                                or self._cfg.moe_experts > 0))

    # ------------------------------------------------------------------
    # compiled steps
    # ------------------------------------------------------------------
    def _key(self, *parts: tp.Any) -> tp.Tuple[tp.Any, ...]:
        """Compile-cache key for one of this engine's executables,
        prefixed with `cache_scope` so co-resident engines (a draft
        mirror) never collide in a shared cache or watchdog."""
        return ((self.cache_scope,) if self.cache_scope else ()) + parts

    @property
    def _cache(self):
        """The device cache pytree, read through the (possibly shared)
        CacheBox so co-resident engines over one pool always see each
        other's latest functional update."""
        return self._cache_box.value

    @_cache.setter
    def _cache(self, value) -> None:
        self._cache_box.value = value

    @property
    def pool(self):
        """This engine's BlockPool (None on the dense layout); shared
        with other engines when one was passed at construction."""
        return self._pool

    @property
    def cache_box(self):
        """The CacheBox holding the device cache pytree (share it with
        a second paged engine over the same `pool` for disaggregated
        prefill/decode handoff)."""
        return self._cache_box

    def pool_key(self, slot: int) -> int:
        """The BlockPool reservation key for an engine slot:
        `slot + pool_slot_base`. Engines sharing one pool keep disjoint
        key ranges so their slot ids never collide on a reservation."""
        return slot + self._pool_base

    def _sample(self, logits, key):
        """Next token from [S, V] logits (matches generate()'s rule)."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope("sample"):
            if self.temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, logits / self.temperature, axis=-1).astype(jnp.int32)

    def _row_slots(self, active):
        """What a paged step over every slot hands `paged_apply_step(
        slots=)`: row s is slot s, or -1 where the slot is parked (free,
        or mid-prefill: its ring is the slices' to write). None without
        window or recurrent layers (the program is the one it always
        was)."""
        import jax.numpy as jnp
        if not (self._windowed or self._recurrent):
            return None
        return jnp.where(active, jnp.arange(self.slots, dtype=jnp.int32), -1)

    def _moe_list(self) -> tp.Optional[tp.List]:
        """What a paged step hands `paged_apply_step(stats=)`: a list the
        expert layers append their counts to, or None (no expert layer:
        the program is the one it always was)."""
        return [] if self._moe_stats else None

    @staticmethod
    def _with_moe(read, stats: tp.Optional[tp.List]):
        """`read` (what the host reads back of a step, any int32 shape)
        flattened, with the layers' summed (assignments, experts hit)
        behind it: one transfer carries both."""
        import jax.numpy as jnp
        counts = jnp.stack([sum(s[0] for s in stats),
                            sum(s[1] for s in stats)]).astype(jnp.int32)
        return jnp.concatenate([read.reshape(-1).astype(jnp.int32), counts])

    def _tap(self, logits) -> tp.Tuple:
        """What `keep_logits` adds to a paged step's outputs: the
        logits it sampled from, or nothing."""
        import jax.numpy as jnp
        return (logits.astype(jnp.float32),) if self.keep_logits else ()

    # The per-slot state (last token, position, liveness) is advanced by
    # the compiled steps themselves, so the next step can be dispatched
    # before anything of this one has been read back.
    @staticmethod
    def _advanced(positions, active):
        """A decode step's positions: every live slot one further."""
        return positions + active.astype(positions.dtype)

    @staticmethod
    def _armed(state, slot, first, length, final):
        """A prefill slice's `(tokens, positions, active)`: where `final`
        (a traced bool: one executable serves every slice) row `slot`
        goes live at the prompt's `length` with its own `first` token."""
        import jax.numpy as jnp
        tokens, positions, active = state
        return (tokens.at[slot].set(jnp.where(final, first, tokens[slot])),
                positions.at[slot].set(
                    jnp.where(final, length, positions[slot])),
                active.at[slot].set(active[slot] | final))

    def _moe_span(self, parent: str, counts) -> None:
        """The empty `<parent>/moe` span that carries a step's counts."""
        with span(parent + SPAN_MOE, self.tracer, category="serve",
                  moe_assignments=int(counts[0]),
                  moe_experts_hit=int(counts[1])):
            pass

    def _table(self):
        """Device copy of the block tables, refreshed only when the host
        tables changed (admission / COW / retirement — never mid-decode,
        reservations are materialized up front)."""
        import jax.numpy as jnp
        if self._table_dirty:
            with span(SPAN_TABLE_UPLOAD, self.tracer, category="serve",
                      bytes=int(self._table_host.nbytes)):
                # a copy, never a view (the CPU backend would alias the
                # host array): a step in flight reads the table it was
                # dispatched with while the host edits the next one
                self._table_dev = jnp.array(self._table_host)
            self._table_dirty = False
        return self._table_dev

    def _layout_args(self) -> tp.Tuple:
        """Extra compiled-step inputs the layout needs (the block
        tables, right after the cache operand) — empty for dense."""
        return (self._table(),) if self.cache_layout == "paged" else ()

    def _build_decode(self) -> tp.Callable:
        import jax
        import jax.numpy as jnp
        from ..models.decoding import _apply_step
        model, cfg, pad = self._model, self._cfg, self.pad_token

        if self.cache_layout == "paged":
            from .paged import paged_apply_step

            def decode_paged(params, cache, table, tokens, positions,
                             active, key):
                # identical contract to the dense step; the table is
                # one more INPUT (contents never change the shape)
                stats = self._moe_list()
                logits, cache = paged_apply_step(
                    model, params, cfg, tokens[:, None],
                    positions[:, None], cache, table,
                    kernel=self.kernel, stats=stats,
                    slots=self._row_slots(active))
                nxt = self._sample(logits[:, -1], key)
                nxt = jnp.where(active, nxt, jnp.int32(pad))
                out = (nxt, cache, self._advanced(positions, active))
                if stats is not None:
                    out += (self._with_moe(nxt, stats),)
                return out + self._tap(logits[:, -1])

            return jax.jit(decode_paged, donate_argnums=self._donate)

        def decode(params, cache, tokens, positions, active, key):
            # tokens/positions/active: [S]; ONE executable for any mix
            # of live slots — liveness is data, not shape. `active`
            # doubles as the SSD state gate: an inactive slot (free, or
            # mid-chunked-prefill with accumulated state) must not have
            # its recurrence advanced by decode ticks it is not part of
            # (attention rows get the same protection from the parked
            # position's dropped writes).
            logits, cache = _apply_step(
                model, params, cfg, tokens[:, None], positions[:, None],
                cache, positions, state_mask=active)
            nxt = self._sample(logits[:, -1], key)
            return (jnp.where(active, nxt, jnp.int32(pad)), cache,
                    self._advanced(positions, active))

        return jax.jit(decode, donate_argnums=self._donate)

    def _build_prefill(self, bucket: int) -> tp.Callable:
        import jax
        import jax.numpy as jnp
        from ..models.decoding import _apply_step, init_cache
        model, cfg = self._model, self._cfg

        def prefill(params, cache, prompt, length, slot, key):
            # prompt: [1, bucket] right-padded; length/slot: scalars.
            # Pad positions >= length are never attended (causal mask)
            # and their K/V rows are overwritten by decode writes before
            # any query can reach them, so right-padding is exact. SSD
            # layers have no positions to hide behind — the token mask
            # keeps pad tokens out of the accumulated state instead.
            mini = init_cache(cfg, 1, bucket)
            positions = jnp.arange(bucket, dtype=jnp.int32)[None]
            mask = (jnp.arange(bucket, dtype=jnp.int32) < length)[None]
            logits, mini = _apply_step(model, params, cfg, prompt,
                                       positions, mini, jnp.int32(0),
                                       token_mask=mask)
            last = jax.lax.dynamic_index_in_dim(logits[0], length - 1,
                                                axis=0, keepdims=True)
            first = self._sample(last, key)[0]

            def merge(big, small):
                start = (0,) * (big.ndim - 4) + (slot, 0, 0, 0)
                return jax.lax.dynamic_update_slice(
                    big, small.astype(big.dtype), start)

            cache = jax.tree_util.tree_map(merge, cache, mini)
            return first, cache

        return jax.jit(prefill, donate_argnums=self._donate)

    def _build_prefill_chunk(self, size: int) -> tp.Callable:
        import jax
        import jax.numpy as jnp
        from ..models.decoding import _apply_step
        model, cfg = self._model, self._cfg

        if self.cache_layout == "paged":
            from .paged import paged_apply_step

            def chunk_paged(params, cache, table, tokens, start, used,
                            slot, key, state, final):
                # tokens: [1, size] at absolute positions start.. —
                # attention reaches the slot's EARLIER blocks (its own
                # previous chunks AND any prefix-shared blocks) through
                # its table row, so chunked prefill and prefix sharing
                # compose with zero copies. Pad rows beyond `used`
                # write at higher positions — past every causal horizon
                # until overwritten, the same right-padding proof.
                row = jax.lax.dynamic_slice(
                    table, (slot, 0), (1, table.shape[1]))
                positions = (start + jnp.arange(size, dtype=jnp.int32))[None]
                stats = self._moe_list()
                # a recurrent layer carries the slot's state across the
                # slices: pads stay out of it, a first slice starts it
                # from zeros (whatever the slot's last occupant left)
                carried = ({"used": used[None], "fresh": (start == 0)[None]}
                           if self._recurrent else {})
                logits, cache = paged_apply_step(
                    model, params, cfg, tokens, positions, cache, row,
                    kernel=self.kernel, stats=stats,
                    slots=(slot[None] if self._windowed or self._recurrent
                           else None), **carried)
                last = jax.lax.dynamic_index_in_dim(
                    logits[0], used - 1, axis=0, keepdims=True)
                first = self._sample(last, key)[0]
                out = (first, cache,
                       self._armed(state, slot, first, start + used, final))
                if stats is not None:
                    out += (self._with_moe(first, stats),)
                return out + self._tap(last)

            return jax.jit(chunk_paged, donate_argnums=self._donate)

        def chunk_step(params, cache, tokens, start, used, slot, key,
                       state, final):
            # tokens: [1, size] right-padded slice of the prompt whose
            # real tokens sit at absolute positions start..start+used-1.
            # Unlike the bucketed prefill (fresh mini cache), a chunk
            # must attend the slot's EARLIER chunks, so the slot's rows
            # are sliced out of the big cache, advanced, and merged
            # back. Pad rows beyond `used` are past every causal
            # horizon until decode overwrites them — the same
            # right-padding proof as the bucketed path.
            def take(big):
                starts = (0,) * (big.ndim - 4) + (slot, 0, 0, 0)
                sizes = big.shape[:-4] + (1,) + big.shape[-3:]
                return jax.lax.dynamic_slice(big, starts, sizes)

            def merge(big, small):
                starts = (0,) * (big.ndim - 4) + (slot, 0, 0, 0)
                return jax.lax.dynamic_update_slice(
                    big, small.astype(big.dtype), starts)

            mini = jax.tree_util.tree_map(take, cache)
            # A chunk at start == 0 begins a FRESH request: whatever SSD
            # state the slot's previous occupant accumulated is zeroed
            # here, inside the same executable (a scalar-input select —
            # no extra reset shape to compile). Later chunks chain the
            # carried state exactly. Attention leaves need no reset:
            # their stale rows sit past every causal horizon.
            mini = _zero_ssd_leaves(mini, start == 0)
            positions = (start + jnp.arange(size, dtype=jnp.int32))[None]
            mask = (jnp.arange(size, dtype=jnp.int32) < used)[None]
            logits, mini = _apply_step(model, params, cfg, tokens,
                                       positions, mini, start,
                                       token_mask=mask)
            last = jax.lax.dynamic_index_in_dim(logits[0], used - 1,
                                                axis=0, keepdims=True)
            first = self._sample(last, key)[0]
            cache = jax.tree_util.tree_map(merge, cache, mini)
            return first, cache, self._armed(state, slot, first,
                                             start + used, final)

        return jax.jit(chunk_step, donate_argnums=self._donate)

    def _build_verify(self, k: int) -> tp.Callable:
        import jax
        import jax.numpy as jnp
        from ..models.decoding import _apply_step, speculative_acceptance
        model, cfg, pad = self._model, self._cfg, self.pad_token

        if self.cache_layout == "paged":
            from .paged import paged_apply_step

            def verify_paged(params, cache, table, tokens, drafts,
                             positions, active, key):
                # same [S, k+1] contract as the dense verify; rollback
                # is free on the paged layout too — stale draft rows
                # sit at positions past accepted+1, beyond every causal
                # horizon until overwritten, whatever block they landed
                # in (overshoot past the reservation clamps into the
                # sentinel).
                toks = jnp.concatenate([tokens[:, None], drafts], axis=1)
                pos = positions[:, None] \
                    + jnp.arange(k + 1, dtype=jnp.int32)[None]
                stats = self._moe_list()
                logits, cache = paged_apply_step(
                    model, params, cfg, toks, pos, cache, table,
                    kernel=self.kernel, stats=stats,
                    slots=self._row_slots(active))
                out, accepted = speculative_acceptance(
                    drafts, logits, temperature=self.temperature,
                    rng=key if self.temperature > 0.0 else None,
                    pad_token=pad)
                out = jnp.where(active[:, None], out, jnp.int32(pad))
                accepted = jnp.where(active, accepted, 0)
                last = jnp.take_along_axis(out, accepted[:, None],
                                           axis=1)[:, 0]
                new_tokens = jnp.where(active, last, jnp.int32(pad))
                new_positions = jnp.where(active, positions + accepted + 1,
                                          positions)
                if stats is not None:  # the counts ride behind `accepted`
                    accepted = self._with_moe(accepted, stats)
                return out, accepted, new_tokens, new_positions, cache

            return jax.jit(verify_paged, donate_argnums=self._donate)

        def verify(params, cache, tokens, drafts, positions, active, key):
            # tokens/positions/active: [S]; drafts: [S, k]. ONE forward
            # scores the last emitted token plus all k drafts per slot
            # — k+1 cache rows written at each slot's own offset via
            # the same per-row [B] cache-index path decode uses.
            toks = jnp.concatenate([tokens[:, None], drafts], axis=1)
            pos = positions[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None]
            logits, cache = _apply_step(model, params, cfg, toks, pos,
                                        cache, positions)
            out, accepted = speculative_acceptance(
                drafts, logits, temperature=self.temperature,
                rng=key if self.temperature > 0.0 else None, pad_token=pad)
            out = jnp.where(active[:, None], out, jnp.int32(pad))
            accepted = jnp.where(active, accepted, 0)
            # Next-step state, computed on-device in the same call:
            # the last emitted token (index `accepted` — the bonus or
            # resampled token) and the position right after it. Rows
            # past it hold stale draft K/V — beyond every causal
            # horizon until overwritten, the rollback-for-free
            # property of position-indexed caches.
            last = jnp.take_along_axis(out, accepted[:, None],
                                       axis=1)[:, 0]
            new_tokens = jnp.where(active, last, jnp.int32(pad))
            new_positions = jnp.where(active, positions + accepted + 1,
                                      positions)
            return out, accepted, new_tokens, new_positions, cache

        return jax.jit(verify, donate_argnums=self._donate)

    def _build_copy(self) -> tp.Callable:
        """The COW fork executable: duplicate pool block `src` onto
        `dst` across every layer and leaf (int8 payloads + scales).
        Scalars are inputs, so one compiled copy serves every fork."""
        import jax
        from .paged import copy_block_fn
        copy = copy_block_fn(self._cfg, self.kv_dtype)
        return jax.jit(lambda cache, src, dst: copy(cache, src, dst),
                       donate_argnums=(0,))

    def _next_key(self):
        import jax
        if self.temperature <= 0.0:
            return self._rng  # greedy: the key is never consulted
        self._rng, sub = jax.random.split(self._rng)
        return sub

    # ------------------------------------------------------------------
    # public surface
    # ------------------------------------------------------------------
    def bucket_for(self, prompt_len: int) -> int:
        """The compiled prefill bucket a prompt of this length lands in."""
        return bucket_length(prompt_len, minimum=self.min_bucket,
                             maximum=self.max_seq_len)

    def warmup(self, prompt_lengths: tp.Iterable[int] = ()) -> None:
        """Pre-compile every executable live traffic can touch: the
        decode step, the chunked-prefill pair (chunk + tail) or the
        power-of-two buckets covering `prompt_lengths`, and — when
        `spec_k` is set — the `[S, k+1]` speculative verify step. Runs
        each once on scratch inputs; slot state is restored to empty
        afterwards.
        """
        import jax.numpy as jnp
        warmed = []
        layout = self._layout_args()
        if self.chunk is not None:
            # chunked mode: the whole prefill lifetime is two shapes.
            # In the paged layout the scratch run's tables are all
            # sentinel, so warm-up K/V lands in the sentinel block and
            # can never touch a real one.
            for size in sorted({self.chunk, self.tail_bucket}):
                dummy = jnp.full((1, size), self.pad_token, jnp.int32)
                _, self._cache, *_ = self.compile_cache.warm(
                    self._key("prefill_chunk", size),
                    lambda: self._build_prefill_chunk(size),
                    self._params, self._cache, *layout, dummy,
                    jnp.int32(0), jnp.int32(1), jnp.int32(0),
                    self._next_key(), self._slot_state(), jnp.bool_(False))
                warmed.append(f"prefill_chunk/{size}")
        else:
            buckets = {self.min_bucket}
            buckets.update(self.bucket_for(n) for n in prompt_lengths)
            for bucket in sorted(buckets):
                dummy = jnp.full((1, bucket), self.pad_token, jnp.int32)
                _, self._cache = self.compile_cache.warm(
                    self._key("prefill", bucket),
                    lambda: self._build_prefill(bucket),
                    self._params, self._cache, dummy, jnp.int32(1),
                    jnp.int32(0), self._next_key())
                warmed.append(f"prefill/{bucket}")
        _, self._cache, *_ = self.compile_cache.warm(
            self._key("decode", self.slots), self._build_decode,
            self._params, self._cache, *layout, self._tokens,
            self._positions, self._active, self._next_key())
        warmed.append(f"decode/{self.slots}")
        if self.spec_k is not None:
            dummy_drafts = jnp.full((self.slots, self.spec_k),
                                    self.pad_token, jnp.int32)
            *_, self._cache = self.compile_cache.warm(
                self._key("verify", self.slots, self.spec_k),
                lambda: self._build_verify(self.spec_k),
                self._params, self._cache, *layout, self._tokens,
                dummy_drafts, self._positions, self._active,
                self._next_key())
            warmed.append(f"verify/{self.slots}/{self.spec_k}")
        if self.cache_layout == "paged":
            # sentinel -> sentinel: a no-op that compiles + warms the
            # COW fork copy so a prefix fork never traces mid-traffic
            self._cache = self.compile_cache.warm(
                self._key("copy_block"), self._build_copy,
                self._cache, jnp.int32(0), jnp.int32(0))
            warmed.append("copy_block")
        # the eager row writes of retire / preempt / hand-off lower on
        # their first use too: here, not in the middle of traffic
        self._park(0)
        # warm-up wrote scratch K/V at slot 0 position 0; a real prefill
        # overwrites it before that slot ever decodes, but reset the
        # host-visible state anyway so the engine starts pristine.
        self._tokens = jnp.full((self.slots,), self.pad_token, jnp.int32)
        self._positions = jnp.full((self.slots,), self.max_seq_len, jnp.int32)
        self._active = jnp.zeros((self.slots,), bool)
        self._positions_host = np.full((self.slots,), self.max_seq_len,
                                       np.int64)
        self._active_host = np.zeros((self.slots,), bool)
        # what imports, tracing and compiling left is collected NOW:
        # left alone, the interpreter's next full collection lands on a
        # live step a few hundred steps into traffic (80-110 ms there
        # beside a 13 ms step: PR 35's slow-step record, every run)
        gc.collect()
        logger.info("serve warm-up done: %d executables (%s)",
                    len(self.compile_cache), ", ".join(warmed))

    def acquire_slot(self, slot: tp.Optional[int] = None) -> tp.Optional[int]:
        """Claim a free slot (None when all are live); prefill into it.
        A specific `slot` can be requested (mirrored draft engines)."""
        return self.allocator.acquire(slot)

    def can_admit(self, prompt: np.ndarray, max_new_tokens: int) -> bool:
        """Whether the cache layout has room for this request RIGHT NOW
        (beyond a free slot, which the caller checks separately).

        Dense: always — the slot IS the reservation. Paged: the block
        pool must cover the request's whole budget net of its prefix-
        cache credit; a False keeps the request queued (head-of-line:
        admission stays FIFO), and the queue filling up turns into
        QueueFull at the submit door — the existing backpressure path.
        """
        if self._pool is None:
            return True
        return self._pool.can_admit(np.asarray(prompt, np.int32),
                                    max_new_tokens)

    def admit(self, slot: int, prompt: np.ndarray,
              max_new_tokens: int) -> int:
        """Reserve the request's cache and return the prefill start.

        Dense: a no-op returning 0 (prefill covers the whole prompt).
        Paged: reserves every block the request can touch (prompt +
        output budget + verify overshoot) so decode can never OOM the
        pool; walks the prefix index, bumping refcounts on shared full
        blocks and device-copying the COW fork for a partially shared
        block; fills the slot's table row. Returns the number of
        prompt tokens served from the cache — chunked prefill resumes
        there (always < len(prompt): the last token re-prefills so the
        first-token logits come from a real forward). Raises
        PoolExhausted (atomically — no state changed) when the pool
        lacks headroom or the `serve.pool` fault site injects a
        failure.
        """
        if self._pool is None:
            return 0
        import jax.numpy as jnp
        if slot not in self.allocator.live:
            raise ValueError(f"slot {slot} was not acquired")
        prompt = np.asarray(prompt, np.int32)
        plan = self._pool.plan(prompt, max_new_tokens)
        row, start, cow = self._pool.commit(plan, self.pool_key(slot))
        self._table_host[slot] = row
        self._table_dirty = True
        if cow is not None:
            src, dst = cow
            fn = self.compile_cache.get(self._key("copy_block"),
                                        self._build_copy)
            self._cache = fn(self._cache, jnp.int32(src), jnp.int32(dst))
        if self.tracer is not None:
            self.tracer.instant(SPAN_ADMIT, category="serve", slot=slot,
                                matched=start, prompt=int(prompt.size),
                                cow=cow is not None)
        return start

    def executables(self) -> tp.Dict[str, tp.Callable]:
        """The audit registry: every compiled executable this engine
        has built (decode / per-bucket prefill / verify / copy), keyed
        by compile-cache name. `compile_cache.signatures[name]` holds
        each one's recorded abstract call signatures — what the FT103
        trace auditor checks for retrace risk, and what `warmup()`
        plus a clean `compile_cache.recompiles()` proves covered.
        This hook's pattern extends across the repo as the numerics
        audit registries (`parallel.audit` / `models.audit` /
        `datapipe.audit`, the FT2xx sweep): `models.audit` re-spells
        this engine's verify and paged-attention contracts as traceable
        programs, since compiled closures here carry no example args to
        re-trace from."""
        return self.compile_cache.executables()

    def pool_stats(self) -> tp.Optional[tp.Dict[str, float]]:
        """Block-pool occupancy/prefix counters plus bytes-per-token
        (None on the dense layout). `kv_bytes_per_token` is the pool
        bytes actually reserved per live token — the number the paged
        layout exists to shrink."""
        if self._pool is None:
            return None
        stats = self._pool.stats()
        per_block = self._block_bytes
        live_tokens = int(sum(self._positions_host[self._active_host]))
        # `window_bytes`: the window layers' rings, fixed whatever the
        # contexts (0 without window layers, which also share no prefix:
        # `BlockPool`); capacity, in_use and peak_in_use stay the pool
        # that grows, the full-attention layers' blocks. A live slot
        # holds its rings beside its blocks.
        stats["window_bytes"] = self._window_bytes
        # `state_bytes`: the recurrent layers' entries, fixed likewise
        # and in none of the block counts; `kv_bytes_per_token` stays
        # the attention layers'
        stats["state_bytes"] = (1 + self.slots) * self._state_row_bytes
        held = (stats["in_use"] * per_block + int(self._active_host.sum())
                * (self._window_bytes // (1 + self.slots)))
        stats["kv_bytes_per_token"] = (
            held / live_tokens if live_tokens else 0.0)
        return stats

    def state_bytes_per_slot(self) -> int:
        """Decode-state bytes one slot of THIS engine reserves at its
        max_seq_len (see module-level `state_bytes_per_slot`)."""
        return state_bytes_per_slot(
            self._cfg, self.max_seq_len, self.cache_layout,
            kv_dtype=self.kv_dtype, block_size=self.block_size,
            ring=self.ring)

    def cache_bytes(self) -> int:
        """Total HBM bytes this engine's KV cache occupies (the fixed
        budget the paged-vs-dense capacity comparison holds constant)."""
        if self._pool is not None:
            from ..ops.paged_attention import pool_bytes
            return pool_bytes(self._cfg, self.num_blocks, self.block_size,
                              self.kv_dtype, slots=self.slots, ring=self.ring)
        import jax
        return int(sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(self._cache)))

    def prefill(self, slot: int, prompt: np.ndarray) -> int:
        """Run `prompt` (1-D int tokens) into `slot`; returns the first
        generated token. The slot must have been `acquire()`d."""
        import jax.numpy as jnp
        prompt = np.asarray(prompt)
        if self.cache_layout == "paged":
            raise ValueError(
                "paged engines prefill in chunks (chunk is always set): "
                "use admit() + prefill_chunk() — the monolithic bucketed "
                "prefill writes through a dense mini-cache merge that "
                "has no meaning for a block pool")
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(f"prompt must be 1-D and non-empty, "
                             f"got shape {prompt.shape}")
        if slot not in self.allocator.live:
            raise ValueError(f"slot {slot} was not acquired")
        length = int(prompt.size)
        bucket = self.bucket_for(length)
        padded = np.full((1, bucket), self.pad_token, np.int32)
        padded[0, :length] = prompt
        fn = self.compile_cache.get(
            self._key("prefill", bucket),
            lambda: self._build_prefill(bucket))
        with span(SPAN_PREFILL, self.tracer, category="serve", slot=slot,
                  bucket=bucket, length=length):
            first, self._cache = fn(self._params, self._cache,
                                    jnp.asarray(padded), jnp.int32(length),
                                    jnp.int32(slot), self._next_key())
            first = int(first)
        self._tokens = self._tokens.at[slot].set(first)
        self._positions = self._positions.at[slot].set(length)
        self._active = self._active.at[slot].set(True)
        self._positions_host[slot] = length
        self._active_host[slot] = True
        return first

    def prefill_chunk(self, slot: int, prompt: np.ndarray, start: int,
                      uid: tp.Optional[int] = None,
                      step: tp.Optional[int] = None
                      ) -> tp.Tuple[int, tp.Optional[int]]:
        """Advance `slot`'s prefill by ONE fixed-size slice and wait for
        it where it is the last: `dispatch_prefill_chunk()` followed by
        `collect()`. Returns `(next_start, first_token)`; `first_token`
        is None until the final slice."""
        start, handle = self.dispatch_prefill_chunk(slot, prompt, start,
                                                    uid=uid, step=step)
        return start, None if handle is None else int(self.collect(handle)[0])

    def dispatch_prefill_chunk(self, slot: int, prompt: np.ndarray,
                               start: int, uid: tp.Optional[int] = None,
                               step: tp.Optional[int] = None
                               ) -> tp.Tuple[int, tp.Optional[StepHandle]]:
        """Enqueue ONE fixed-size slice of `slot`'s prefill; waits for
        nothing.

        Processes `prompt[start : start + size]` where size is `chunk`,
        or `tail_bucket` when the remainder fits it — so the compiled
        prefill set in chunked mode is exactly those two shapes.
        Returns `(next_start, handle)`; `handle` is None until the
        final slice, whose executable itself puts the slot live on the
        device (its first token, the prompt's length, active) so a
        decode step dispatched next already carries the row;
        `collect(handle)[0]` is that first token. The
        scheduler interleaves these ticks with decode steps, bounding
        the stall a long prompt can impose on live slots to one
        slice's compute. `uid` (the scheduler's request id) and `step`
        (its step number) only ride on the span, so a request's slices
        can be followed in a trace and a read-back paired with the
        dispatch it reads.
        """
        import jax.numpy as jnp
        if self.chunk is None:
            raise ValueError("engine was built without chunk=...; use "
                             "prefill() for monolithic bucketed prefill")
        prompt = np.asarray(prompt)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(f"prompt must be 1-D and non-empty, "
                             f"got shape {prompt.shape}")
        if slot not in self.allocator.live:
            raise ValueError(f"slot {slot} was not acquired")
        length = int(prompt.size)
        if length > self.max_seq_len and not self.unbounded:
            raise ValueError(f"prompt length {length} exceeds "
                             f"max_seq_len {self.max_seq_len}")
        if not 0 <= start < length:
            raise ValueError(f"chunk start {start} outside prompt "
                             f"[0, {length})")
        remaining = length - start
        size = self.tail_bucket if remaining <= self.tail_bucket \
            else self.chunk
        used = min(remaining, size)
        final = start + used >= length
        padded = np.full((1, size), self.pad_token, np.int32)
        padded[0, :used] = prompt[start:start + used]
        fn = self.compile_cache.get(
            self._key("prefill_chunk", size),
            lambda: self._build_prefill_chunk(size))
        stats = {} if uid is None else {"uid": uid}
        stats.update(_step_stat(step))
        stats.update(self._kv_read_stats(size, [start]))
        stats.update(self._state_stats(1))
        with span(SPAN_PREFILL_CHUNK, self.tracer, category="serve",
                  slot=slot, size=size, offset=start, length=length,
                  final=final, **stats):
            first, self._cache, state, *packed = fn(
                self._params, self._cache, *self._layout_args(),
                jnp.asarray(padded), jnp.int32(start), jnp.int32(used),
                jnp.int32(slot), self._next_key(), self._slot_state(),
                jnp.bool_(final))
            if not final:
                # nothing of this slice is read back, its counts neither
                return start + used, None
            self._tokens, self._positions, self._active = state
            self._positions_host[slot] = length
            self._active_host[slot] = True
            if self._pool is not None:
                # prompt fully written (in device order: whatever is
                # enqueued after this slice sees it): index its full
                # blocks so later admissions share them
                self._pool.on_live(self.pool_key(slot))
        tap = packed.pop() if self.keep_logits else None
        return start + used, StepHandle(SPAN_PREFILL_CHUNK,
                                        packed[0] if packed else first, tap,
                                        step)

    def collect(self, handle: StepHandle) -> np.ndarray:
        """Wait for a dispatched step and read it back: a decode step's
        [S] tokens (pad_token on inactive slots), a final slice's first
        token as [1]. The step's logits, where kept, become
        `tapped[...]` now: `tapped` holds the logits of the tokens most
        recently read, not of a step still in flight."""
        with span(handle.span + SPAN_READBACK, self.tracer,
                  category="serve", **_step_stat(handle.step)):
            read = np.asarray(handle.read).reshape(-1)
        if self._moe_stats:
            self._moe_span(handle.span, read[-2:])
            read = read[:-2]
        if handle.tap is not None:
            self.tapped[handle.span.rpartition("/")[2]] = handle.tap
        return read

    def _kv_read_stats(self, queries: int, bases) -> tp.Dict[str, int]:
        """Span stats of one paged read of `queries` rows per slot from
        first positions `bases` (host mirrors; no device work). When the
        read is the fused kernel's: `kv_blocks`, the pool blocks it
        attends in one layer, and `kv_steps`, the compute steps it runs
        for them — their ratio is how many blocks a step carries
        (ops/paged_decode.walk_counts; a grouped pool's 'fused' is the
        walk of its full-attention layers, so the layer counted is one
        of those, by `grouped_call_walk`). For a latent pool, whichever
        read serves it: `kv_bytes`, the bytes as stored, over all
        layers, of the latent rows the live slots' queries attend
        (parked slots sit at max_seq_len). For a grouped pool likewise,
        by layer kind: a full-attention layer counts a slot's live
        context, a window layer the rows its queries see of it
        (`min(context, window - 1 + queries)`), and `kv_bytes_window`
        is the window layers' part."""
        if self._pool is None:
            return {}
        stats = {}
        if self._latent or self._grouped:
            bases = np.asarray(bases)
            rows = bases[bases < self.max_seq_len] + queries
            grows, ring = self._token_bytes
            stats["kv_bytes"] = int(rows.sum()) * grows
            if self._windowed:
                seen = np.minimum(rows, self._cfg.window - 1 + queries)
                stats["kv_bytes_window"] = int(seen.sum()) * ring
                stats["kv_bytes"] += stats["kv_bytes_window"]
        if self.kernel != "fused":
            return stats
        from ..ops.paged_decode import (call_walk, grouped_call_walk,
                                        latent_call_walk, walk_counts)
        walk = self._kv_walks.get(queries)
        if walk is None:
            cfg, entries = self._cfg, self._pool.max_blocks
            if self._latent:
                import jax

                from ..ops.paged_attention import cfg_pool_spec
                spec = cfg_pool_spec(cfg, 1, self.block_size, self.kv_dtype)
                walk = latent_call_walk(
                    queries, cfg.num_heads,
                    {name: jax.ShapeDtypeStruct(*leaf)
                     for name, leaf in spec.items()}, entries=entries)
            elif self._grouped:
                from ..models.gqa import layer_kinds
                walk = grouped_call_walk(
                    cfg, next(kind for kind in layer_kinds(cfg)
                              if not kind.window),  # those layers walk
                    queries, block_size=self.block_size, entries=entries)
            else:
                walk = call_walk(
                    queries, cfg.num_heads, cfg.head_dim,
                    block_size=self.block_size, entries=entries,
                    quantized=self.kv_dtype == "int8", dtype=cfg.dtype)
            self._kv_walks[queries] = walk
        stats["kv_blocks"], stats["kv_steps"] = walk_counts(
            bases, queries, walk, self.block_size, self._pool.max_blocks)
        return stats

    def _state_stats(self, rows: int) -> tp.Dict[str, int]:
        """Span stat of a step that advances `rows` sequences under
        recurrent layers: `ssm_state_bytes`, the bytes of state and conv
        tail, all layers, that those rows read and write again (host
        arithmetic on the slot mirror; parked rows' sentinel traffic is
        not work). Nothing without such layers."""
        if not self._recurrent:
            return {}
        return {"ssm_state_bytes": 2 * rows * self._state_row_bytes}

    def decode(self, step: tp.Optional[int] = None) -> np.ndarray:
        """One [S, 1] decode step over every slot, waited for:
        `dispatch_decode()` followed by `collect()`. Returns the [S]
        next tokens (pad_token on inactive slots)."""
        return self.collect(self.dispatch_decode(step=step))

    def dispatch_decode(self, step: tp.Optional[int] = None) -> StepHandle:
        """Enqueue one [S, 1] decode step over every slot; waits for
        nothing. Always the same compiled executable, whatever the live
        mix. The step feeds each live slot its own token back and
        advances its position on the device, so the next step (or the
        next slice) can be dispatched before `collect(handle)` reads
        this one's tokens. `step` (the scheduler's step number) rides
        on the spans and the handle, so the read-back says which
        dispatch it read."""
        fn = self.compile_cache.get(self._key("decode", self.slots),
                                    self._build_decode)
        numbered = _step_stat(step)
        with span(SPAN_DECODE, self.tracer, category="serve",
                  live=self.allocator.live_count,
                  running=int(self._active_host.sum()), **numbered,
                  **self._kv_read_stats(1, self._positions_host),
                  **self._state_stats(int(self._active_host.sum()))):
            layout, key = self._layout_args(), self._next_key()
            with span(SPAN_DECODE + SPAN_DISPATCH, self.tracer,
                      category="serve", **numbered):
                self._tokens, self._cache, self._positions, *packed = fn(
                    self._params, self._cache, *layout, self._tokens,
                    self._positions, self._active, key)
            self._positions_host += self._active_host
        tap = packed.pop() if self.keep_logits else None
        return StepHandle(SPAN_DECODE, packed[0] if packed else self._tokens,
                          tap, step)

    def decode_speculative(self, drafts: np.ndarray,
                           step: tp.Optional[int] = None
                           ) -> tp.Tuple[np.ndarray, np.ndarray]:
        """One `[S, k+1]` verify step over every slot against `drafts`
        ([S, k] proposed tokens; inactive rows ignored).

        Returns `(out_tokens, accepted)`: out_tokens [S, k+1] holds
        each live slot's emitted tokens at indices 0..accepted[s]
        (accepted drafts + the bonus/resampled token, `pad_token`
        beyond — and everywhere on inactive rows); accepted [S] counts
        kept drafts. Greedy engines emit exactly `generate()`'s
        tokens; see `models.decoding.speculative_acceptance`. Rollback
        after rejection is free: the step advances each slot's
        position by accepted+1, and the stale draft K/V rows beyond it
        are past every causal horizon until overwritten. `step` (the
        scheduler's step number) rides on `serve/verify` and its
        read-back.
        """
        import jax.numpy as jnp
        if self.cache_layout == "ssd":
            raise ValueError(
                "speculative decoding is not supported on the SSD "
                "layout: the recurrence state is cumulative, so "
                "rejected drafts cannot be rolled back for free")
        drafts = np.asarray(drafts, np.int32)
        if drafts.ndim != 2 or drafts.shape[0] != self.slots \
                or drafts.shape[1] < 1:
            raise ValueError(f"drafts must be [S={self.slots}, k>=1], "
                             f"got {drafts.shape}")
        k = int(drafts.shape[1])
        fn = self.compile_cache.get(self._key("verify", self.slots, k),
                                    lambda: self._build_verify(k))
        numbered = _step_stat(step)
        with span(SPAN_VERIFY, self.tracer, category="serve", k=k,
                  live=self.allocator.live_count,
                  running=int(self._active_host.sum()), **numbered,
                  **self._kv_read_stats(k + 1, self._positions_host)):
            layout, key = self._layout_args(), self._next_key()
            with span(SPAN_VERIFY + SPAN_DISPATCH, self.tracer,
                      category="serve", **numbered):
                (out, accepted, self._tokens, self._positions,
                 self._cache) = fn(
                    self._params, self._cache, *layout, self._tokens,
                    jnp.asarray(drafts), self._positions, self._active, key)
            with span(SPAN_VERIFY + SPAN_READBACK, self.tracer,
                      category="serve", **numbered):
                out_np = np.asarray(out)
                accepted_np = np.asarray(accepted)
            if self._moe_stats:
                self._moe_span(SPAN_VERIFY, accepted_np[self.slots:])
                accepted_np = accepted_np[:self.slots]
        self._positions_host += np.where(self._active_host,
                                         accepted_np.astype(np.int64) + 1, 0)
        return out_np, accepted_np

    def set_slot_state(self, slot: int, last_token: int,
                       position: int) -> None:
        """Overwrite a live slot's (last token, position) pair.

        This IS speculative rollback/resync for a mirrored engine: a
        draft engine that ran ahead k tokens resets to the verified
        position + bonus token here, and the stale K/V rows beyond
        `position` need no cleanup (beyond every causal horizon until
        overwritten). Also the test hook for forcing cache states.
        """
        if slot not in self.allocator.live:
            raise ValueError(f"slot {slot} is not live")
        if not (0 <= position <= self.max_seq_len or
                (self.unbounded and position >= 0)):
            raise ValueError(f"position {position} outside "
                             f"[0, {self.max_seq_len}]")
        self._tokens = self._tokens.at[slot].set(int(last_token))
        self._positions = self._positions.at[slot].set(int(position))
        self._positions_host[slot] = int(position)

    def _slot_state(self) -> tp.Tuple:
        """The device's `(tokens, positions, active)`, as the prefill
        slices take and return them."""
        return self._tokens, self._positions, self._active

    def _park(self, slot: int) -> None:
        """Deactivate row `slot` on the device and in the host mirrors:
        inactive, at position `max_seq_len`, where its writes fall out
        of range. Eager row writes of host constants, enqueued behind
        every step dispatched so far: nothing is read, nothing waits."""
        self._active = self._active.at[slot].set(False)
        self._positions = self._positions.at[slot].set(self.max_seq_len)
        self._tokens = self._tokens.at[slot].set(self.pad_token)
        self._positions_host[slot] = self.max_seq_len
        self._active_host[slot] = False

    def retire(self, slot: int) -> None:
        """Free `slot`: deactivate it and park its position out of range
        so pending decode writes drop instead of landing in the cache
        (dense mode="drop"; paged writes clamp into the sentinel). On
        the paged layout the slot's block refcounts drop too — blocks
        no table references return to the free list, except prompt
        blocks the prefix index still caches for future admissions."""
        self._park(slot)
        if self._pool is not None and self._pool.holds(self.pool_key(slot)):
            self._pool.release(self.pool_key(slot))
            self._table_host[slot] = 0
            self._table_dirty = True
        self.allocator.release(slot)

    def preempt_slot(self, slot: int) -> None:
        """Tear a live slot down mid-decode so a higher-priority request
        can take its capacity.

        Same deactivation as `retire()` — the parked position makes any
        pending write fall out of range — but the pool teardown goes
        through `BlockPool.evict_slot`, which counts the preemption and
        keeps the prompt's prefix-indexed blocks cached, so the
        preempted request's eventual re-admission re-matches its own
        prompt chain instead of re-prefilling it. Rollback needs no K/V
        cleanup: rows the request wrote sit beyond every causal horizon
        once the position parks, until some later reservation
        overwrites them (the speculative-rejection argument).
        """
        if slot not in self.allocator.live:
            raise ValueError(f"slot {slot} is not live")
        self._park(slot)
        if self._pool is not None and self._pool.holds(self.pool_key(slot)):
            self._pool.evict_slot(self.pool_key(slot))
            self._table_host[slot] = 0
            self._table_dirty = True
        self.allocator.release(slot)

    def release_for_handoff(self, slot: int) -> tp.Dict[str, tp.Any]:
        """Export a live slot's decode state and detach the slot WITHOUT
        freeing its pool blocks (the prefill half of disaggregation).

        Returns `{"blocks", "position", "last_token"}`: the ordered
        pool block ids backing the slot's table, the next write
        position (prompt + generated length), and the last emitted
        token — everything a decode-role engine over the SAME pool and
        CacheBox needs to continue the request token-exactly. The pool
        reservation stays keyed to this engine's `pool_key(slot)` until
        the importer re-keys it (`BlockPool.transfer_slot`); this slot
        itself is deactivated and returned to the allocator. Paged
        engines only. The last token is read from the device, behind
        every step dispatched so far: a caller that still holds such a
        step's handle finds the same token in it.
        """
        if self._pool is None:
            raise ValueError("handoff requires the paged layout: the "
                             "transfer unit is a block id list")
        if slot not in self.allocator.live or not self._active_host[slot]:
            raise ValueError(f"slot {slot} is not live")
        packet = {
            "blocks": self._pool.slot_blocks(self.pool_key(slot)),
            "position": int(self._positions_host[slot]),
            "last_token": int(np.asarray(self._tokens)[slot]),
        }
        self._park(slot)
        self._table_host[slot] = 0
        self._table_dirty = True
        self.allocator.release(slot)
        return packet

    def adopt_handoff(self, slot: int, blocks: tp.Sequence[int],
                      last_token: int, position: int) -> None:
        """Install an exported reservation into an acquired slot (the
        decode half of disaggregation).

        Fills the slot's table row with the handed-off block list and
        arms the slot at (`last_token`, `position`) — the fused/gather
        kernels read whatever table they are handed, so the next decode
        step continues exactly where the prefill engine stopped. The
        pool reservation must already be keyed to this engine's
        `pool_key(slot)` via `BlockPool.transfer_slot` (the fleet's
        `hand_off` does both halves in order). Token-exactness is the
        purity argument: the blocks hold K/V rows that are pure
        functions of (token, position, params), and this engine shares
        all three.
        """
        if self._pool is None:
            raise ValueError("handoff requires the paged layout")
        if slot not in self.allocator.live:
            raise ValueError(f"slot {slot} was not acquired")
        if not self._pool.holds(self.pool_key(slot)):
            raise ValueError(
                f"pool holds no reservation keyed to {self.pool_key(slot)} "
                f"— transfer_slot() must re-key the export first")
        if not 0 < position <= self.max_seq_len:
            raise ValueError(f"position {position} outside "
                             f"(0, {self.max_seq_len}]")
        blocks = list(blocks)
        if len(blocks) > self._pool.max_blocks:
            raise ValueError(f"{len(blocks)} blocks exceed the "
                             f"{self._pool.max_blocks}-entry table")
        row = np.zeros(self._pool.max_blocks, np.int32)  # sentinel-padded
        row[:len(blocks)] = blocks
        self._table_host[slot] = row
        self._table_dirty = True
        self._tokens = self._tokens.at[slot].set(int(last_token))
        self._positions = self._positions.at[slot].set(int(position))
        self._active = self._active.at[slot].set(True)
        self._positions_host[slot] = int(position)
        self._active_host[slot] = True

    def slot_length(self, slot: int) -> int:
        """Current sequence length of a live slot (prompt + generated).

        Served from the host position snapshot — no device->host sync,
        so the scheduler can call it per live slot per step for free.
        """
        return int(self._positions_host[slot])

    @property
    def live_count(self) -> int:
        return self.allocator.live_count

    @property
    def free_count(self) -> int:
        return self.allocator.free_count
