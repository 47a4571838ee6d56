# Speculative decoding + chunked prefill: the acceptance rule
# (greedy longest-prefix and rejection sampling), the [S, k+1] verify
# step's token-exactness whatever the draft proposes, rollback-by-
# position-reset (stale K/V rows provably harmless — asserted
# bit-level), chunked prefill exactness around chunk boundaries, the
# scheduler's prefill/decode interleave stall bound, the draft
# providers, and the metrics/telemetry surface.
import logging

import numpy as np
import pytest

from flashy_tpu.serve import (
    ContinuousBatchingScheduler, DecodeEngine, ModelDraft, NGramDraft,
    ServeMetrics, SlotAllocator,
)


def _tiny_model(vocab=32, max_seq_len=32):
    import jax
    import jax.numpy as jnp
    from flashy_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=vocab, dim=16, num_layers=2,
                            num_heads=2, attention="dense",
                            max_seq_len=max_seq_len, dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32))
    return model, params


# ----------------------------------------------------------------------
# the acceptance rule (models/decoding.py)
# ----------------------------------------------------------------------
def _logits_for(targets, vocab):
    """[B, n, V] logits whose argmax (and ~all mass) is `targets`."""
    import jax.numpy as jnp
    targets = np.asarray(targets)
    out = np.full(targets.shape + (vocab,), -10.0, np.float32)
    batch, n = targets.shape
    for b in range(batch):
        for i in range(n):
            out[b, i, targets[b, i]] = 10.0
    return jnp.asarray(out)


def test_speculative_acceptance_greedy_longest_prefix():
    from flashy_tpu.models.decoding import speculative_acceptance

    vocab = 8
    # target greedy tokens per position: [1, 2, 3, 4] + bonus 5
    logits = _logits_for([[1, 2, 3, 4, 5]], vocab)
    # full acceptance: all 4 drafts match -> 5 emitted, bonus last
    out, acc = speculative_acceptance(
        np.asarray([[1, 2, 3, 4]], np.int32), logits, pad_token=0)
    assert int(acc[0]) == 4
    assert out[0].tolist() == [1, 2, 3, 4, 5]
    # partial: first mismatch at index 2 -> 2 accepted + the target's
    # own token there; positions beyond are pad
    out, acc = speculative_acceptance(
        np.asarray([[1, 2, 7, 4]], np.int32), logits, pad_token=0)
    assert int(acc[0]) == 2
    assert out[0].tolist() == [1, 2, 3, 0, 0]
    # zero acceptance: the step still emits the target's first token
    out, acc = speculative_acceptance(
        np.asarray([[7, 7, 7, 7]], np.int32), logits, pad_token=0)
    assert int(acc[0]) == 0
    assert out[0].tolist() == [1, 0, 0, 0, 0]
    # a LATER match without the prefix counts for nothing (longest
    # prefix, not any-position matching)
    out, acc = speculative_acceptance(
        np.asarray([[7, 2, 3, 4]], np.int32), logits, pad_token=0)
    assert int(acc[0]) == 0 and out[0].tolist() == [1, 0, 0, 0, 0]


def test_speculative_acceptance_rows_independent():
    from flashy_tpu.models.decoding import speculative_acceptance

    logits = _logits_for([[1, 2, 3], [4, 5, 6]], 8)
    out, acc = speculative_acceptance(
        np.asarray([[1, 2], [9 % 8, 5]], np.int32), logits, pad_token=7)
    assert acc.tolist() == [2, 0]
    assert out[0].tolist() == [1, 2, 3]
    assert out[1].tolist() == [4, 7, 7]


def test_speculative_acceptance_sampling_deterministic_cases():
    # rejection sampling with a (near-)deterministic target: p(x) ~ 1
    # accepts always; a draft the target gives ~0 mass rejects at 0 and
    # the residual (~= p) resamples the target's own token.
    import jax
    from flashy_tpu.models.decoding import speculative_acceptance

    logits = _logits_for([[1, 2, 3]], 8)  # +-10 logits, temp 0.5 -> p~1
    rng = jax.random.PRNGKey(0)
    out, acc = speculative_acceptance(
        np.asarray([[1, 2]], np.int32), logits, temperature=0.5, rng=rng,
        pad_token=0)
    assert int(acc[0]) == 2 and out[0].tolist() == [1, 2, 3]
    out, acc = speculative_acceptance(
        np.asarray([[5, 2]], np.int32), logits, temperature=0.5, rng=rng,
        pad_token=0)
    assert int(acc[0]) == 0 and out[0].tolist() == [1, 0, 0]


def test_speculative_acceptance_sampling_requires_rng():
    from flashy_tpu.models.decoding import speculative_acceptance

    with pytest.raises(ValueError, match="rng"):
        speculative_acceptance(np.asarray([[1]], np.int32),
                               _logits_for([[1, 2]], 8), temperature=0.7)


def test_speculative_acceptance_sampling_matches_target_distribution():
    # the rejection-sampling identity: over many keys, the emitted
    # first token's distribution matches sampling the target directly —
    # even under a deterministic (one-hot) proposal the target mostly
    # rejects.
    import jax
    import jax.numpy as jnp
    from flashy_tpu.models.decoding import speculative_acceptance

    vocab = 4
    base = np.asarray([2.0, 1.0, 0.0, -1.0], np.float32)
    logits = jnp.asarray(np.tile(base, (1, 2, 1)))  # [1, 2, V]
    p = np.exp(base) / np.exp(base).sum()
    draws = []
    for seed in range(4000):
        out, acc = speculative_acceptance(
            np.asarray([[3]], np.int32), logits, temperature=1.0,
            rng=jax.random.PRNGKey(seed), pad_token=0)
        draws.append(int(out[0, 0]))
    freq = np.bincount(draws, minlength=vocab) / len(draws)
    np.testing.assert_allclose(freq, p, atol=0.03)


# ----------------------------------------------------------------------
# engine verify step
# ----------------------------------------------------------------------
def test_verify_step_token_exact_any_draft():
    # greedy speculative decode reproduces generate() exactly whether
    # the draft is an oracle (full acceptance) or garbage (zero)
    from flashy_tpu.models.decoding import generate

    model, params = _tiny_model()
    prompt = np.asarray([5, 9, 2, 14, 7], np.int32)
    want = np.asarray(generate(model, params, prompt[None],
                               max_new_tokens=9))[0][len(prompt):]

    for oracle in (True, False):
        engine = DecodeEngine(model, params, slots=2, spec_k=3)
        engine.warmup(prompt_lengths=[len(prompt)])
        warm = engine.compile_cache.stats()["misses"]
        slot = engine.acquire_slot()
        got = [engine.prefill(slot, prompt)]
        while len(got) < 9:
            drafts = np.full((2, 3), 31, np.int32)
            if oracle:
                future = [int(t) for t in want[len(got):len(got) + 3]]
                drafts[slot, :len(future)] = future
            out, acc = engine.decode_speculative(drafts)
            n = int(acc[slot]) + 1
            if oracle:
                assert n >= min(3, 9 - len(got))  # oracle drafts accepted
            got.extend(int(t) for t in out[slot, :n])
        assert got[:9] == [int(t) for t in want], (oracle, got)
        stats = engine.compile_cache.stats()
        assert stats["misses"] == warm and stats["recompiles"] == 0


def test_verify_step_sampling_engine_runs():
    # temperature > 0 engines verify with rejection sampling: tokens
    # stay in-vocab, accepted counts in [0, k], positions advance by
    # accepted+1 — the distributional identity itself is unit-tested
    # on speculative_acceptance directly.
    import jax

    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2, spec_k=3,
                          temperature=0.8, rng=jax.random.PRNGKey(5))
    engine.warmup(prompt_lengths=[4])
    slot = engine.acquire_slot()
    engine.prefill(slot, np.asarray([1, 2, 3, 4], np.int32))
    before = engine.slot_length(slot)
    out, acc = engine.decode_speculative(np.full((2, 3), 7, np.int32))
    assert 0 <= int(acc[slot]) <= 3
    span = out[slot, :int(acc[slot]) + 1]
    assert ((0 <= span) & (span < 32)).all()
    assert engine.slot_length(slot) == before + int(acc[slot]) + 1


def test_verify_step_inactive_slots_untouched():
    # a verify step must not corrupt slots that are mid-prefill or
    # free: their positions park at max_seq_len so draft writes drop
    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2, spec_k=2)
    engine.warmup(prompt_lengths=[4])
    slot = engine.acquire_slot()
    engine.prefill(slot, np.asarray([1, 2, 3, 4], np.int32))
    import jax
    snapshot = jax.tree_util.tree_map(
        lambda leaf: np.asarray(leaf), engine._cache)
    other = 1 - slot
    out, acc = engine.decode_speculative(np.full((2, 2), 9, np.int32))
    after = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf),
                                   engine._cache)
    for a, b in zip(jax.tree_util.tree_leaves(snapshot),
                    jax.tree_util.tree_leaves(after)):
        # the OTHER slot's rows are bit-identical; axis -4 is the slot
        np.testing.assert_array_equal(a[..., other, :, :, :],
                                      b[..., other, :, :, :])
    assert int(out[other, 0]) == engine.pad_token and int(acc[other]) == 0


def _slot_rows(engine, slot, upto):
    """np copy of a slot's cache rows [0, upto) across all leaves."""
    import jax
    return [np.asarray(leaf[..., slot, :upto, :, :])
            for leaf in jax.tree_util.tree_leaves(engine._cache)]


# What a full rejection leaves behind, stated as the two things XLA:CPU
# can still prove (jax 0.9.0 does not round dots of different shapes
# alike: the fresh prefill's [1, 8] bucket against the verify step's
# [2, 4] rows differ by 3.6e-7 on the one row the verify step wrote,
# in the second layer, whose input went through the first layer's
# attention). Nothing is loosened where both sides run the same dots.
# The bound, in float32 ulps at the leaf's largest magnitude (about 3):
# 1.4e-6 against the 3.6e-7 seen; a stale draft row leaking into the
# horizon would move a row by its own size.
ROLLBACK_ULPS = 4


@pytest.mark.parametrize("against", ["same_executable", "fresh_prefill"])
def test_full_rejection_rollback_leaves_nothing_behind(against):
    # after a forced full-rejection step, the slot's cache region up to
    # the accepted position is what the accepted tokens alone produce
    from flashy_tpu.models.decoding import generate

    model, params = _tiny_model()
    prompt = np.asarray([5, 9, 2, 14, 7], np.int32)

    engine = DecodeEngine(model, params, slots=2, spec_k=3)
    engine.warmup(prompt_lengths=[len(prompt), len(prompt) + 1])
    slot = engine.acquire_slot()
    first = engine.prefill(slot, prompt)
    # drafts of token 31 reject in full against this model/prompt
    out, acc = engine.decode_speculative(np.full((2, 3), 31, np.int32))
    assert int(acc[slot]) == 0, "construction broke: drafts were accepted"
    assert engine.slot_length(slot) == len(prompt) + 1
    # region up to the accepted position: prompt rows + the row the
    # verify step wrote for `first` at position len(prompt)
    got = _slot_rows(engine, slot, len(prompt) + 1)

    if against == "same_executable":
        # the SAME verify executable on the same prompt with the
        # rejected drafts cut down to the accepted ones (none: pads):
        # same-shaped dots, so the rows are bit-equal — they do not
        # depend on what was rejected — and the stream goes on exactly
        # as generate() does
        other = DecodeEngine(model, params, slots=2, spec_k=3,
                             compile_cache=engine.compile_cache)
        other_slot = other.acquire_slot()
        assert other.prefill(other_slot, prompt) == first
        pads = np.full((2, 3), other.pad_token, np.int32)
        _, other_acc = other.decode_speculative(pads)
        assert int(other_acc[other_slot]) == 0
        want = _slot_rows(other, other_slot, len(prompt) + 1)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        stream = [first, int(out[slot, 0])] + [
            int(engine.decode()[slot]) for _ in range(4)]
        reference = np.asarray(generate(model, params, prompt[None],
                                        max_new_tokens=6))[0][len(prompt):]
        assert stream == [int(t) for t in reference]
    else:
        # a fresh prefill of the same tokens runs other dot shapes (one
        # [1, 8] bucket): equal to a few ulps at the rows' magnitude
        fresh = DecodeEngine(model, params, slots=2,
                             compile_cache=engine.compile_cache)
        fresh_slot = fresh.acquire_slot()
        fresh.prefill(fresh_slot, np.concatenate([prompt, [first]])
                      .astype(np.int32))
        want = _slot_rows(fresh, fresh_slot, len(prompt) + 1)
        for g, w in zip(got, want):
            bound = (ROLLBACK_ULPS * np.finfo(np.float32).eps
                     * np.abs(w).max())
            np.testing.assert_allclose(g, w, rtol=0, atol=bound)


# ----------------------------------------------------------------------
# chunked prefill
# ----------------------------------------------------------------------
@pytest.mark.parametrize("length", [1, 7, 8, 9])
def test_chunked_prefill_token_exact_at_boundaries(length):
    # prompt lengths straddling the chunk boundary (1, chunk-1, chunk,
    # chunk+1) produce the same first token and continuation as both
    # generate() and the monolithic bucketed path
    from flashy_tpu.models.decoding import generate

    chunk = 8
    model, params = _tiny_model()
    prompt = ((np.arange(length) * 3 + 1) % 32).astype(np.int32)
    want = np.asarray(generate(model, params, prompt[None],
                               max_new_tokens=4))[0][length:]

    engine = DecodeEngine(model, params, slots=2, chunk=chunk)
    engine.warmup()
    slot = engine.acquire_slot()
    start, token = 0, None
    ticks = 0
    while token is None:
        start, token = engine.prefill_chunk(slot, prompt, start)
        ticks += 1
    assert ticks == -(-length // chunk) or length <= engine.tail_bucket
    got = [token] + [int(engine.decode()[slot]) for _ in range(3)]
    assert got == [int(t) for t in want]
    assert engine.compile_cache.stats()["recompiles"] == 0

    bucketed = DecodeEngine(model, params, slots=2)
    b_slot = bucketed.acquire_slot()
    assert bucketed.prefill(b_slot, prompt) == got[0]


def test_chunked_engine_validates_geometry():
    model, params = _tiny_model(max_seq_len=32)
    with pytest.raises(ValueError, match="divide"):
        DecodeEngine(model, params, slots=2, chunk=7)
    with pytest.raises(ValueError, match="tail_bucket"):
        DecodeEngine(model, params, slots=2, chunk=8, tail_bucket=9)
    engine = DecodeEngine(model, params, slots=2, chunk=8)
    with pytest.raises(ValueError, match="chunk"):
        # monolithic engine has no chunk path
        DecodeEngine(model, params, slots=2).prefill_chunk(
            0, np.asarray([1, 2], np.int32), 0)
    slot = engine.acquire_slot()
    with pytest.raises(ValueError, match="start"):
        engine.prefill_chunk(slot, np.asarray([1, 2], np.int32), 5)


def test_chunked_prefill_interleaves_with_decode():
    # the stall bound, structurally: while a long prompt prefills, each
    # scheduler step advances at most one chunk of prompt AND the live
    # request still emits its token on every step.
    model, params = _tiny_model(max_seq_len=64)
    chunk = 8
    engine = DecodeEngine(model, params, slots=2, chunk=chunk)
    engine.warmup()
    scheduler = ContinuousBatchingScheduler(engine)
    short = scheduler.submit(np.asarray([1, 2, 3], np.int32),
                             max_new_tokens=16)
    scheduler.step()  # launches the slice and the first decode step
    assert short.state == "prefilling" and not scheduler.idle
    scheduler.step()  # ... and reads them while the next one runs
    assert short.state == "running" and len(short.generated) == 2
    long = scheduler.submit((np.arange(3 * chunk + 2) % 32)
                            .astype(np.int32), max_new_tokens=2)
    ticks = 0
    while long.state in ("queued", "prefilling"):
        before = len(short.generated)
        scheduler.step()
        ticks += 1
        assert scheduler.prefill_tokens_last_step <= chunk
        assert len(short.generated) == before + 1  # no stall
    assert ticks >= -(-long.prompt.size // chunk)
    scheduler.run()
    assert short.done and long.done
    assert scheduler.max_prefill_tokens_per_step <= chunk
    assert engine.compile_cache.stats()["recompiles"] == 0


# ----------------------------------------------------------------------
# draft providers
# ----------------------------------------------------------------------
def test_ngram_draft_lookup_and_fallback():
    draft = NGramDraft(slots=2, k=3, ngram=2)
    draft.begin(0, np.asarray([1, 2, 3, 1, 2], np.int32), first_token=3)
    # trailing [2, 3] occurred at positions 1..2; continuation 1, 2, 3
    proposal = draft.propose()
    assert proposal[0].tolist() == [1, 2, 3]
    assert proposal[1].tolist() == [0, 0, 0]  # no live request -> pad
    # observe a novel token: no n-gram/1-gram continuation long enough
    # still yields k tokens (repeat padding), never a shape change
    draft.observe(0, [7, 7], position=8)
    assert len(draft.propose()[0]) == 3
    draft.retire(0)
    assert draft.propose()[0].tolist() == [0, 0, 0]


def test_ngram_draft_proposes_cycle_continuation():
    draft = NGramDraft(slots=1, k=4, ngram=3)
    draft.begin(0, np.asarray([5, 6, 5, 6, 5, 6], np.int32), first_token=5)
    # history 5 6 5 6 5 6 5: trailing 3-gram [5, 6, 5] last recurs at
    # index 2, continuation [6, 5]; the tail pads by repeating the
    # last proposed token
    assert draft.propose()[0].tolist() == [6, 5, 5, 5]


def test_slot_allocator_specific_acquire():
    alloc = SlotAllocator(3)
    assert alloc.acquire(1) == 1
    assert alloc.acquire() == 0  # lowest free, skipping the taken one
    with pytest.raises(ValueError, match="not free"):
        alloc.acquire(1)
    with pytest.raises(ValueError, match="not free"):
        alloc.acquire(7)
    alloc.release(1)
    assert alloc.acquire(1) == 1


def test_scheduler_rejects_draft_k_mismatch():
    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2, spec_k=4)
    with pytest.raises(ValueError, match="spec_k"):
        ContinuousBatchingScheduler(engine, draft=NGramDraft(slots=2, k=2))


# ----------------------------------------------------------------------
# scheduler end-to-end under speculation
# ----------------------------------------------------------------------
def _serve_speculative(engine, draft, workload, **submit_kw):
    scheduler = ContinuousBatchingScheduler(engine, draft=draft)
    handles = [scheduler.submit(p, m, **submit_kw) for p, m in workload]
    scheduler.run()
    return scheduler, handles


def test_scheduler_speculative_matches_generate():
    from flashy_tpu.models.decoding import generate

    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2, spec_k=3, chunk=8)
    engine.warmup()
    warm = engine.compile_cache.stats()["misses"]
    rng = np.random.default_rng(3)
    workload = [(np.tile(rng.integers(0, 32, 3), 4)[:n].astype(np.int32),
                 m) for n, m in [(5, 8), (9, 6), (3, 10), (11, 7)]]
    scheduler, handles = _serve_speculative(
        engine, NGramDraft(slots=2, k=3), workload)
    stats = engine.compile_cache.stats()
    assert stats["misses"] == warm and stats["recompiles"] == 0
    for handle, (prompt, max_new) in zip(handles, workload):
        assert handle.done
        want = np.asarray(generate(model, params, prompt[None],
                                   max_new_tokens=max_new))[0]
        np.testing.assert_array_equal(handle.output, want)
    summary = scheduler.metrics.summary()
    assert summary["spec_drafted"] > 0
    # every token except each request's prefill-emitted first one came
    # out of a verify step
    assert summary["spec_emitted"] == \
        sum(len(h.generated) for h in handles) - len(handles)
    assert 0.0 <= summary["acceptance_rate"] <= 1.0
    assert engine.live_count == 0


def test_scheduler_speculative_scan_layers_matches_generate():
    # the stacked [L, S, T, H, Dh] cache layout: verify's per-row
    # writes and the chunk slice/merge must address the slot axis at
    # -4, not 0
    import jax
    import jax.numpy as jnp
    from flashy_tpu.models import TransformerConfig, TransformerLM
    from flashy_tpu.models.decoding import generate

    cfg = TransformerConfig(vocab_size=32, dim=16, num_layers=2,
                            num_heads=2, attention="dense", max_seq_len=32,
                            dtype=jnp.float32, scan_layers=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32))
    engine = DecodeEngine(model, params, slots=2, spec_k=3, chunk=8)
    engine.warmup()
    workload = [(np.tile([3, 7], 5)[:9].astype(np.int32), 8),
                (np.asarray([1, 2, 3], np.int32), 10)]
    scheduler, handles = _serve_speculative(
        engine, NGramDraft(slots=2, k=3), workload)
    for handle, (prompt, max_new) in zip(handles, workload):
        want = np.asarray(generate(model, params, prompt[None],
                                   max_new_tokens=max_new))[0]
        np.testing.assert_array_equal(handle.output, want)
    assert engine.compile_cache.stats()["recompiles"] == 0


def test_scheduler_speculative_eos_truncates_span():
    # EOS inside an accepted span must end the request exactly there,
    # matching generate(eos_token=...)'s pinned prefix
    from flashy_tpu.models.decoding import generate

    model, params = _tiny_model()
    prompt = np.asarray([5, 9, 2, 14, 7], np.int32)
    free_run = np.asarray(generate(model, params, prompt[None],
                                   max_new_tokens=8))[0]
    eos = int(free_run[len(prompt) + 2])

    engine = DecodeEngine(model, params, slots=2, spec_k=4)
    engine.warmup(prompt_lengths=[len(prompt)])
    scheduler, (handle,) = _serve_speculative(
        engine, NGramDraft(slots=2, k=4), [(prompt, 8)], eos_token=eos)
    assert handle.finish_reason == "eos"
    assert handle.generated[-1] == eos and eos not in handle.generated[:-1]
    pinned = np.asarray(generate(model, params, prompt[None],
                                 max_new_tokens=8, eos_token=eos))[0]
    np.testing.assert_array_equal(
        handle.output, pinned[:len(prompt) + len(handle.generated)])
    assert engine.free_count == 2


@pytest.mark.slow
def test_scheduler_speculative_model_draft_matches_generate():
    import jax
    import jax.numpy as jnp
    from flashy_tpu.models.decoding import generate

    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2, spec_k=2)
    engine.warmup(prompt_lengths=[5, 9])
    # a differently-initialized draft: bad proposals, exact output
    draft_params = model.init(jax.random.PRNGKey(7),
                              jnp.ones((1, 4), jnp.int32))
    draft = ModelDraft(model, draft_params, slots=2, k=2)
    draft.warmup(prompt_lengths=[5, 9])
    workload = [(np.asarray([5, 9, 2, 14, 7], np.int32), 6),
                ((np.arange(9) % 32).astype(np.int32), 7)]
    scheduler, handles = _serve_speculative(engine, draft, workload)
    for handle, (prompt, max_new) in zip(handles, workload):
        want = np.asarray(generate(model, params, prompt[None],
                                   max_new_tokens=max_new))[0]
        np.testing.assert_array_equal(handle.output, want)
    # the mirror released its slots alongside the target
    assert draft.engine.live_count == 0 and engine.live_count == 0


def test_model_draft_mirror_cache_has_no_holes():
    # regression: with an oracle draft (same weights as the target)
    # every span fully accepts, and the mirror's row for the LAST
    # accepted draft must still be written — propose() runs k+1 decode
    # steps precisely so that row exists. Rows below the mirror's
    # position must match a fresh prefill of the same tokens exactly.
    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2, spec_k=3)
    engine.warmup(prompt_lengths=[5])
    draft = ModelDraft(model, params, slots=2, k=3)
    draft.warmup(prompt_lengths=[5, 16])
    scheduler = ContinuousBatchingScheduler(engine, draft=draft)
    prompt = np.asarray([5, 9, 2, 14, 7], np.int32)
    handle = scheduler.submit(prompt, max_new_tokens=20)
    for _ in range(3):
        scheduler.step()
    assert not handle.done  # mid-flight: mirror state is inspectable
    slot = handle.slot
    position = draft.engine.slot_length(slot)
    # oracle drafts fully accept -> 4 tokens per step after the first
    assert position == engine.slot_length(slot)
    tokens = np.concatenate([prompt, handle.generated]).astype(np.int32)

    fresh = DecodeEngine(model, params, slots=2)
    fresh_slot = fresh.acquire_slot()
    fresh.prefill(fresh_slot, tokens[:position])
    got = _slot_rows(draft.engine, slot, position)
    want = _slot_rows(fresh, fresh_slot, position)
    for g, w in zip(got, want):
        # sequential [S, 1] decode writes vs one batched prefill round
        # differently (~1e-7); the hole this guards against is an
        # all-zero row, orders of magnitude outside this tolerance
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-4)
    scheduler.run()
    assert handle.done


def test_model_draft_scoped_watchdog_keeps_target_compile_free(tmp_path):
    # regression: target + mirror engines under ONE telemetry watchdog
    # must not collide — the mirror's first 'decode/S' compile used to
    # count against the target's warm-up budget, tripping the
    # zero-recompile serving gate on a healthy run.
    from flashy_tpu.observability import enable_telemetry, disable_telemetry

    telemetry = enable_telemetry(folder=tmp_path)
    try:
        model, params = _tiny_model()
        engine = DecodeEngine(model, params, slots=2, spec_k=2)
        engine.warmup(prompt_lengths=[4])
        warm = engine.compile_cache.stats()["misses"]
        draft = ModelDraft(model, params, slots=2, k=2)
        draft.warmup(prompt_lengths=[4])
        scheduler = ContinuousBatchingScheduler(engine, draft=draft)
        scheduler.submit(np.asarray([1, 2, 3, 4], np.int32),
                         max_new_tokens=6)
        scheduler.run()
        stats = engine.compile_cache.stats()
        assert stats["recompiles"] == 0
        assert stats["misses"] == warm
        assert draft.engine.compile_cache.recompiles() == 0
        # both engines report through the same watchdog, under
        # disjoint names
        names = set(telemetry.watchdog.counts)
        assert "decode/2" in names
        assert "draft/decode/2" in names
    finally:
        disable_telemetry()


def test_slot_length_serves_from_host_snapshot():
    # slot_length must agree with the device positions at every
    # lifecycle point WITHOUT reading them back (satellite: the
    # scheduler calls it per live slot per step)
    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2, spec_k=2)
    engine.warmup(prompt_lengths=[4])
    slot = engine.acquire_slot()
    engine.prefill(slot, np.asarray([1, 2, 3, 4], np.int32))
    assert engine.slot_length(slot) == 4 == int(engine._positions[slot])
    engine.decode()
    assert engine.slot_length(slot) == 5 == int(engine._positions[slot])
    out, acc = engine.decode_speculative(np.full((2, 2), 31, np.int32))
    want = 5 + int(acc[slot]) + 1
    assert engine.slot_length(slot) == want == int(engine._positions[slot])
    engine.set_slot_state(slot, 3, 6)
    assert engine.slot_length(slot) == 6 == int(engine._positions[slot])
    engine.retire(slot)
    assert engine.slot_length(slot) == engine.max_seq_len


# ----------------------------------------------------------------------
# metrics + demo
# ----------------------------------------------------------------------
def test_spec_metrics_summary_fields():
    metrics = ServeMetrics()
    assert "acceptance_rate" not in metrics.summary()  # spec-off: absent
    metrics.on_spec_step(drafted=4, accepted=[4, 0], emitted=6)
    metrics.on_spec_step(drafted=4, accepted=[2], emitted=3)
    summary = metrics.summary()
    assert summary["spec_drafted"] == 12
    assert summary["spec_emitted"] == 9
    assert np.isclose(summary["acceptance_rate"], 6 / 12)
    assert summary["accepted_per_step_p50"] == 2.0
    assert summary["accepted_per_step_p95"] >= 2.0


def test_serve_formatter_and_info_render_acceptance():
    from flashy_tpu.info import format_serve_status
    from flashy_tpu.logging import serve_formatter

    out = serve_formatter()({"acceptance_rate": 0.512, "spec_drafted": 80,
                             "accepted_per_step_p50": 2.5})
    assert out["acceptance_rate"] == "51%"
    assert out["spec_drafted"] == "80"
    line = format_serve_status({"requests": 4, "acceptance_rate": 0.5,
                                "accepted_per_step_p50": 2.0})
    assert "acceptance=50%" in line and "accepted_per_step_p50=2.0" in line


@pytest.mark.slow
def test_serve_reports_spec_through_telemetry(tmp_path):
    import json
    from flashy_tpu.observability import enable_telemetry, disable_telemetry

    telemetry = enable_telemetry(folder=tmp_path)
    try:
        model, params = _tiny_model()
        engine = DecodeEngine(model, params, slots=2, spec_k=2, chunk=8)
        engine.warmup()
        scheduler = ContinuousBatchingScheduler(
            engine, draft=NGramDraft(slots=2, k=2))
        scheduler.submit(np.asarray([1, 2, 1, 2, 1], np.int32),
                         max_new_tokens=6)
        scheduler.run()
        scheduler.metrics.record()
        scheduler.metrics.write_status(tmp_path)
        names = {e.get("name") for e in telemetry.tracer.events}
        assert "serve/verify" in names
        assert "serve/prefill_chunk" in names
        assert "serve/acceptance" in names
    finally:
        disable_telemetry()
    status = json.loads((tmp_path / "serve.json").read_text())
    assert "acceptance_rate" in status
    journal = [json.loads(line)
               for line in (tmp_path / "telemetry.jsonl").read_text()
               .splitlines()]
    summaries = [r for r in journal if r["type"] == "serve_summary"]
    assert summaries and "spec_drafted" in summaries[-1]


@pytest.mark.slow
def test_spec_demo_entrypoint_smoke(caplog):
    from flashy_tpu.serve.__main__ import run_chunked_demo, run_spec_demo

    with caplog.at_level(logging.INFO, logger="flashy_tpu.serve.demo"):
        assert run_spec_demo(requests=6, slots=2, k=3, chunk=8,
                             accept_floor=0.0, seed=1) == 0
        assert run_chunked_demo(chunk=8, seed=1) == 0
