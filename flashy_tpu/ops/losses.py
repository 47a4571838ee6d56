# Loss ops. The reference computes losses with stock torch functionals
# (e.g. F.cross_entropy, examples/cifar/solver.py) — nothing here to
# port. flashy_tpu ships a TPU-shaped extra: a chunked softmax
# cross-entropy for large-vocab LM heads.
#
# The motivation is HBM, not FLOPs: the flagship LM's tied head emits
# f32 logits [B, T, V] — at B=16, T=1024, V=32768 that is a 2 GiB
# tensor (plus softmax intermediates) materialized purely to be
# reduced to one scalar per token. The chunked form runs the head
# matmul chunk-by-chunk under lax.scan, keeping only [B, chunk, V]
# alive at once, and recomputes the chunk's probabilities in the
# backward from the saved per-token logsumexp (the same
# save-the-normalizer trick as flash attention's backward,
# ops/attention.py). Peak head memory drops by T/chunk (e.g. 16x at
# chunk=64... T=1024), for two extra chunk matmuls in the backward.
"""Losses: chunked (never-materialize-the-logits) cross-entropy."""
import functools

import jax
import jax.numpy as jnp


def _pad_to_chunks(x, chunk: int, axis: int = 1, value=0):
    t = x.shape[axis]
    pad = (-t) % chunk
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _chunked(hidden, head, labels, chunk: int):
    """Common chunking: [B, T, D] -> scan over [n, B, chunk, D]."""
    batch, t, dim = hidden.shape
    hidden = _pad_to_chunks(hidden, chunk)
    labels = _pad_to_chunks(labels, chunk)
    n = hidden.shape[1] // chunk
    hc = hidden.reshape(batch, n, chunk, dim).transpose(1, 0, 2, 3)
    yc = labels.reshape(batch, n, chunk).transpose(1, 0, 2)
    return hc, yc, n


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_softmax_cross_entropy(hidden: jax.Array, head: jax.Array,
                                  labels: jax.Array,
                                  chunk_size: int = 256) -> jax.Array:
    """Per-token CE of a tied/linear LM head without full logits.

    Args:
        hidden: [B, T, D] final hidden states (compute dtype; the head
            matmul runs in this dtype with f32 accumulation — the same
            operand scheme as the dense head).
        head: [V, D] output embedding (any float dtype; grads come back
            in f32).
        labels: [B, T] int32 target ids.
        chunk_size: tokens per scan step; peak memory for the head is
            [B, chunk_size, V] f32. T is padded up internally.

    Returns:
        [B, T] f32 per-token `logsumexp(logits) - logits[label]`.
        Reduce (mask + mean) at the call site.
    """
    loss, _ = _ce_fwd(hidden, head, labels, chunk_size)
    return loss


def _chunk_logits(x, head, dtype):
    return jnp.einsum("bcd,vd->bcv", x, head.astype(dtype),
                      preferred_element_type=jnp.float32)


def _ce_fwd(hidden, head, labels, chunk_size):
    batch, t, _ = hidden.shape
    hc, yc, _ = _chunked(hidden, head, labels, chunk_size)

    def body(_, xy):
        x, y = xy
        logits = _chunk_logits(x, head, hidden.dtype)     # [B, c, V] f32
        lse = jax.nn.logsumexp(logits, axis=-1)           # [B, c]
        correct = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return None, (lse - correct, lse)

    _, (loss, lse) = jax.lax.scan(body, None, (hc, yc))
    loss = loss.transpose(1, 0, 2).reshape(batch, -1)[:, :t]
    lse = lse.transpose(1, 0, 2).reshape(batch, -1)[:, :t]
    return loss, (hidden, head, labels, lse)


def _ce_bwd(chunk_size, residuals, g):
    hidden, head, labels, lse = residuals
    batch, t, dim = hidden.shape
    hc, yc, n = _chunked(hidden, head, labels, chunk_size)
    # Zero cotangent on padded tokens: they then contribute nothing to
    # either gradient.
    gc = _pad_to_chunks(g.astype(jnp.float32), chunk_size)
    gc = gc.reshape(batch, n, chunk_size).transpose(1, 0, 2)
    lc = _pad_to_chunks(lse, chunk_size)
    lc = lc.reshape(batch, n, chunk_size).transpose(1, 0, 2)

    def body(dhead_acc, xygl):
        x, y, gch, lch = xygl
        logits = _chunk_logits(x, head, hidden.dtype)
        # d(lse - correct)/dlogits = softmax - onehot(label); the saved
        # logsumexp removes the second full reduction.
        probs = jnp.exp(logits - lch[..., None])
        onehot = jax.nn.one_hot(y, head.shape[0], dtype=probs.dtype)
        dlogits = (probs - onehot) * gch[..., None]       # [B, c, V] f32
        # Operands in the compute dtype + f32 accumulation (the matmul
        # scheme used across the kernels).
        dl = dlogits.astype(hidden.dtype)
        dx = jnp.einsum("bcv,vd->bcd", dl, head.astype(hidden.dtype),
                        preferred_element_type=jnp.float32)
        dhead_acc = dhead_acc + jnp.einsum(
            "bcv,bcd->vd", dl, x, preferred_element_type=jnp.float32)
        return dhead_acc, dx

    dhead0 = jnp.zeros(head.shape, jnp.float32)
    dhead, dx = jax.lax.scan(body, dhead0, (hc, yc, gc, lc))
    dx = dx.transpose(1, 0, 2, 3).reshape(batch, -1, dim)[:, :t]
    return (dx.astype(hidden.dtype), dhead.astype(head.dtype), None)


chunked_softmax_cross_entropy.defvjp(_ce_fwd, _ce_bwd)


def lm_next_token_loss(model, variables, tokens, *, mode: str = "dense",
                       chunk_size: int = 256) -> jax.Array:
    """Mean next-token CE for a TransformerLM — dense or chunked head.

    'dense' materializes [B, T, V] logits (fine for small vocab);
    'chunked' runs `chunked_softmax_cross_entropy` over the final
    hidden states (large-vocab HBM saver). Both are the same math.
    The cross-entropy (with the chunked head) is traced under the named
    scope `loss`, which a device trace reads as the head's share.
    """
    if mode == "dense":
        import optax
        logits = model.apply(variables, tokens)
        with jax.named_scope("loss"):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]).mean()
    if mode != "chunked":
        raise ValueError(f"mode must be 'dense' or 'chunked', got {mode!r}")
    hidden, head = model.apply(variables, tokens, return_hidden=True)
    with jax.named_scope("loss"):
        loss = chunked_softmax_cross_entropy(hidden[:, :-1], head,
                                             tokens[:, 1:], chunk_size)
        return loss.mean()
