# Training batches. The stream is `examples/lm/solver.py`'s
# `synthetic_token_stream` (copied: the yardstick lives with the
# benchmark): a seeded Markov-ish chain with 15% jumps, so there is
# next-token structure a model can learn and the loss falls.
"""Traffic kind `token_batches`: seeded [batch, seq_len] token batches."""
import numpy as np


def generate(params: dict, seed: int, vocab_size: int):
    """Returns batch(step) -> int32 [batch_size, seq_len]. Every step of
    every seed has the same shape; the seed changes the chain and the
    draws, never the amount of work."""
    batch_size, seq_len = params["batch_size"], params["seq_len"]
    jump = params.get("jump_probability", 0.15)
    mixing = np.random.default_rng(seed).integers(1, vocab_size - 1, size=257)

    def batch(step: int) -> np.ndarray:
        gen = np.random.default_rng([seed, step])
        tokens = np.empty((batch_size, seq_len), np.int64)
        tokens[:, 0] = gen.integers(0, vocab_size, batch_size)
        noise = gen.random((batch_size, seq_len)) < jump
        jumps = gen.integers(0, vocab_size, (batch_size, seq_len))
        for t in range(1, seq_len):
            last = tokens[:, t - 1]
            follow = (last * 31 + mixing[last % 257]) % vocab_size
            tokens[:, t] = np.where(noise[:, t], jumps[:, t], follow)
        return tokens.astype(np.int32)

    return batch


def describe(params: dict) -> dict:
    return {"tokens_per_step": params["batch_size"] * params["seq_len"],
            "seq_len": params["seq_len"], "batch_size": params["batch_size"]}
