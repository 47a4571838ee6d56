# Closed-loop clients: `clients` callers, each sending its next request
# the moment its last one completes. The generator fixes WHAT is sent;
# the runner sends it.
#
# Every seed sends the same sizes in the same order. Lengths are not
# sampled: a round of `round_size` requests holds the quantiles
# (i + 0.5) / round_size of the length distribution, once each, so any
# whole number of rounds is the same work. The order within each round
# and the pairing of prompt with output lengths come from the mix's own
# `order_seed` — a fixed trace of sizes, replayed — because the order
# alone moves a tail: with the order drawn from --seed, the 95th
# percentile of time to first token over 330 requests differed by 10%
# between seeds and by 0.02% between two runs of one seed (chip runs,
# PR 24). --seed draws the token ids (uniform over the vocabulary: no
# shared prefixes) and, in the runner, the weights; neither changes the
# amount of work.
"""Traffic kind `closed_clients`: request stream for N waiting callers."""
import math
from statistics import NormalDist

import numpy as np


def _quantiles(spec: dict, count: int) -> np.ndarray:
    """`count` whole-number lengths at the mid-quantiles of `spec`:
    {"dist": "lognormal", "median", "sigma", "min", "max"} or
    {"dist": "uniform", "min", "max"}."""
    points = (np.arange(count) + 0.5) / count
    if spec["dist"] == "lognormal":
        normal = NormalDist()
        values = [spec["median"] * math.exp(spec["sigma"] * normal.inv_cdf(p))
                  for p in points]
    elif spec["dist"] == "uniform":
        values = spec["min"] + points * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(values), spec["min"], spec["max"]).astype(int)


def generate(params: dict, seed: int, vocab_size: int):
    """Returns request(index) -> (prompt int32 [P], output budget), for
    index 0, 1, 2, ... in the order clients take them. The first
    `clients` requests (one per client, all sent at once) draw their
    budget from `first_output` when the mix gives one, so that the
    first generation's completions spread."""
    size, order_seed = params["round_size"], params["order_seed"]
    prompts, outputs = (_quantiles(params[key], size)
                        for key in ("prompt", "output"))
    first = (_quantiles(params["first_output"], params["clients"])
             if "first_output" in params else None)
    rounds = {}

    def round_of(number: int):
        if number not in rounds:
            rng = np.random.default_rng([order_seed, 1, number])
            rounds[number] = (rng.permutation(prompts),
                              rng.permutation(outputs))
        return rounds[number]

    first_order = np.random.default_rng([order_seed, 2]).permutation(
        params["clients"])

    def request(index: int):
        round_prompts, round_outputs = round_of(index // size)
        length = int(round_prompts[index % size])
        budget = int(round_outputs[index % size])
        if first is not None and index < params["clients"]:
            budget = int(first[first_order[index]])
        rng = np.random.default_rng([seed, 3, index])
        return rng.integers(0, vocab_size, length).astype(np.int32), budget

    return request


def describe(params: dict) -> dict:
    size = params["round_size"]
    prompts, outputs = (_quantiles(params[key], size)
                        for key in ("prompt", "output"))
    summary = lambda v: {"min": int(v.min()), "p50": float(np.median(v)),
                         "mean": float(v.mean()), "max": int(v.max())}
    return {"clients": params["clients"], "round_size": size,
            "prompt_tokens": summary(prompts),
            "output_tokens": summary(outputs),
            "longest_request": int(prompts.max() + outputs.max())}
