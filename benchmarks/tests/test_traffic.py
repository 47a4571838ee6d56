"""The generators: same seed, same requests; every seed, the same sizes."""
import json
import os

import numpy as np
import pytest

from benchmarks.traffic import closed_clients, token_batches


@pytest.fixture(params=["chat-closed", "fullctx-closed"])
def mix(request, root):
    with open(os.path.join(root, "benchmarks", "traffic",
                           f"{request.param}.json")) as f:
        return json.load(f)


def test_same_seed_same_requests(mix):
    one = closed_clients.generate(mix, 2 ** 31 + 12345, 50304)
    two = closed_clients.generate(mix, 2 ** 31 + 12345, 50304)
    for index in (0, 1, 31, 100, 1000):
        (p1, b1), (p2, b2) = one(index), two(index)
        assert b1 == b2 and np.array_equal(p1, p2)
        assert p1.dtype == np.int32 and 0 <= p1.min() and p1.max() < 50304


def test_lengths_within_clips_and_context(mix):
    request = closed_clients.generate(mix, 7, 50304)
    for index in range(3 * mix["round_size"]):
        prompt, budget = request(index)
        assert mix["prompt"]["min"] <= prompt.size <= mix["prompt"]["max"]
        low = min(mix["output"]["min"],
                  mix.get("first_output", mix["output"])["min"])
        assert low <= budget <= mix["output"]["max"]
        assert prompt.size + budget <= 2048


def test_every_seed_sends_the_same_sizes_in_the_same_order(mix):
    one = closed_clients.generate(mix, 1, 50304)
    two = closed_clients.generate(mix, 2 ** 31 + 5, 50304)
    span = range(2 * max(mix["round_size"], mix["clients"]))
    assert [(one(i)[0].size, one(i)[1]) for i in span] \
        == [(two(i)[0].size, two(i)[1]) for i in span]
    assert not any(np.array_equal(one(i)[0], two(i)[0]) for i in span)
    # a whole round holds each mid-quantile of the lengths once
    size = mix["round_size"]
    start = -max(size, mix["clients"]) % size + max(size, mix["clients"])
    rounds = [sorted(one(i)[0].size for i in range(start + k * size,
                                                   start + (k + 1) * size))
              for k in range(2)]
    assert rounds[0] == rounds[1]


def test_chat_mix_means_are_the_issues():
    with open(os.path.join(os.path.dirname(__file__), "..", "traffic",
                           "chat-closed.json")) as f:
        described = closed_clients.describe(json.load(f))
    assert 165 <= described["prompt_tokens"]["mean"] <= 185
    assert 78 <= described["output_tokens"]["mean"] <= 90
    assert described["longest_request"] <= 1300


def test_token_batches_are_seeded_and_learnable():
    params = {"seq_len": 64, "batch_size": 4}
    one = token_batches.generate(params, 2 ** 31 + 9, 512)
    two = token_batches.generate(params, 2 ** 31 + 9, 512)
    assert np.array_equal(one(3), two(3))
    assert not np.array_equal(one(3), one(4))
    batch = one(0)
    assert batch.shape == (4, 64) and batch.dtype == np.int32
    assert 0 <= batch.min() and batch.max() < 512
    # a chain: most next tokens are a function of the last
    other = token_batches.generate(params, 5, 512)(0)
    assert not np.array_equal(batch, other)
