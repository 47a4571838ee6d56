"""The harness's own tests: CPU, toy sizes. Run from the repo root:
`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session")
def root():
    return ROOT


@pytest.fixture(scope="session")
def manifest(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)
