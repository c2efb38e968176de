"""Tests of the benchmark itself, on the CPU (not collected by the
repository's tier-1 run, whose test path is ``tests/``):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "bench", kind, f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny(config: str, **sizes) -> dict:
    """A configuration cut to a size a CPU test run can hold."""
    cfg = load("configs", config)
    cfg.update(sizes)
    return cfg
