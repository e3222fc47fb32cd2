"""Test harness config: the CPU backend with 8 virtual devices.

Sharding/collective tests run against CPU XLA with 8 virtual devices
(SURVEY.md §4 implication) — no TPU hardware needed. ``JAX_PLATFORMS=cpu``
is all it takes with the installed jax; the env must be set before jax
first imports.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
# the persistent compile cache stays off under test (entry points turn
# it on; worker subprocesses inherit this too)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
os.environ.setdefault("TOKENIZERS_PARALLELISM", "false")

import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# Pallas kernels run interpreted throughout the suite: the program never
# picks interpret mode from the backend, it has to be asked for. (The
# one file that lowers kernels for real, tests/test_tpu_compile.py,
# turns it off inside its own fixture.)
from replicatinggpt_tpu.ops.flash_pallas import set_interpret

set_interpret(True)

import numpy as np
import pytest


@pytest.fixture(scope="session")
def corpus_text():
    return (REPO / "datasets" / "shakespeare.txt").read_text()


@pytest.fixture(scope="session")
def tiny_corpus(corpus_text):
    return corpus_text[:50_000]


@pytest.fixture()
def lane_tile_blocks(monkeypatch):
    """The paged kernels' walk in blocks of ONE 128-token lane tile
    (``ops.paged_pallas.BLOCK_BYTES`` cut to a byte): at the narrow rows of
    the tests' pools the bytes rule covers a slot's whole table in one
    block, and a test of a walk over MANY blocks asks for this. Every jit
    cache is emptied on both sides: a traced kernel holds its block."""
    import jax

    from replicatinggpt_tpu.ops import paged_pallas

    monkeypatch.setattr(paged_pallas, "BLOCK_BYTES", 1)
    jax.clear_caches()
    yield
    jax.clear_caches()
