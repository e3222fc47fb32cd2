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


@pytest.fixture()
def dma_interpret(monkeypatch):
    """The Pallas kernels under the TPU interpreter that models each DMA
    and its semaphore (``pltpu.InterpretParams``), with its race detector
    on: a copy waited short of its bytes leaves its rows unlanded, which
    the default interpreter (a copy done when it starts) cannot tell from
    a whole wait. Fails the test if any kernel call found a race, or if
    no kernel ran under it. Every jit cache is emptied on both sides."""
    import jax
    from jax._src.pallas.mosaic.interpret import \
        interpret_pallas_call as tpu_interpret
    from jax.experimental.pallas import tpu as pltpu

    from replicatinggpt_tpu.ops import flash_pallas

    made, real = [], tpu_interpret.RaceDetectionState

    def detector(**kw):
        made.append(real(**kw))
        return made[-1]

    monkeypatch.setattr(tpu_interpret, "RaceDetectionState", detector)
    prev = flash_pallas._INTERPRET
    flash_pallas.set_interpret(pltpu.InterpretParams(detect_races=True))
    jax.clear_caches()
    try:
        yield
    finally:
        flash_pallas.set_interpret(prev)
        jax.clear_caches()
    assert made and not any(r.races_found for r in made)
