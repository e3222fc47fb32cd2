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


def _run_tables(B, mp, P, layout):
    """(B, mp) page tables over B * mp pages, slot b's pages b * mp ..:
    in order ("runs": every block of ``P`` entries one run of the pool),
    each block's in reverse ("none": no block of two pages or more is
    one), or every other block's ("mixed")."""
    j = np.arange(mp)
    start = j // P * P
    last = np.minimum(start + P, mp) - 1
    back = start + last - j                   # the block's entries reversed
    flip = {"runs": j < 0, "none": j >= 0, "mixed": j // P % 2 == 1}[layout]
    return (np.arange(B)[:, None] * mp + np.where(flip, back, j)[None]
            ).astype(np.int32)


def _scatter_pages(tables, arrays, seed, parts=1):
    """The same rows at every position of every slot, the pages moved to
    random places of each stacked (layers, n_pages, ...) array and the
    tables rewritten to match: few blocks are left as runs. ``parts``:
    a page stays in its part of that many of the page axis (a shard's)."""
    n = np.asarray(arrays[0]).shape[1]
    rng = np.random.default_rng(seed)
    to = np.concatenate([i * (n // parts) + rng.permutation(n // parts)
                         for i in range(parts)])
    back = np.argsort(to)
    return (to[np.asarray(tables)].astype(np.int32),
            [np.asarray(a)[:, back] for a in arrays])


def _run_words(table, P):
    """The reference of ``_blocked_walk``'s run field, a block of the
    padded walk table (-1: unowned): the first owned page's index in the
    block plus one where the owned pages are consecutive entries holding
    consecutive pages, else 0."""
    out = []
    for blk in np.asarray(table).reshape(-1, P):
        idx = np.flatnonzero(blk >= 0)
        run = idx.size and (np.diff(idx) == 1).all() \
            and (np.diff(blk[idx]) == 1).all()
        out.append(int(idx[0]) + 1 if run else 0)
    return out


@pytest.fixture()
def page_runs():
    """Page tables whose blocks are runs of the pool or not, and the same
    pages scattered (``tables``, ``scatter``), and the run field the walk
    should carry (``words``): for tests of the paged kernels' run copies,
    whose outputs must be bit-equal either way."""
    import types
    return types.SimpleNamespace(tables=_run_tables, scatter=_scatter_pages,
                                 words=_run_words)
