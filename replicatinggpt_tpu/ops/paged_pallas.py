"""Paged decode attention: one Pallas kernel family whose scalar-
prefetched page table streams ONLY a slot's mapped pages, a BLOCK of
pages a turn of the slot's own loop.

The XLA paged decode path (models/gpt.py decode_step_paged) gathers
every slot's full (max_pages, page, C) view each layer each step —
simple and parity-exact, but it fetches max_pages pages per slot
regardless of how short the slot's sequence actually is. This kernel
puts the page table in scalar-prefetch SMEM, leaves the WHOLE STACKED
pool (layers, n_pages, page, C) in HBM and FETCHES FOR ITSELF by
``(layer, page)``, so no layer of the pool is sliced out for it. The
grid is ``(B,)``: ONE TURN A SLOT. A slot's pages go in blocks of ``P``
consecutive logical pages (``block_pages``: whole 128-token lane tiles
that carry ``BLOCK_BYTES`` of the pool; 8 pages of 16 for gpt2-large and
K-EXAONE, 16 for LFM2, 32 for a latent row), and the blocks are a LOOP
IN THE BODY as long as the
slot's own live blocks (``_walk_blocks``: one ``fori_loop`` of traced
length over the list ``_blocked_walk`` compacts for the slot), each
copied, scattered as its pages lie in the pool, into one half of a
(2, P * page, C) VMEM double buffer, so the arithmetic reads it as ONE
tile: ONE pass of all of the slot's heads runs once over the block's
columns, not once a head or a
page. Until PR 39 the block axis was the grid's second (B,
ceil(max_pages / P)) and a grid step cost its turn whether or not it
had work: 1.0-1.4 ms of a gpt2-large launch (3.5 ms at no live
token for 2.3 now; PERF.md section 5) went to the two thirds of the
steps past the slots' frontiers. Logical pages past the slot's live
frontier (or behind a window's lower bound, or on another shard) are
NOT OWNED: they are not fetched and their columns are masked, a block
with no owned page is in no slot's list, and the live blocks send for
one another's pages a block ahead, across slots, so a slot at position
p streams ceil(p/page) pages, not max_pages, and an idle slot's turn
is an init and a finalize. Accumulation is online softmax across the
loop (f32 running max / denominator / accumulator in VMEM scratch);
the fresh K/V window rides separately and folds in after the loop,
so the kernel attends the STALE pool bit-equivalently to
write-then-attend (cache[pos] would hold exactly the fresh k/v) — the
caller scatters the fresh row afterwards, mirroring
ops/decode_pallas.py's packed kernel. A block whose owned pages lie
together in the pool, in order (a RUN: what ``serve.pages.PageAllocator``
hands a request's blocks out as), is started as one copy a set bit of its
owned-page count, so a whole block of 32 latent pages is ONE copy; any
other block is started a page at a time. Either way a block is waited by
its bytes: one wait a pool a set bit of the count.

Every process start traces and lowers its programs, compile cache warm
or not, so what the body costs to trace is paid in every cell's set-up:
the body is emitted ONCE (no unrolled loop, no second copy of the block
step for a first or last block), and each entry point runs under one
inner ``jax.jit`` (``_window_call``, ``_gqa_call``), so a program whose
layers are a Python loop holds a kernel once a layer KIND.

Packed (page, C) layout only: heads are static lane slices of the
fully-packed row (no D-minor tile padding in the stream). A block's
step is ONE pass for all of a slot's query rows (``heads_per_pass``):
each head's rows zero outside its own lanes, one score product over
the whole row, one masked online update, one value product: a block's
cost follows the number of its passes (each a chain of dependent MXU
and vector steps), not its bytes or its rows.
Gated to TPU (`_paged_attn_backend_ok`, monkeypatched by tests to
exercise the interpreter on CPU) and to shapes inside
`paged_decode_supported`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental import pallas as pl

from .flash_pallas import (LANES, NEG_INF, _compiler_params,
                           _interpret_mode, _vmem_spec, pltpu)

# VMEM budget: the K and the V tile of ONE BLOCK of pages (P x (page, C)
# each; the double buffer holds two of each), beside the (W, C) rows and
# the f32 accumulators. ``block_pages`` keeps a block inside it and the
# envelope refuses a page that alone is over it. 4 MiB: a block of 8
# gpt2-large pages of 16 tokens bf16 takes 0.64 MiB, and one C=768 page
# of 1024 tokens (a block of one) 3 MiB.
PAGED_DECODE_BYTES = 4 * 1024 * 1024

# a block of a slot's walk carries at least this many bytes of the pool
# (table and budget allowing), in whole ``LANES``-token tiles: a block is
# ONE dependent chain (score product, masked online update, value
# product) with a fixed part, about 0.4 us, that a narrow row pays too
# often at 128 tokens; past it a block's pages (their copies, a slot's
# half-empty last block) cost more than it saves. The kernels alone on a
# TPU v5e at the
# cells' fills (``tools/kernel_fit.py --block-tokens``), ns a live token
# at blocks of 128 / 256 / 512 / 1024 tokens: LFM2's K and V rows (2,048
# B a token) 8.09 / 6.94 / 6.81 / 7.30, the latent row (1,280 B) 7.60 /
# 6.22 / 5.74 / 6.16. Each stops falling by more than 5% past its first
# block of 512 KiB or more (LFM2's 256 tokens, the latent row's 512);
# gpt2-large's and K-EXAONE's 128-token blocks carry 640 and 512 KiB.
BLOCK_BYTES = 512 * 1024

# VMEM share of a turn's stacked query rows (float32, and again in the
# MXU's dtype) and float32 accumulator: 12 B a row a lane. A block's
# step is ONE pass over the whole row where they fit (gpt2-large's 20
# rows x 1,280 lanes at W = 1 take 0.3 MiB, at W = 8 2.5 MiB: two passes
# of 10 heads); ``heads_per_pass`` splits a wider window into whole heads.
PASS_STATE_BYTES = 2 * 1024 * 1024


def _paged_attn_backend_ok() -> bool:
    """Pallas lowering gate (tests monkeypatch this to run the
    interpret-mode kernel on CPU). Sharding safety is a SEPARATE gate:
    ``paged_kernel_mesh_ok`` — the serve engine may now run on a
    (data, model) mesh, where a bare pallas_call cannot partition."""
    return jax.default_backend() == "tpu"


def paged_kernel_mesh_ok(mesh, n_pages=None, n_embd=None,
                         n_head=None) -> bool:
    """Sharding-aware kernel routing. A bare ``pallas_call`` cannot be
    GSPMD-partitioned, but the per-layer windowed kernel now ships a
    ``shard_map`` wrapper (``sharded_paged_window_attention``): each
    chip runs the kernel on its own contiguous page block with the
    scalar-prefetched table localized per shard, partial online-softmax
    state merged across 'data' and heads fully local over 'model'. The
    wrapper needs clean per-shard blocks, so a >1 mesh routes the
    kernel iff the page axis divides over 'data' and channels AND heads
    divide over 'model' (the same divisibility-drop rule
    parallel.mesh.page_pool_pspec applies to the pool specs). Callers
    that cannot supply the geometry get the conservative answer for a
    >1 mesh."""
    if mesh is None or mesh.size == 1:
        return True
    if n_pages is None or n_embd is None or n_head is None:
        return False
    shape = dict(getattr(mesh, "shape", {}))
    data = int(shape.get("data", 1))
    model = int(shape.get("model", 1))
    if data * model != mesh.size:
        return False
    return (n_pages % data == 0 and n_embd % model == 0
            and n_head % model == 0)


def mixed_step_kernel_ok(n_head: int, head_dim: int, page_size: int,
                         itemsize: int = 2, mesh=None,
                         kv_quant: str = "none",
                         granularity: str = "page",
                         n_pages=None) -> bool:
    """Kernel routing for the MIXED prefill+decode window step and the
    speculative verify forward (models.gpt.verify_step_paged): the seam
    PR 12 documented is now FLIPPED — ``paged_window_attention`` walks
    a (W, C) query block per slot, so prefilling slots scatter chunk
    rows through their page tables and decoding slots do the
    verify<->decode row math in ONE kernel launch per layer (same
    ``mode='drop'`` routing as the XLA path; the scatter itself stays
    outside the kernel, exactly like the decode kernels'
    attend-stale-then-write contract). Same envelope as the decode
    kernel — the window width W is a block-shape parameter, not an
    envelope axis (Pallas pads the sublane dim)."""
    ok, _ = paged_attention_envelope(
        n_head, head_dim, page_size, itemsize=itemsize, mesh=mesh,
        kv_quant=kv_quant, granularity=granularity, n_pages=n_pages)
    return ok


def paged_attention_envelope(n_head: int, head_dim: int, page_size: int,
                             *, itemsize: int = 2, mesh=None,
                             kv_quant: str = "none",
                             granularity: str = "page",
                             n_pages=None, n_kv_head=None) -> tuple:
    """THE shared kernel envelope — one set of gate checks consumed by
    every route predicate (``paged_decode_supported``,
    ``mixed_step_kernel_ok``), so the mesh/quant/shape logic cannot
    drift between the decode and the windowed steps. Returns
    ``(ok, reasons)`` — ``reasons`` names every failed
    check (the engine's kernel-route export surfaces them, so a silent
    XLA fallback is observable, not asserted).

    What the unified kernel family now accepts: int8 AND fp8 pools at
    page AND head granularity (per-head scale-lane selection + the
    saturating e4m3 cast run inside the accumulation loop), and >1
    (data, model) meshes through the shard_map wrapper when the pool
    geometry divides (``paged_kernel_mesh_ok``)."""
    reasons = []
    n_kv_head = n_kv_head or n_head
    if n_kv_head != n_head:
        # grouped queries run ``paged_gqa_attention``: one chip, a plain
        # pool, whole groups of query heads to a KV head
        if mesh is not None and mesh.size > 1:
            reasons.append("gqa_mesh")
        if kv_quant != "none":
            reasons.append("gqa_kv_quant")
        if n_head % n_kv_head:
            reasons.append("gqa_group")
    if not paged_kernel_mesh_ok(mesh, n_pages=n_pages,
                                n_embd=n_head * head_dim,
                                n_head=n_head):
        reasons.append("mesh_indivisible")
    if kv_quant not in ("none", "int8", "fp8"):
        reasons.append("kv_quant_unknown")
    if granularity not in ("page", "head"):
        reasons.append("granularity_unknown")
    if head_dim not in (32, 64, 128, 256):
        reasons.append("head_dim")
    if n_head > LANES:
        reasons.append("n_head_gt_lanes")
    if page_size % 8 != 0:
        reasons.append("page_align")
    C = n_kv_head * head_dim          # a page's row is KV heads wide
    if not block_pages(page_size, 1, C * itemsize, 2):
        reasons.append("vmem_budget")     # a block of ONE page is over it
    return (not reasons), tuple(reasons)


def paged_decode_supported(n_head: int, head_dim: int, page_size: int,
                           itemsize: int = 2, mesh=None,
                           kv_quant: str = "none",
                           granularity: str = "page",
                           n_pages=None) -> bool:
    """Per-layer decode-kernel envelope — a thin view over
    ``paged_attention_envelope`` (one shared gate, no drift)."""
    ok, _ = paged_attention_envelope(
        n_head, head_dim, page_size, itemsize=itemsize, mesh=mesh,
        kv_quant=kv_quant, granularity=granularity, n_pages=n_pages)
    return ok


def block_pages(page_size: int, n_table: int, row_bytes: int,
                tiles: int) -> int:
    """``P``: how many consecutive logical pages of a slot one block
    covers. Enough whole ``LANES``-token tiles that the block's ``tiles``
    (K and V: 2; a latent row, key and value at once: 1) of ``row_bytes``
    a token carry ``BLOCK_BYTES``, no more than the table has, and no
    more than keeps them inside ``PAGED_DECODE_BYTES``; 0 where one page
    alone is over it. Follows from shapes: nothing configures it."""
    token_bytes = tiles * row_bytes
    lane_tiles = -(-BLOCK_BYTES // (LANES * token_bytes))
    want = -(-lane_tiles * LANES // page_size)
    fit = PAGED_DECODE_BYTES // (page_size * token_bytes)
    return min(want, n_table, fit)


def _walk_shape(n_table: int, page_size: int, row_bytes: int,
                tiles: int) -> tuple:
    """``(P, n_blocks)``: the pages a block covers and the blocks a
    slot's table of ``n_table`` entries makes (the last may be short)."""
    P = block_pages(page_size, n_table, row_bytes, tiles)
    return P, -(-n_table // P)


# the owned pages of a block as bits, a word of 32 pages (one at every
# cell's block): what ``cols()`` expands to the block's columns
WORD = 32


def _mask_words(n_block: int) -> int:
    return -(-n_block // WORD)


def live_blocks(pos, page_size: int, n_block: int):
    """Blocks of ``n_block`` pages that hold a position < ``pos``, a
    slot: the iterations of the unsharded walk's loop (numpy or jnp; the
    engine's ``kv_blocks_live`` sums it over the live slots)."""
    return -(-pos // (page_size * n_block))


def block_runs(pages: np.ndarray, n_block: int) -> np.ndarray:
    """(n_blocks,) int: how many of its first pages each block of
    ``n_block`` of a slot's table ``pages`` has in ONE run, page i of the
    block at the pool's page ``pages[first] + i`` (numpy, at admission:
    the engine's ``kv_block_runs`` reads a live block as a run while its
    owned pages are no more than this, the rule ``_blocked_walk`` applies
    on the device)."""
    pages = np.asarray(pages, np.int64)
    nb = -(-pages.size // n_block)
    lag = np.full((nb * n_block,), -1, np.int64)
    lag[:pages.size] = pages - np.arange(pages.size) % n_block
    same = lag.reshape(nb, n_block) == lag[::n_block, None]
    return np.where(same.all(axis=1), n_block, np.argmin(same, axis=1))


def block_waits(pos, page_size: int, n_block: int):
    """The DMA waits a pool that the unsharded walk's loop makes for a
    slot at ``pos`` (numpy; the engine's ``kv_block_waits`` sums it over
    the live slots and the pools): a live block waits once a set bit of
    its owned pages' count (``_walk_blocks``), so a whole block of a
    power of two is one wait and a slot's last block one to
    ``n_block.bit_length()``."""
    full, rest = np.divmod(-(-np.asarray(pos) // page_size), n_block)
    return full * int(n_block).bit_count() + np.bitwise_count(rest)


# scalar-prefetch operands ``_walk_blocks`` reads: ``_blocked_walk``'s
# seven and the pool's layer
N_WALK = 8

# a block's word of ``_blocked_walk``'s ``pages``: its owned pages' count
# in the low bits, and above them, where they are one run, the index in
# the block of the first plus one (0: not a run)
RUN_SHIFT = 16
COUNT_MASK = (1 << RUN_SHIFT) - 1


@jax.named_scope("kv_gather")
def _blocked_walk(tables: jnp.ndarray, owned: jnp.ndarray, page_size: int,
                  row_bytes: int, tiles: int) -> tuple:
    """The walk every kernel makes, from a (B, max_pages) table and the
    mask of the entries a slot's rows read: the scalars ``_walk_blocks``
    reads beside the layer, ``(table, blocks, count, rank, next, bits,
    pages)``.
    What is left OUTSIDE the kernel of addressing the pool's pages, hence
    the scope; the same for every layer of a step.

    Block ``p`` of slot ``b`` covers its logical pages p*P .. p*P + P - 1
    (``_walk_shape``). The table and the mask are padded to whole blocks
    with unowned entries (a length P does not divide ends in a short
    block), and the walk's table says which entries are owned: an unowned
    one reads -1 (ONE (B, n_blocks * P) array in scalar memory, not a
    table and a mask: at 256 slots of 512 pages the two were 1.05 MB of a
    v5e's 1 MB). A block is LIVE if it holds an owned page. ``blocks[b * n_blocks + i]`` is
    slot b's i-th live block, in rising order, for i < ``count[b]``: a
    prefix of the table for plain decode, a range behind a window's lower
    bound, any subset under a shard's mask. The live blocks fetch for one
    another across slots, so a slot carries the ``rank`` of its first
    live block among all of the call's (its parity picks the half of the
    double buffer) and the ``next`` slot that has one (-1: none).
    ``bits[(b * n_blocks + p) * words + w]`` holds the owned pages
    ``w * WORD ..`` of block p of slot b, page j in bit j % WORD: the mask
    of a block's columns in as many words as its pages need, however many
    pages they are. ``pages[b * n_blocks + p]`` counts them (its
    ``COUNT_MASK`` bits: what the block's DMA waits add up to) and says
    whether they are a RUN: owned pages j0 .. j0 + n - 1 of the block, at
    table entries ``table[j0] + i``, one slice of the pool; such a block
    holds ``j0 + 1`` above ``RUN_SHIFT``, and the walk starts it as a few
    copies, not one a page."""
    B, mp = tables.shape
    P, nb = _walk_shape(mp, page_size, row_bytes, tiles)
    assert P < 1 << (31 - RUN_SHIFT), P
    pad = ((0, 0), (0, nb * P - mp))
    owned = jnp.pad(owned, pad)
    table = jnp.where(owned, jnp.pad(jnp.asarray(tables, jnp.int32), pad), -1)
    nw = _mask_words(P)
    words = jnp.pad(owned.reshape(B, nb, P),
                    ((0, 0), (0, 0), (0, nw * WORD - P)))
    bit = jnp.left_shift(jnp.uint32(1), jnp.arange(WORD, dtype=jnp.uint32))
    bits = jnp.sum(jnp.where(words.reshape(B, nb, nw, WORD), bit,
                             jnp.uint32(0)), axis=3, dtype=jnp.uint32)
    own = owned.reshape(B, nb, P)
    pages = own.sum(axis=2, dtype=jnp.int32)
    live = pages > 0
    # a run: its owned pages' entries less their index in the block all
    # one number, and the first and the last owned page n - 1 apart
    lag = table.reshape(B, nb, P) - jnp.arange(P, dtype=jnp.int32)
    big = jnp.iinfo(jnp.int32).max
    first = jnp.argmax(own, axis=2).astype(jnp.int32)
    last = P - 1 - jnp.argmax(own[..., ::-1], axis=2).astype(jnp.int32)
    run = (live & (last - first + 1 == pages)
           & (jnp.min(jnp.where(own, lag, big), axis=2)
              == jnp.max(jnp.where(own, lag, -big), axis=2)))
    word = pages | jnp.where(run, (first + 1) << RUN_SHIFT, 0)
    # a slot's i-th live block is the first with i + 1 live blocks up to
    # and with it: as many blocks as have i or fewer (no sort)
    upto = jnp.cumsum(live, axis=1, dtype=jnp.int32)
    blocks = jnp.sum(upto[:, None, :] <= jnp.arange(nb)[None, :, None],
                     axis=2, dtype=jnp.int32)
    count = upto[:, -1]
    slot = jnp.arange(B, dtype=jnp.int32)
    later = jax.lax.cummin(jnp.where(count > 0, slot, B), reverse=True)
    nxt = jnp.concatenate([later[1:], jnp.full((1,), B, jnp.int32)])
    return (table, blocks.reshape(B * nb), count,
            jnp.cumsum(count, dtype=jnp.int32) - count,
            jnp.where(nxt < B, nxt, -1),
            jax.lax.bitcast_convert_type(bits, jnp.int32).reshape(-1),
            word.reshape(B * nb))


def window_walk(tables: jnp.ndarray, pos: jnp.ndarray, page_size: int,
                row_bytes: int, tiles: int, owned=None) -> tuple:
    """The walk over ``tables`` at positions ``pos`` (``_blocked_walk``'s
    scalars) of a kernel that fetches ``tiles`` arrays of pages
    ``row_bytes`` a token wide (``paged_window_attention``: K and V). It
    depends on no layer: a caller whose layers run in one scan
    builds it ONCE a step outside the scan and hands it to every
    layer's call (``walk=``), where XLA would rebuild it a layer.
    ``owned`` as in ``paged_window_attention``."""
    pos = jnp.asarray(pos, jnp.int32)
    if owned is None:
        owned = gqa_owned_pages(pos, jnp.zeros_like(pos), tables.shape[1],
                                page_size, 0)
    return _blocked_walk(tables, owned, page_size, row_bytes, tiles)


def heads_per_pass(n_head: int, rows: int, head_dim: int) -> int:
    """Heads one pass over a block takes together: the most that divide
    ``n_head`` and keep the stacked query rows (float32 and the MXU's
    dtype) and the float32 accumulator of all passes, ``n_head * rows``
    rows of a pass's lanes, inside ``PASS_STATE_BYTES`` (``rows``: a
    head's query rows, W, or G * W for grouped queries). Every head of
    every cell in one pass; a wide window splits into whole heads.
    Follows from shapes: nothing configures it."""
    fit = PASS_STATE_BYTES // (12 * n_head * rows * head_dim)
    return max(h for h in range(1, n_head + 1)
               if n_head % h == 0 and (h == 1 or h <= fit))


def block_passes(n_head: int, head_dim: int, window: int = 1,
                 n_kv_head=None) -> int:
    """Passes a block of a slot's loop takes: one score product, one
    masked online update and one value product each (the engine's
    ``kv_block_passes`` is the live blocks times this)."""
    n_kv = n_kv_head or n_head
    return n_kv // heads_per_pass(n_kv, n_head // n_kv * window, head_dim)


# -- what a block step does, shared by both kernels --------------------------

def _walk_blocks(walk, pools, bufs, sem, n_block: int, n_blocks: int,
                 page_size: int, step) -> None:
    """This grid turn's slot: ONE loop over the slot's own live blocks,
    each fetched by the kernel itself and handed to ``step(p, half,
    cols)`` (block ``p`` of the slot's table, in half ``half`` of the
    buffers).

    ``pools`` are the pool's stacked arrays left in HBM as rows, (layers,
    n_pages * page, width) (``_pool_operands``), addressed in place by
    ``(layer, page * page_size)``; ``bufs`` their (2, P * page, width)
    VMEM halves. A block finds its owned
    pages already on their way into half ``rank % 2`` (the live block
    before it sent for them; the call's first sends for its own), sends
    for the NEXT live block's into the other half, then waits for its
    own: the copies of block t + 1 run under the arithmetic of block t.
    A slot's last block sends for the first block of the next slot that
    has one, so the buffers do not drain at a slot's edge.
    A block whose owned pages are a RUN (``_blocked_walk``: one slice of
    the pool, in order) starts one copy of ``2**k`` pages a set bit ``k``
    of their count, any other block one copy a page; either way a block
    is waited by its bytes, not a copy at a time: all of a block's copies
    into a half signal that half's semaphore of the pool, a DMA wait
    takes its descriptor's bytes off it, and only the block's own copies
    are in flight there (the next block's go to the other half), so waits
    whose rows add up to the block's owned pages (its count in the walk's
    scalars) release exactly when they have all landed. A size is static,
    so it is one wait of ``2**k`` pages a set bit ``k`` of the count: one
    a pool for a whole block of a power of two, ``n_block.bit_length()``
    at most.
    Unowned pages are not fetched, and ``cols()`` masks their columns:
    what a half holds there is an older page or the zeros of the
    call's first turn. A slot with no live block reads its count here
    and copies nothing."""
    (table_ref, blocks_ref, count_ref, rank_ref, next_ref, bits_ref,
     pages_ref, layer_ref) = walk
    P, psz, nw = n_block, page_size, _mask_words(n_block)
    b = pl.program_id(0)
    n = count_ref[b]

    @pl.when(b == 0)
    def _finite():
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)

    def start(slot, p, half):
        word = pages_ref[slot * n_blocks + p]
        j0 = (word >> RUN_SHIFT) - 1          # -1: not a run

        def copy(src, dst, n_pages):
            # pool pages src .. into the block's pages dst .., a static
            # number of them
            rows = n_pages * psz
            for a, (pool, buf) in enumerate(zip(pools, bufs)):
                pltpu.make_async_copy(
                    pool.at[layer_ref[0],
                            pl.ds(pl.multiple_of(src * psz, psz), rows)],
                    buf.at[half, pl.ds(pl.multiple_of(dst * psz, psz), rows)],
                    sem.at[half, a]).start()

        @pl.when(j0 >= 0)
        def _run():
            count = word & COUNT_MASK
            src = table_ref[slot, p * P + j0]
            for k in range(P.bit_length()):
                @pl.when((count >> k) & 1 == 1)
                def _bit():
                    off = count & ((1 << k) - 1)   # the lower bits' pages
                    copy(src + off, j0 + off, 1 << k)

        @pl.when(j0 < 0)
        def _pages():
            def page(j, _):
                @pl.when(table_ref[slot, p * P + j] >= 0)
                def _owned():
                    copy(table_ref[slot, p * P + j], j, 1)

            # a loop, not P copies of the body: the program is traced and
            # lowered at every process start, and these are most of its
            # size
            jax.lax.fori_loop(0, P, page, None)

    def wait(half, n_pages):
        # a wait of 2**k pages' rows a set bit k of the owned count: the
        # rows only size the descriptor, whose bytes come off the semaphore
        for k in range(P.bit_length()):
            @pl.when((n_pages >> k) & 1 == 1)
            def _bit():
                for a, buf in enumerate(bufs):
                    rows = buf.at[half, pl.ds(0, psz << k)]
                    pltpu.make_async_copy(rows, rows, sem.at[half, a]).wait()

    def block(i, _):
        p = blocks_ref[b * n_blocks + i]
        rank = rank_ref[b] + i
        half = rank % 2

        @pl.when(rank == 0)
        def _first():
            start(b, p, half)

        last = i + 1 == n
        ahead = jnp.where(last, next_ref[b], b)

        @pl.when(ahead >= 0)
        def _ahead():
            start(ahead,
                  blocks_ref[ahead * n_blocks + jnp.where(last, 0, i + 1)],
                  1 - half)

        wait(half, pages_ref[b * n_blocks + p] & COUNT_MASK)

        def cols():
            # a column's bit in the block's words: a few vector steps a
            # word of 32 pages, not one a page, so that neither the
            # program's size nor a block's work follows P
            page = jax.lax.broadcasted_iota(jnp.int32, (1, P * psz), 1) // psz

            def word(w, own):
                bits = bits_ref[(b * n_blocks + p) * nw + w]
                hit = jax.lax.shift_right_logical(
                    jnp.full_like(page, bits), page % WORD) & 1
                return jnp.where(page // WORD == w, hit, own)

            return jax.lax.fori_loop(0, nw, word, jnp.zeros_like(page),
                                     unroll=nw == 1) > 0

        step(p, half, cols)

    jax.lax.fori_loop(0, n, block, None)


def _scores(q, k, mask, scale):
    """(R, T) float32 scores of q rows (R, L) on k rows (T, L), NEG_INF
    where ``mask`` is off; ``scale`` a number or a tile that broadcasts
    to (R, T). Operands of one dtype go to the MXU as they are (bf16
    products are exact in the float32 accumulator)."""
    if q.dtype != k.dtype:
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return jnp.where(mask, s, NEG_INF)


def _online_update(s, v, acc_ref, m_ref, l_ref, i: int, v_scale=None):
    """One online-softmax step of state ``i``: fold masked scores ``s``
    (R, T) and values ``v`` (T, L) into acc (R, L) and the running max
    and denominator (R, LANES; every lane the same). ``v_scale`` (R, T)
    or (1, T) weighs the value product's probabilities, not the
    denominator's: a quantized tile's scale of a row's own head."""
    m_prev = m_ref[i]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # masked columns contribute EXACTLY zero (not exp(0)): with a
    # fully-masked row m_new stays NEG_INF and s - m_new == 0
    pexp = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new[:, :1]), 0.0)
    l_ref[i] = l_ref[i] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
    if v_scale is not None:
        pexp = pexp * v_scale
    acc_ref[i] = (acc_ref[i] * alpha[:, :1]
                  + jax.lax.dot_general(
                      pexp, v.astype(jnp.float32),
                      (((1,), (0,)), ((), ())),
                      preferred_element_type=jnp.float32))
    m_ref[i] = m_new


def _paged_window_kernel(*refs, n_head, head_dim, page_size, n_block,
                         n_blocks, window, scale, quantized, head_gran,
                         fold):
    """ONE kernel body for the whole paged-attention family.

    W = ``window`` query rows per slot (W=1 is plain decode; W>1 is the
    mixed prefill+decode / speculative-verify step, where row j sits at
    logical position pos+j). A grid turn is a slot, and a turn of its loop
    holds a BLOCK of ``n_block`` stale pool pages as one (n_block * page,
    C) tile (``_walk_blocks``).
    Blocks accumulate online-softmax gated on the scalar-prefetched
    OWNED mask (per-slot page prefix unsharded; an arbitrary owned
    subset under the shard_map wrapper), their columns masked page by
    page by it and to positions < pos — identical for every query row,
    since rows 0..W-1 attend the fresh window via the causal fold.
    The turn stacks the W rows of every head into ONE (heads * W, C)
    query block, head h's rows zero outside its own lanes, so ONE
    product scores all of the slot's heads over the block's columns, ONE
    masked online update folds them and ONE more product weighs the
    whole value tile; of a row's accumulator only its own head's lanes
    are ever read. A wide window splits the heads into whole-head passes
    (``heads_per_pass``), each over its own lanes.
    Quantized pools bring the slot's scales as one more operand, a
    block's (1 or H, n_block * page) tile a token a lane: the scores and
    the value product's probabilities of a row take its own head's
    scale, the tiles go to the product as stored (int8 AND fp8 — the
    e4m3 block ``astype``s to f32 like any other storage dtype).

    ``fold=True`` folds the fresh causal (W, W) block after the loop and
    writes normalized output; ``fold=False`` emits the raw
    (acc, m, l) partials instead — the shard_map wrapper merges them
    across the 'data' axis (pmax/psum softmax merge) and folds the
    fresh window outside, where the collective lives."""
    walk = refs[:N_WALK]
    pos_ref, q_ref, knew_ref, vnew_ref, *refs = refs[N_WALK:]
    if quantized:
        ksc_ref, vsc_ref, *refs = refs
    k_hbm, v_hbm, *refs = refs
    *outs, q32_ref, qs_ref, acc_ref, m_ref, l_ref, k_buf, v_buf, sem = refs
    pools, bufs = (k_hbm, v_hbm), (k_buf, v_buf)
    b = pl.program_id(0)
    D, psz, W, T = head_dim, page_size, window, n_block * page_size
    n_pass, R, L = acc_ref.shape         # R = hpp * W rows of L lanes
    hpp = L // D
    passes = [slice(i * L, (i + 1) * L) for i in range(n_pass)]
    pos = pos_ref[b]
    _clear_state(acc_ref, m_ref, l_ref)
    if W == 1:       # a head's one row: the slot's row, its head's lanes
        mine = (jax.lax.broadcasted_iota(jnp.int32, (R, L), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (R, L), 1) // D)
        for i, lanes in enumerate(passes):
            q = jnp.where(mine, q_ref[:, lanes].astype(jnp.float32), 0.0)
            q32_ref[i] = q
            qs_ref[i] = q.astype(qs_ref.dtype)
    else:
        _stack_rows(q32_ref, qs_ref, [
            (i, r * W, r * D, q_ref[:, h * D:(h + 1) * D])
            for h in range(n_head) for i, r in [divmod(h, hpp)]])

    def own_head(sc, i):     # scales (1 or H, T) -> a row's own head's
        if not head_gran:
            return sc
        head = jax.lax.broadcasted_iota(jnp.int32, (R, T), 0) // W
        return functools.reduce(
            lambda a, r: jnp.where(head == r, sc[i * hpp + r:
                                                 i * hpp + r + 1], a),
            range(1, hpp), sc[i * hpp:i * hpp + 1])

    def _accumulate(p, half, owned_cols):
        kpos = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1) + p * T
        mask = owned_cols() & (kpos < pos)                   # (1, T)
        for i, lanes in enumerate(passes):
            k, v = bufs[0][half, :, lanes], bufs[1][half, :, lanes]
            if quantized:            # the block's scales: (1 or H, T)
                s = _scores(qs_ref[i], k, mask,
                            scale * own_head(ksc_ref[p], i))
                _online_update(s, v, acc_ref, m_ref, l_ref, i,
                               own_head(vsc_ref[p], i))
            else:
                _online_update(_scores(qs_ref[i], k, mask, scale), v,
                               acc_ref, m_ref, l_ref, i)

    _walk_blocks(walk, pools, bufs, sem, n_block, n_blocks, psz,
                 _accumulate)

    row_j = jax.lax.broadcasted_iota(jnp.int32, (R, W), 0) % W
    col = jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)
    causal = col <= row_j              # fresh row j attends rows 0..j
    for i, lanes in enumerate(passes):
        if fold:
            # denominator >= the diagonal term > 0 always (row j
            # attends itself)
            s_new = _scores(q32_ref[i], knew_ref[:, lanes], causal, scale)
            _online_update(s_new, vnew_ref[:, lanes], acc_ref, m_ref, l_ref,
                           i)
        if W == 1 and fold:  # each lane from its own head's one row: a
            # masked sum down the rows and ONE store, not a store a head
            outs[0][:, lanes] = jnp.sum(
                jnp.where(mine, acc_ref[i] / l_ref[i][:, :1], 0.0), axis=0,
                keepdims=True).astype(outs[0].dtype)
            continue
        for r in range(hpp):           # a head's rows, its own lanes
            h = i * hpp + r
            rows, own = slice(r * W, (r + 1) * W), slice(h * D, (h + 1) * D)
            acc = acc_ref[i, rows, r * D:(r + 1) * D]
            if fold:
                outs[0][:, own] = (acc / l_ref[i, rows, :1]).astype(
                    outs[0].dtype)
            else:
                outs[0][:, own] = acc
                outs[1][:, h:h + 1] = m_ref[i, rows, h:h + 1]
                outs[2][:, h:h + 1] = l_ref[i, rows, h:h + 1]


def _clear_state(acc_ref, m_ref, l_ref) -> None:
    """A turn's start: the online-softmax state of every pass cleared."""
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _stack_rows(q32_ref, qs_ref, pieces) -> None:
    """The slot's query rows stacked a pass at a time, each head's rows
    zero outside its own lanes. ``pieces`` are ``(pass, row, lane,
    rows)``: a head's query rows and where they go. ``q32_ref`` keeps
    them in float32 for the fresh window's fold, ``qs_ref`` in the dtype
    the block products take (cast once a turn, not once a block)."""
    q32_ref[...] = jnp.zeros_like(q32_ref)
    for i, r0, c0, q in pieces:
        n, d = q.shape
        q32_ref[i, r0:r0 + n, c0:c0 + d] = q.astype(jnp.float32)
    qs_ref[...] = q32_ref[...].astype(qs_ref.dtype)


def _state_scratch(n_head: int, rows: int, head_dim: int,
                   mxu_dtype) -> list:
    """A turn's state, ``(passes, heads_per_pass * rows, lanes)`` each:
    the stacked query rows in float32 and in ``mxu_dtype`` (the pool's
    own dtype where the query has it: the MXU takes the stored bf16 as it
    is), the float32 accumulator, and the running max and denominator a
    row (``LANES`` wide, every lane the same). ``n_head`` heads of
    ``rows`` query rows and ``head_dim`` lanes each."""
    hpp = heads_per_pass(n_head, rows, head_dim)
    state = (n_head // hpp, hpp * rows)
    lanes = hpp * head_dim
    return [pltpu.VMEM((*state, lanes), jnp.float32),
            pltpu.VMEM((*state, lanes), mxu_dtype),
            pltpu.VMEM((*state, lanes), jnp.float32),
            pltpu.VMEM((*state, LANES), jnp.float32),
            pltpu.VMEM((*state, LANES), jnp.float32)]


def _pool_operands(arrays, n_block: int) -> tuple:
    """``(operands, in_specs, scratch_shapes)`` for stacked pool arrays
    (layers, n_pages, page, width) the kernel fetches itself: each viewed
    as rows, (layers, n_pages * page, width), so that a run of pages is
    one slice of it (a bitcast, not a copy: a page is whole tiles of its
    rows), left in HBM; a (2, P * page, width) VMEM double buffer each,
    and a DMA semaphore a half an array."""
    return ([a.reshape(a.shape[0], -1, a.shape[3]) for a in arrays],
            [pl.BlockSpec(memory_space=pltpu.HBM)] * len(arrays),
            [pltpu.VMEM((2, n_block * a.shape[2], a.shape[3]), a.dtype)
             for a in arrays]
            + [pltpu.SemaphoreType.DMA((2, len(arrays)))])


def paged_window_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                           v_new: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, tables: jnp.ndarray,
                           pos: jnp.ndarray, *, n_head: int, layer,
                           k_scales=None, v_scales=None, owned=None,
                           fold: bool = True, walk=None):
    """Windowed paged attention for one layer of a packed pool — the
    SINGLE entry point behind every per-layer engine route.

    q, k_new, v_new: (B, W, C) fresh merged window rows (row j of slot
    b sits at logical position ``pos[b] + j``; callers pad dead rows —
    garbage-in-garbage-out, the diagonal fold keeps them NaN-free);
    k_pages/v_pages: the WHOLE stacked (layers, n_pages, page, C) STALE
    pool (positions >= pos not yet written) and ``layer`` the scalar
    (traced inside a layer scan) that says which of its layers to read:
    the kernel copies page ``(layer, table entry)``, nothing is sliced
    out first; tables: (B, max_pages) int32; pos: (B,) int32.
    Returns (B, W, C) — bit-equivalent to scattering the window rows at
    pos..pos+W-1 and attending causally, because stale-pool history is
    masked to positions < pos and the in-window positions are covered
    by the causal fresh fold (write-then-attend == attend-stale-then-
    fold, the same contract the W=1 decode kernel always had).

    ``k_scales``/``v_scales`` mark a QUANTIZED pool — stacked like the
    pages, (layers, n_pages, page) f32 at page granularity or (layers,
    n_pages, page, H) at head granularity
    (int8 or fp8 storage; the kernel only ever sees f32 scale blocks
    and ``astype``s the e4m3 pages like any storage dtype). The caller
    passes window rows already fake-quantized so the fresh fold attends
    exactly what the post-kernel scatter stores.

    ``owned`` (B, max_pages) bool is the shard_map wrapper's seam: the
    table entries THIS call may read (with ``fold=False`` it returns
    raw (acc, m, l) partials for the cross-'data' softmax merge); plain
    callers leave it unset and get the prefix of pages that hold a
    position < pos. ``walk`` is ``window_walk`` of the same tables,
    positions and pool, built by a caller that runs many layers on it;
    unset, it is built here."""
    return _window_call(q, k_new, v_new, k_pages, v_pages, tables, pos,
                        layer, k_scales, v_scales, owned, walk,
                        n_head=n_head, fold=fold,
                        interpret=_interpret_mode())


# Both kernels run under ONE inner ``jax.jit`` each, static in what makes
# a kernel a different kernel (head counts, ``attn_window``, ``name``; the
# shapes are the trace cache's own key) and traced in ``layer``: a
# program whose layers are a Python loop traces and lowers a kernel once
# a layer KIND, and every further layer of the kind is a call of the one
# private function (XLA inlines it: the compiled program is the same).
# ``interpret`` is static so that a trace never outlives the mode it was
# made in.

@functools.partial(jax.jit, static_argnames=("n_head", "fold", "interpret"))
def _window_call(q, k_new, v_new, k_pages, v_pages, tables, pos, layer,
                 k_scales, v_scales, owned, walk, *, n_head, fold,
                 interpret):
    _, _, psz, C = k_pages.shape
    B, W, _ = q.shape
    D = C // n_head
    quantized = k_scales is not None
    head_gran = quantized and k_scales.ndim == 4
    pos = jnp.asarray(pos, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    row_bytes = C * k_pages.dtype.itemsize
    P, nb = _walk_shape(tables.shape[1], psz, row_bytes, 2)
    if walk is None:
        walk = window_walk(tables, pos, psz, row_bytes, 2, owned)
    kernel = functools.partial(
        _paged_window_kernel, n_head=n_head, head_dim=D, page_size=psz,
        n_block=P, n_blocks=nb, window=W, scale=D ** -0.5,
        quantized=quantized, head_gran=head_gran, fold=fold)

    def row_map(b, *_):
        return (b, 0, 0)

    row = _vmem_spec((None, W, C), row_map)
    in_specs, inputs = [row, row, row], [q, k_new, v_new]
    if quantized:
        # a pool's scales are a 64th of its bytes or less: XLA gathers
        # each slot's by (layer, page) in the order of its (padded)
        # table, and they arrive as ONE (blocks, width, block tokens)
        # operand a slot, a token a lane, which the loop indexes by block
        T, swidth = P * psz, n_head if head_gran else 1
        in_specs += [_vmem_spec((None, nb, swidth, T),
                                lambda b, *_: (b, 0, 0, 0))] * 2
        with jax.named_scope("kv_gather"):
            inputs += [jnp.swapaxes(sc[layer, jnp.maximum(walk[0], 0)]
                                    .reshape(B, nb, T, swidth), 2, 3)
                       for sc in (k_scales, v_scales)]
    pools, pool_specs, pool_scratch = _pool_operands([k_pages, v_pages], P)
    scratch = _state_scratch(n_head, W, D, q.dtype if not quantized
                             and q.dtype == k_pages.dtype else jnp.float32)
    if fold:
        out_specs = row
        out_shape = jax.ShapeDtypeStruct((B, W, C), q.dtype)
    else:
        rowL = _vmem_spec((None, W, LANES), row_map)
        out_specs = [_vmem_spec((None, W, C), row_map), rowL, rowL]
        out_shape = [jax.ShapeDtypeStruct((B, W, C), jnp.float32),
                     jax.ShapeDtypeStruct((B, W, LANES), jnp.float32),
                     jax.ShapeDtypeStruct((B, W, LANES), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=N_WALK + 1,
        grid=(B,),
        in_specs=in_specs + pool_specs,
        out_specs=out_specs,
        scratch_shapes=scratch + pool_scratch,
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        name="paged_window_attention", interpret=interpret,
        compiler_params=_compiler_params(0, 1),
    )(*walk, layer.reshape(1), pos, *inputs, *pools)


def paged_decode_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                           v_new: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, tables: jnp.ndarray,
                           pos: jnp.ndarray, *, n_head: int, layer,
                           k_scales=None, v_scales=None) -> jnp.ndarray:
    """Decode attention for one layer of a paged packed pool — the
    W=1 view of :func:`paged_window_attention` (kept as the named
    decode entry point; a single-row window's causal fold degenerates
    to the scalar fresh-column fold of the original decode kernel).

    q, k_new, v_new: (B, C) fresh merged rows. Returns (B, C) —
    bit-equivalent to scattering k_new/v_new at ``pos`` and attending
    positions <= pos; the caller scatters afterwards."""
    return paged_window_attention(
        q[:, None, :], k_new[:, None, :], v_new[:, None, :],
        k_pages, v_pages, tables, pos, n_head=n_head, layer=layer,
        k_scales=k_scales, v_scales=v_scales)[:, 0, :]


def _fold_fresh_window(acc: jnp.ndarray, m: jnp.ndarray, l: jnp.ndarray,
                       q: jnp.ndarray, k_new: jnp.ndarray,
                       v_new: jnp.ndarray, n_head: int) -> jnp.ndarray:
    """Fold the fresh causal (W, W) window into raw kernel partials —
    the jnp twin of the kernel's ``fold=True`` finalize, run by the
    shard_map wrapper AFTER the cross-'data' merge (the fresh rows are
    replicated over 'data'; folding them per shard before the psum
    would double-count). acc: (B, W, C) f32; m/l: (B, W, LANES) f32
    with per-head state in columns :n_head."""
    B, W, C = q.shape
    D = C // n_head
    qh = q.astype(jnp.float32).reshape(B, W, n_head, D)
    knh = k_new.astype(jnp.float32).reshape(B, W, n_head, D)
    vnh = v_new.astype(jnp.float32).reshape(B, W, n_head, D)
    s_new = jnp.einsum("bwhd,bjhd->bhwj", qh, knh) * D ** -0.5
    causal = (jnp.arange(W)[None, :]
              <= jnp.arange(W)[:, None])[None, None]   # col <= row
    s_new = jnp.where(causal, s_new, NEG_INF)
    m_h = jnp.swapaxes(m[..., :n_head], 1, 2)          # (B, H, W)
    l_h = jnp.swapaxes(l[..., :n_head], 1, 2)
    m2 = jnp.maximum(m_h, jnp.max(s_new, axis=-1))
    alpha = jnp.exp(m_h - m2)
    p_new = jnp.where(causal, jnp.exp(s_new - m2[..., None]), 0.0)
    # denom >= diagonal term > 0 always (row j attends itself)
    denom = l_h * alpha + jnp.sum(p_new, axis=-1)
    acch = jnp.swapaxes(acc.reshape(B, W, n_head, D), 1, 2)
    out = (acch * alpha[..., None]
           + jnp.einsum("bhwj,bjhd->bhwd", p_new, vnh)) / denom[..., None]
    return jnp.swapaxes(out, 1, 2).reshape(B, W, C)


def sharded_paged_window_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                                   v_new: jnp.ndarray,
                                   k_pages: jnp.ndarray,
                                   v_pages: jnp.ndarray,
                                   tables: jnp.ndarray, pos: jnp.ndarray,
                                   *, n_head: int, mesh, layer,
                                   k_scales=None, v_scales=None):
    """:func:`paged_window_attention` over a (data, model) serve mesh,
    the same operands: the stacked pool and the ``layer`` scalar, which
    every shard gets whole (the layer axis is never sharded, so a
    shard's block is addressed ``(layer, local page)`` like the bare
    call's).

    ``shard_map`` runs the kernel per chip: the pool's page axis splits
    over 'data' (each shard holds a contiguous physical block of
    ``n_pages // data`` pages), channels/heads split over 'model'
    (heads are whole per shard — ``paged_kernel_mesh_ok`` gates on
    that), and the replicated page table is LOCALIZED per shard — a
    shard owns a logical page iff its physical index lands in the
    shard's block; the owned mask says which pages of a block this
    shard copies and masks the other pages' columns, so the contract
    survives arbitrary owned subsets (a slot's pages interleave across
    shards under allocation churn). Each shard emits raw (acc, m, l)
    partials (``fold=False``); the online-softmax merge across 'data'
    is exact — pmax the maxima,
    rescale, psum — and the fresh causal window folds once afterwards
    on the merged state ('model' needs no collective: heads are fully
    local). Output matches the unsharded kernel to f32 merge order."""
    P = jax.sharding.PartitionSpec
    shape = dict(mesh.shape)
    data = int(shape.get("data", 1))
    model = int(shape.get("model", 1))
    _, N, psz, C = k_pages.shape
    mp = tables.shape[1]
    N_loc = N // data
    H_loc = n_head // model
    quantized = k_scales is not None
    head_gran = quantized and k_scales.ndim == 4
    d_ax = "data" if data > 1 else None
    m_ax = "model" if model > 1 else None
    qspec = P(None, None, m_ax)
    pspec = P(None, d_ax, None, m_ax)

    def local_fn(q_l, kn_l, vn_l, kp_l, vp_l, tab, pos_l, layer_l, *scales):
        ks_l, vs_l = scales if scales else (None, None)
        lo = jax.lax.axis_index("data") * N_loc
        live = (pos_l + psz - 1) // psz
        p_idx = jnp.arange(mp, dtype=jnp.int32)[None, :]
        tab = jnp.asarray(tab, jnp.int32)
        owned = ((p_idx < live[:, None]) & (tab >= lo)
                 & (tab < lo + N_loc))
        acc, m_, l_ = paged_window_attention(
            q_l, kn_l, vn_l, kp_l, vp_l, tab - lo, pos_l, n_head=H_loc,
            layer=layer_l, k_scales=ks_l, v_scales=vs_l, owned=owned,
            fold=False)
        # exact cross-shard online-softmax merge: max, rescale, sum
        m_g = jax.lax.pmax(m_, "data")
        corr = jnp.exp(m_ - m_g)      # 1 where both stayed NEG_INF
        l_g = jax.lax.psum(l_ * corr, "data")
        D = (C // model) // H_loc
        corr_c = jnp.repeat(corr[..., :H_loc], D, axis=-1)
        acc_g = jax.lax.psum(acc * corr_c, "data")
        return _fold_fresh_window(acc_g, m_g, l_g, q_l, kn_l, vn_l,
                                  H_loc).astype(q_l.dtype)

    in_specs = [qspec, qspec, qspec, pspec, pspec, P(), P(), P()]
    args = [q, k_new, v_new, k_pages, v_pages,
            jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(layer, jnp.int32)]
    if quantized:
        sspec = (P(None, d_ax, None, m_ax) if head_gran
                 else P(None, d_ax, None))
        in_specs += [sspec, sspec]
        args += [k_scales, v_scales]
    return shard_map(local_fn, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=qspec, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# grouped queries, and a lower bound on the positions read
#
# The family above has one head count on both sides (C = n_head * D for q
# and for a page's row). A grouped-query model's page row is n_kv_head * D
# wide and G = n_head // n_kv_head query heads read each KV head, so the
# kernel below stacks every KV head's G * W query rows into ONE
# (n_kv_head * G * W, n_kv_head * D) block, a group's rows zero outside
# its KV head's lanes: a block costs one score product and one value
# product over the whole row, as in the family above. ``attn_window``
# bounds the positions a row reads from below (row j at position pos + j
# attends k with pos + j - window < k <= pos + j): pages wholly behind the
# bound are unowned and skipped exactly like pages past the frontier, at
# the walk's other end. ``page0`` gives the absolute logical
# page of a slot's first table entry, so the same walk serves a per-slot
# RING of pages (a window layer's bounded state) as well as the pool.
# Both are static: gpt2's programs never reach this kernel (their owned
# prefix is ``gqa_owned_pages`` with neither).


def _paged_gqa_kernel(*refs, n_kv_head, head_dim, page_size, n_block,
                      n_blocks, window, attn_window, scale):
    walk = refs[:N_WALK]
    (pos_ref, page0_ref, q_ref, knew_ref, vnew_ref, k_hbm, v_hbm, out_ref,
     q32_ref, qs_ref, acc_ref, m_ref, l_ref, k_buf, v_buf,
     sem) = refs[N_WALK:]
    pools, bufs = (k_hbm, v_hbm), (k_buf, v_buf)
    b = pl.program_id(0)
    D, psz, W, T = head_dim, page_size, window, n_block * page_size
    GW = q_ref.shape[1]                       # G * W rows a KV head
    n_pass, R, L = acc_ref.shape              # R = KV heads a pass * GW
    hk = L // D
    passes = [slice(i * L, (i + 1) * L) for i in range(n_pass)]
    pos = pos_ref[b]
    # row r of a pass is query row j = r % W of the window
    row_j = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) % W
    _clear_state(acc_ref, m_ref, l_ref)
    _stack_rows(q32_ref, qs_ref, [
        (i, r * GW, r * D, q_ref[g])
        for g in range(n_kv_head) for i, r in [divmod(g, hk)]])

    def _accumulate(p, half, owned_cols):
        kpos = (jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
                + (page0_ref[b] + p * n_block) * psz)
        mask = owned_cols() & (kpos < pos)                   # (1, T)
        if attn_window:
            mask = mask & (kpos > pos + row_j - attn_window)  # (R, T)
        for i, lanes in enumerate(passes):
            s = _scores(qs_ref[i], bufs[0][half, :, lanes], mask, scale)
            _online_update(s, bufs[1][half, :, lanes], acc_ref, m_ref,
                           l_ref, i)

    _walk_blocks(walk, pools, bufs, sem, n_block, n_blocks, psz,
                 _accumulate)

    col = jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)
    fresh = col <= row_j               # row j attends fresh rows 0..j
    if attn_window:
        fresh = fresh & (col > row_j - attn_window)
    for i, lanes in enumerate(passes):
        # denominator >= the diagonal term > 0 always (row j attends
        # itself)
        s_new = _scores(q32_ref[i], knew_ref[:, lanes], fresh, scale)
        _online_update(s_new, vnew_ref[:, lanes], acc_ref, m_ref, l_ref, i)
        for r in range(hk):            # a KV head's rows, its own lanes
            rows = slice(r * GW, (r + 1) * GW)
            out_ref[i * hk + r] = (acc_ref[i, rows, r * D:(r + 1) * D]
                                   / l_ref[i, rows, :1]).astype(
                                       out_ref.dtype)


@jax.named_scope("kv_gather")
def gqa_owned_pages(pos: jnp.ndarray, page0: jnp.ndarray, n_table: int,
                    page_size: int, attn_window: int) -> jnp.ndarray:
    """(B, n_table) bool: the table entries whose page holds a position
    the window's rows read from the STALE state: some k < pos and, under
    a window, some k > pos - attn_window (row 0's bound, the lowest)."""
    a = page0[:, None] + jnp.arange(n_table, dtype=jnp.int32)[None, :]
    owned = a * page_size < pos[:, None]
    if attn_window:
        owned &= (a + 1) * page_size - 1 > pos[:, None] - attn_window
    return owned & (a >= 0)


def paged_gqa_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                        v_new: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, tables: jnp.ndarray,
                        pos: jnp.ndarray, *, n_head: int, n_kv_head: int,
                        layer, attn_window: int = 0, page0=None,
                        name: str = "paged_window_attention"):
    """``paged_window_attention`` for grouped queries: q (B, W, n_head*D),
    k_new/v_new (B, W, n_kv_head*D), pages (layers, N, page,
    n_kv_head*D) read at ``layer``; query head n reads KV head
    ``n // (n_head // n_kv_head)``. Same contract:
    attends the STALE pages masked to positions < pos and folds the fresh
    causal window, so the caller scatters afterwards.

    ``attn_window`` > 0 keeps row j to positions > pos + j - attn_window;
    pages wholly behind it are never fetched. ``page0`` (B,) is the
    absolute logical page of ``tables[:, 0]`` (default 0: the table
    starts at the sequence's first page); a window layer's ring passes
    the first page of its walk. ``name`` is the kernel's name in the
    HLO and the trace: full layers keep ``paged_window_attention``, a
    window layer's call says ``swa_...``."""
    return _gqa_call(q, k_new, v_new, k_pages, v_pages, tables, pos, layer,
                     page0, n_head=n_head, n_kv_head=n_kv_head,
                     attn_window=int(attn_window), name=name,
                     interpret=_interpret_mode())


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv_head", "attn_window", "name", "interpret"))
def _gqa_call(q, k_new, v_new, k_pages, v_pages, tables, pos, layer, page0,
              *, n_head, n_kv_head, attn_window, name, interpret):
    _, _, psz, Ckv = k_pages.shape
    B, W, Cq = q.shape
    D = Ckv // n_kv_head
    G = n_head // n_kv_head
    assert Cq == n_head * D and n_head == G * n_kv_head, (q.shape,
                                                          k_pages.shape)
    pos = jnp.asarray(pos, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    page0 = (jnp.zeros((B,), jnp.int32) if page0 is None
             else jnp.asarray(page0, jnp.int32))
    row_bytes = Ckv * k_pages.dtype.itemsize
    P, nb = _walk_shape(tables.shape[1], psz, row_bytes, 2)
    walk = _blocked_walk(
        tables, gqa_owned_pages(pos, page0, tables.shape[1], psz,
                                attn_window),
        psz, row_bytes, 2)
    # a KV head's G query heads as G*W rows of one block
    qg = (q.reshape(B, W, n_kv_head, G, D).transpose(0, 2, 3, 1, 4)
          .reshape(B, n_kv_head, G * W, D))
    kernel = functools.partial(
        _paged_gqa_kernel, n_kv_head=n_kv_head, head_dim=D, page_size=psz,
        n_block=P, n_blocks=nb, window=W, attn_window=attn_window,
        scale=D ** -0.5)

    def q_map(b, *_):
        return (b, 0, 0, 0)

    def row_map(b, *_):
        return (b, 0, 0)

    qspec = _vmem_spec((None, n_kv_head, G * W, D), q_map)
    row = _vmem_spec((None, W, Ckv), row_map)
    pools, pool_specs, pool_scratch = _pool_operands([k_pages, v_pages], P)
    scratch = _state_scratch(n_kv_head, G * W, D, q.dtype
                             if q.dtype == k_pages.dtype else jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=N_WALK + 2,
        grid=(B,),
        in_specs=[qspec, row, row, *pool_specs],
        out_specs=qspec,
        scratch_shapes=scratch + pool_scratch,
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv_head, G * W, D), q.dtype),
        name=name, interpret=interpret,
        compiler_params=_compiler_params(0, 1),
    )(*walk, layer.reshape(1), pos, page0, qg, k_new, v_new, *pools)
    return (out.reshape(B, n_kv_head, G, W, D).transpose(0, 3, 1, 2, 4)
            .reshape(B, W, Cq))


# ---------------------------------------------------------------------------
# latent attention (MLA)
#
# A latent layer caches ONE row a token and no head axis: the normed latent
# c (``latent`` lanes) beside the rotary key that every head shares, padded
# to whole 128-lane tiles (what the HBM's tiling would pad it to anyway).
# Decode reads it with every head's query ABSORBED into the latent space
# (the caller multiplies q_nope by the head's key expansion), so a head
# scores a row as one product over the whole row, and the row's first
# ``latent`` lanes are also the value. All of a slot's heads are ONE block
# of query rows against one key tile: a block is one score product, one
# masked online update and one value product, as in the kernels above, and
# each page is fetched once for every head. What comes back is the latent
# output a head, sum_j p_j c_j; the caller applies the value expansion.


def mla_attention_envelope(n_head: int, row_width: int, page_size: int, *,
                           itemsize: int = 2, mesh=None,
                           kv_quant: str = "none") -> tuple:
    """``paged_attention_envelope`` for ``paged_mla_attention``: ``(ok,
    reasons)``. One chip, a plain pool, rows of whole lane tiles, a page
    inside the VMEM budget."""
    reasons = []
    if mesh is not None and mesh.size > 1:
        reasons.append("mla_mesh")
    if kv_quant != "none":
        reasons.append("mla_kv_quant")
    if row_width % LANES:
        reasons.append("mla_row_lanes")
    if page_size % 8 != 0:
        reasons.append("page_align")
    if not block_pages(page_size, 1, row_width * itemsize, 1):
        reasons.append("vmem_budget")
    return (not reasons), tuple(reasons)


def _paged_mla_kernel(*refs, page_size, n_block, n_blocks, latent, scale):
    walk = refs[:N_WALK]
    (pos_ref, q_ref, new_ref, kv_hbm, out_ref, acc_ref, m_ref, l_ref,
     kv_buf, sem) = refs[N_WALK:]
    b = pl.program_id(0)
    T = n_block * page_size
    pos = pos_ref[b]
    _clear_state(acc_ref, m_ref, l_ref)

    def _accumulate(p, half, owned_cols):
        kpos = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1) + p * T
        rows = kv_buf[half]                          # (T, row): key AND value
        s = _scores(q_ref[...], rows, owned_cols() & (kpos < pos), scale)
        _online_update(s, rows[:, :latent], acc_ref, m_ref, l_ref, 0)

    _walk_blocks(walk, (kv_hbm,), (kv_buf,), sem, n_block, n_blocks,
                 page_size, _accumulate)
    # the fresh row, one column: a lane sum and an outer product on the
    # vector unit (the same float32 sums of the same products as the
    # MXU's); every head attends it, so the denominator is > 0 always
    new = new_ref[...].astype(jnp.float32)               # (1, row)
    s_new = jnp.sum(q_ref[...].astype(jnp.float32) * new, axis=1,
                    keepdims=True) * scale               # (H, 1)
    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, s_new)
    alpha = jnp.exp(m_prev - m_new)
    p_new = jnp.exp(s_new - m_new[:, :1])
    l_new = l_ref[0] * alpha + p_new
    acc = acc_ref[0] * alpha[:, :1] + p_new * new[:, :latent]
    out_ref[...] = (acc / l_new[:, :1]).astype(out_ref.dtype)


def paged_mla_attention(q: jnp.ndarray, kv_new: jnp.ndarray,
                        pages: jnp.ndarray, tables: jnp.ndarray,
                        pos: jnp.ndarray, *, layer, latent: int,
                        scale: float, name: str = "paged_mla_attention"):
    """Absorbed latent attention of one layer over a paged latent pool.

    q: (B, H, row) every head's absorbed query row (``latent`` lanes of
    q_nope times the head's key expansion, then the rotated q_rope, zeros
    in the padding lanes); kv_new: (B, row) the fresh latent row of each
    slot; pages: the whole stacked (layers, n_pages, page, row) STALE pool,
    read at ``layer``; tables (B, max_pages) int32; pos (B,) int32.
    Returns (B, H, latent): each head's softmax over positions < pos and
    the fresh row of ``scale`` times the row products, weighing the rows'
    first ``latent`` lanes. The caller scatters the fresh row afterwards
    and applies the value expansion."""
    return _mla_call(q, kv_new, pages, tables, pos, layer, latent=latent,
                     scale=float(scale), name=name,
                     interpret=_interpret_mode())


@functools.partial(jax.jit, static_argnames=(
    "latent", "scale", "name", "interpret"))
def _mla_call(q, kv_new, pages, tables, pos, layer, *, latent, scale, name,
              interpret):
    _, _, psz, row = pages.shape
    B, H, _ = q.shape
    assert q.shape[2] == row == kv_new.shape[1], (q.shape, pages.shape)
    pos = jnp.asarray(pos, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    row_bytes = row * pages.dtype.itemsize
    P, nb = _walk_shape(tables.shape[1], psz, row_bytes, 1)
    walk = window_walk(tables, pos, psz, row_bytes, 1)
    kernel = functools.partial(
        _paged_mla_kernel, page_size=psz, n_block=P, n_blocks=nb,
        latent=latent, scale=scale)
    pools, pool_specs, pool_scratch = _pool_operands([pages], P)
    state = [pltpu.VMEM((1, H, latent), jnp.float32),
             pltpu.VMEM((1, H, LANES), jnp.float32),
             pltpu.VMEM((1, H, LANES), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=N_WALK + 1,
        grid=(B,),
        in_specs=[_vmem_spec((None, H, row), lambda b, *_: (b, 0, 0)),
                  _vmem_spec((None, 1, row), lambda b, *_: (b, 0, 0)),
                  *pool_specs],
        out_specs=_vmem_spec((None, H, latent), lambda b, *_: (b, 0, 0)),
        scratch_shapes=state + pool_scratch,
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, latent), q.dtype),
        name=name, interpret=interpret,
        compiler_params=_compiler_params(0, 1),
    )(*walk, layer.reshape(1), pos, q, kv_new[:, None], *pools)
