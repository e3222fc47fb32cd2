#!/usr/bin/env python3
"""The paged attention kernels alone, at the serve cells' shapes: the
milliseconds of a launch (a scan over the cell's layers) at several fills
of the pool, what a live token and a live block cost, and a least-squares
fit of a constant plus a cost a live block a layer.

    python3 tools/kernel_fit.py [--tree DIR] [--family NAME ...] [--save DIR]
                                [--block-tokens N ...] [--tables runs]
    python3 tools/kernel_fit.py --compare DIR_A DIR_B

``--tree`` imports the package of another checkout (a parent commit
unpacked beside this one), so that two trees run the same script on the
same chip, one process each. Pools and queries are random bf16 made on
the device from fixed seeds, and a fill is a number of LIVE TOKENS a
layer, so two trees whose blocks differ read the same pools at the same
positions. One JSON line a fill (``ms`` the best of two means of 20
launches; ``live_blocks`` the live blocks a layer of the kernel's own
walk; ``ns_per_live_token`` and ``us_per_live_block``: the launch's time
over the empty pool's, over the live tokens or blocks of all layers) and
one a family (``fit``: ``const_ms`` + ``us_per_block`` x live blocks x
layers). ``--block-tokens`` runs each family once a block of that many
tokens (the walk's block size replaced inside this process: the sweep
that sized ``ops.paged_pallas.BLOCK_BYTES``). ``--save`` keeps each
family's output at its densest fill but one (bf16 bits) for
``--compare``, which prints the largest difference between two trees'
outputs. A slot's table holds random pages of the pool (an aged pool
under a free list), or with ``--tables runs`` slot b's pages b * table ..
in order, wrapped at the pool's end: every block one run of the pool, as
``serve.pages.PageAllocator`` hands them out (a ring's table is that
always).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

PAGE = 16
# a cell's engine, at its published widths: layers, slots, table entries
# a slot, pool pages; query heads, KV heads and head size (a latent
# family: its row's lanes, ``row``, of which the first ``latent`` are the
# value); ``window`` a ring layer's (its fills: slots deep in their
# sequence); fills in live tokens a layer (the cells' among them)
FAMILIES = {
    "gpt2-large": dict(layers=36, slots=96, table=64, pages=3072, heads=20,
                       kv_heads=20, head_dim=64,
                       fills=(0, 16_000, 25_000, 35_000, 98_000)),
    "kexaone-full": dict(layers=2, slots=64, table=512, pages=10240,
                         heads=64, kv_heads=8, head_dim=128,
                         fills=(0, 52_000, 104_000, 208_000)),
    "kexaone-ring": dict(layers=6, slots=64, table=9, pages=576, heads=64,
                         kv_heads=8, head_dim=128, window=128,
                         fills=(0, 32, 64)),
    "lfm2-full": dict(layers=2, slots=256, table=512, pages=40960,
                      heads=32, kv_heads=8, head_dim=64,
                      fills=(0, 208_000, 416_000, 832_000)),
    "joyai-latent": dict(layers=10, slots=256, table=512, pages=40960,
                         heads=32, row=640, latent=512,
                         fills=(0, 108_000, 216_000, 433_000, 866_000)),
}


def _positions(rng, slots, table, fill, ring):
    """Slot positions: ``fill`` live tokens spread over the slots, each
    drawn evenly about their mean and inside the table (a ring: ``fill``
    slots deep in their sequence, the rest idle)."""
    pos = np.zeros(slots, np.int32)
    if ring:
        pos[rng.permutation(slots)[:fill]] = rng.integers(1000, 3000, fill)
        return pos
    if fill:
        two, cap = round(2 * fill / slots), table * PAGE
        lo = max(1, two - cap)
        pos[:] = rng.integers(lo, two - lo + 1, slots)
    return pos


def _block_pages(pp, row_bytes, tiles, n_table):
    """The walk's pages a block of the tree under test (a tree from
    before blocks were sized by their bytes takes no count of tiles)."""
    try:
        return pp.block_pages(PAGE, n_table, row_bytes, tiles)
    except TypeError:
        return pp.block_pages(PAGE, n_table, row_bytes)


def fit_family(name, save, block_tokens=None, runs=False):
    import jax
    from replicatinggpt_tpu.ops import paged_pallas as pp

    real = pp.block_pages
    if block_tokens:        # the walk's block, replaced in this process
        pp.block_pages = (lambda page_size, n_table, *_:
                          min(-(-block_tokens // page_size), n_table))
    jax.clear_caches()      # the kernels' traces hold a block
    try:
        _fit(pp, name, save, block_tokens, runs)
    finally:
        pp.block_pages = real


def _fit(pp, name, save, block_tokens, runs):
    import jax
    import jax.numpy as jnp

    f = FAMILIES[name]
    L, B, mp, N = f["layers"], f["slots"], f["table"], f["pages"]
    window, latent = f.get("window", 0), f.get("latent", 0)
    H = f["heads"]
    width = f["row"] if latent else f["kv_heads"] * f["head_dim"]
    tiles = 1 if latent else 2          # a latent row is key and value
    P = _block_pages(pp, width * 2, tiles, mp)
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    # a layer's random pages, scaled a layer: no float32 temporary of a
    # whole pool (gpt2-large's is 4.5 GB a side in bf16)
    grade = jnp.linspace(0.5, 1.5, L, dtype=bf)[:, None, None, None]
    pools = tuple(jax.jit(lambda k: jax.random.normal(
        k, (N, PAGE, width), bf)[None] * grade)(k)
        for k in keys[:1 if latent else 2])
    q = jax.random.normal(keys[2], (L, B) + ((H, width) if latent
                                             else (1, H * f["head_dim"])),
                          bf)
    new = tuple(jax.random.normal(k, (L, B) + ((width,) if latent
                                               else (1, width)), bf)
                for k in keys[3:4 if latent else 5])
    rng = np.random.default_rng(0)
    if window or runs:
        tables = (np.arange(B * mp, dtype=np.int32) % N).reshape(B, mp)
    else:
        tables = rng.integers(0, N, (B, mp)).astype(np.int32)

    def attend(q, new, pools, tables, pos, page0, layer):
        if latent:
            return pp.paged_mla_attention(
                q, new[0], pools[0], tables, pos, layer=layer, latent=latent,
                scale=192 ** -0.5)
        if f["kv_heads"] == H:
            return pp.paged_window_attention(
                q, *new, *pools, tables, pos, n_head=H, layer=layer)
        return pp.paged_gqa_attention(
            q, *new, *pools, tables, pos, n_head=H, n_kv_head=f["kv_heads"],
            layer=layer, attn_window=window,
            page0=page0 if window else None)

    @jax.jit
    def launch(q, new, pools, tables, pos, page0):
        return jax.lax.scan(
            lambda c, l: (c, attend(q[l], tuple(n[l] for n in new), pools,
                                    tables, pos, page0, l)),
            0, jnp.arange(L))[1]

    rows = []
    for fill in f["fills"]:
        pos = _positions(rng, B, mp, fill, window)
        page0 = np.maximum(pos - window + 1, 0) // PAGE * bool(window)
        # the walk's own owned pages, once a fill, outside the timings
        owned = np.asarray(pp.gqa_owned_pages(  # graftlint: disable=GL004
            jnp.asarray(pos), jnp.asarray(page0), mp, PAGE, window))
        nb = -(-mp // P)
        owned = np.pad(owned, ((0, 0), (0, nb * P - mp)))
        live = int(owned.reshape(B, nb, P).any(axis=2).sum())
        tokens = int(owned.sum()) * PAGE if window else int(pos.sum())
        args = (q, new, pools) + tuple(map(jnp.asarray, (tables, pos, page0)))
        out = launch(*args)
        out.block_until_ready()
        means = []
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(20):
                out = launch(*args)
            out.block_until_ready()
            means.append((time.perf_counter() - t0) / 20 * 1e3)
        ms = min(means)
        rows.append((live, ms))
        line = {"family": name, "block_pages": P, "block_tokens": P * PAGE,
                "live_blocks": live, "live_tokens": tokens, "ms": round(ms, 4)}
        if live:
            extra = ms - rows[0][1]
            line.update(
                ns_per_live_token=round(extra / (tokens * L) * 1e6, 4),
                us_per_live_block=round(extra / (live * L) * 1e3, 4))
        print(json.dumps(line), flush=True)
        if save and fill == f["fills"][-2]:
            os.makedirs(save, exist_ok=True)
            # one fetch a family, after its timings
            bits = np.asarray(out).view(np.uint16)  # graftlint: disable=GL004
            tag = f"-{block_tokens}" if block_tokens else ""
            np.save(os.path.join(save, name + tag + ".npy"), bits)
    x = np.array([[1.0, live * L] for live, _ in rows])
    (const, per), *_ = np.linalg.lstsq(x, np.array([ms for _, ms in rows]),
                                       rcond=None)
    print(json.dumps({"family": name, "block_tokens": P * PAGE, "fit": {
        "const_ms": round(float(const), 4),
        "us_per_block": round(float(per) * 1e3, 4)}}), flush=True)


def compare(a, b):
    def f32(path):
        return (np.load(path).astype(np.uint32) << 16).view(np.float32)
    names = sorted(os.listdir(a))
    pairs = [(f32(os.path.join(a, n)), f32(os.path.join(b, n)))
             for n in names]
    equal = [bool((x.view(np.uint32) == y.view(np.uint32)).all())
             for x, y in pairs]
    diff = np.array([np.abs(x - y).max() for x, y in pairs]).tolist()
    top = np.array([np.abs(x).max() for x, _ in pairs]).tolist()
    for row in zip(names, equal, diff, top):
        print(json.dumps(dict(zip(
            ("family", "bit_equal", "max_abs_diff", "max_abs"),
            (row[0][:-4],) + row[1:]))), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--family", nargs="*", default=list(FAMILIES))
    ap.add_argument("--save")
    ap.add_argument("--block-tokens", type=int, nargs="*", default=[None])
    ap.add_argument("--compare", nargs=2)
    ap.add_argument("--tables", choices=("random", "runs"), default="random")
    args = ap.parse_args(argv)
    if args.compare:
        compare(*args.compare)
        return 0
    sys.path.insert(0, os.path.abspath(args.tree))
    for name in args.family:    # each family times its own launches
        for tokens in args.block_tokens or [None]:
            fit_family(name, args.save, tokens,  # graftlint: disable=GL004
                       args.tables == "runs")
    return 0


if __name__ == "__main__":
    sys.exit(main())
