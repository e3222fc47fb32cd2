#!/usr/bin/env python3
"""The paged attention kernels alone, at the serve cells' shapes: the
milliseconds of a launch (a scan over the cell's layers) at several fills
of the pool, and a least-squares fit of a constant plus a cost a live
block a layer.

    python3 tools/kernel_fit.py [--tree DIR] [--family NAME ...] [--save DIR]
    python3 tools/kernel_fit.py --compare DIR_A DIR_B

``--tree`` imports the package of another checkout (a parent commit
unpacked beside this one), so that two trees run the same script on the
same chip, one process each. Pools and queries are random bf16 made on
the device from fixed seeds, so both trees read the same data. One JSON
line a fill (``ms`` the best of two means of 20 launches, ``live_blocks``
the live blocks a layer of the kernel's own walk) and one a family
(``fit``: ``const_ms`` + ``us_per_block`` x live blocks x layers).
``--save`` keeps each family's output at its densest fill but one
(bf16 bits) for ``--compare``, which prints the largest difference
between two trees' outputs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# layers, slots, table entries a slot, pool pages, query heads, KV heads,
# head size, the window of a ring layer (0: a full layer), live blocks a
# layer at each fill (the cells' fills among them)
FAMILIES = {
    "gpt2-large": (36, 96, 64, 3072, 20, 20, 64, 0, (0, 126, 198, 278, 768)),
    "kexaone-full": (2, 64, 512, 10240, 64, 8, 128, 0, (0, 419, 838, 1676)),
    "kexaone-ring": (6, 64, 9, 576, 64, 8, 128, 128, (0, 32, 64)),
    "lfm2-full": (2, 256, 512, 40960, 32, 8, 64, 0, (0, 1676, 3353, 6706)),
}
PAGE = 16
BLOCK_TOKENS = 128


def _positions(rng, slots, table, n_blocks, ring):
    """Slot positions whose live blocks a layer add up to ``n_blocks``
    (a ring: that many slots deep in their sequence, the rest idle)."""
    pos = np.zeros(slots, np.int32)
    if ring:
        pos[rng.permutation(slots)[:n_blocks]] = rng.integers(
            1000, 3000, n_blocks)
        return pos
    per = np.full(slots, n_blocks // slots)
    per[rng.permutation(slots)[:n_blocks % slots]] += 1
    assert per.max() * BLOCK_TOKENS <= table * PAGE, n_blocks
    live = per > 0
    pos[live] = per[live] * BLOCK_TOKENS - rng.integers(
        0, BLOCK_TOKENS, live.sum())
    return pos


def fit_family(name, save):
    import jax
    import jax.numpy as jnp
    from replicatinggpt_tpu.ops import paged_pallas as pp

    L, B, mp, N, H, Hkv, D, window, fills = FAMILIES[name]
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    # a layer's random pages, scaled a layer: no float32 temporary of a
    # whole pool (gpt2-large's is 4.5 GB a side in bf16)
    grade = jnp.linspace(0.5, 1.5, L, dtype=bf)[:, None, None, None]
    kp, vp = (jax.jit(lambda k: jax.random.normal(
        k, (N, PAGE, Hkv * D), bf)[None] * grade)(k) for k in keys[:2])
    q = jax.random.normal(keys[2], (L, B, 1, H * D), bf)
    kn, vn = (jax.random.normal(k, (L, B, 1, Hkv * D), bf)
              for k in keys[3:])
    rng = np.random.default_rng(0)
    if window:
        tables = np.arange(B * mp, dtype=np.int32).reshape(B, mp)
    else:
        tables = rng.integers(0, N, (B, mp)).astype(np.int32)

    def attend(q, kn, vn, kp, vp, tables, pos, page0, layer):
        if Hkv == H:
            return pp.paged_window_attention(q, kn, vn, kp, vp, tables, pos,
                                             n_head=H, layer=layer)
        return pp.paged_gqa_attention(
            q, kn, vn, kp, vp, tables, pos, n_head=H, n_kv_head=Hkv,
            layer=layer, attn_window=window,
            page0=page0 if window else None)

    @jax.jit
    def launch(q, kn, vn, kp, vp, tables, pos, page0):
        return jax.lax.scan(
            lambda c, l: (c, attend(q[l], kn[l], vn[l], kp, vp, tables, pos,
                                    page0, l)), 0, jnp.arange(L))[1]

    rows = []
    for n in fills:
        pos = _positions(rng, B, mp, n, window)
        page0 = np.maximum(pos - window + 1, 0) // PAGE * bool(window)
        owned = pp.gqa_owned_pages(jnp.asarray(pos), jnp.asarray(page0), mp,
                                   PAGE, window)
        live = int(pp._blocked_walk(jnp.asarray(tables), owned, PAGE,
                                    Hkv * D * 2)[2].sum())
        args = (q, kn, vn, kp, vp) + tuple(map(jnp.asarray,
                                               (tables, pos, page0)))
        out = launch(*args)
        out.block_until_ready()
        means = []
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(20):
                out = launch(*args)
            out.block_until_ready()
            means.append((time.perf_counter() - t0) / 20 * 1e3)
        rows.append((live, min(means)))
        print(json.dumps({"family": name, "live_blocks": live,
                          "live_tokens": int(pos.sum()),
                          "ms": round(min(means), 4)}), flush=True)
        if save and n == fills[-2]:
            os.makedirs(save, exist_ok=True)
            # one fetch a family, after its timings
            bits = np.asarray(out).view(np.uint16)  # graftlint: disable=GL004
            np.save(os.path.join(save, name + ".npy"), bits)
    x = np.array([[1.0, live * L] for live, _ in rows])
    (const, per), *_ = np.linalg.lstsq(x, np.array([ms for _, ms in rows]),
                                       rcond=None)
    print(json.dumps({"family": name, "fit": {
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
    ap.add_argument("--compare", nargs=2)
    args = ap.parse_args(argv)
    if args.compare:
        compare(*args.compare)
        return 0
    sys.path.insert(0, os.path.abspath(args.tree))
    for name in args.family:    # each family times its own launches
        fit_family(name, args.save)  # graftlint: disable=GL004
    return 0


if __name__ == "__main__":
    sys.exit(main())
