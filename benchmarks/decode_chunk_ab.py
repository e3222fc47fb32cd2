"""In-run A/B of the chunked (grow-as-you-go) KV-cache decode vs the
monolithic full-bucket scan, per batch size, in ONE process — both modes
share the model, the device and the thermal/noise environment,
so the delta is the chunking and not run-to-run drift.

The monolithic arm is the same code with attend_granule = block_size
(one chunk at full width — exactly the pre-chunking program). Repro:

    python benchmarks/decode_chunk_ab.py --preset gpt2-small \
        --batch-sizes 1,8,32 --laps 5

Writes a JSON summary line per (mode, B); RESULTS.md decode rows cite
this script. Capability context: the reference's sampler re-forwards
the whole window per token (/root/reference/GPT1.py:196-212); both arms
here are KV-cached and identical in output (tests pin trajectory
bit-parity), so this measures bytes, not semantics.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt2-small")
    ap.add_argument("--batch-sizes", default="1,8,32")
    ap.add_argument("--laps", type=int, default=5)
    ap.add_argument("--tokens", type=int, default=1000)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.sample import GenerateConfig, generate
    from replicatinggpt_tpu.train.state import create_train_state

    cfg = get_config(args.preset)
    state = create_train_state(jax.random.PRNGKey(0), cfg.model, cfg.train)
    shipped_granule = GenerateConfig().attend_granule  # what users get
    out = {}
    for B in (int(b) for b in args.batch_sizes.split(",")):
        prompt = jnp.zeros((B, 1), jnp.int32)
        for mode, granule in (("monolithic", cfg.model.block_size),
                              ("chunked", shipped_granule)):
            # attend_granule is part of the static jit key, so the two
            # arms compile as distinct programs — no cache clearing
            gcfg = GenerateConfig(max_new_tokens=args.tokens, top_k=50,
                                  attend_granule=granule)
            # warm/compile
            jax.device_get(generate(state.params, prompt, cfg.model, gcfg))
            laps = []
            for i in range(args.laps):
                t0 = time.perf_counter()
                toks = generate(state.params, prompt, cfg.model, gcfg,
                                rng=jax.random.PRNGKey(i))
                jax.device_get(toks)  # real fetch; block_until_ready lies
                laps.append(time.perf_counter() - t0)
            p50 = sorted(laps)[len(laps) // 2]  # laps stay chronological
            row = {"p50_ms_per_1k": round(p50 * 1e3 * 1000 / args.tokens, 1),
                   "aggregate_tok_s": round(B * args.tokens / p50, 1),
                   "laps_ms": [round(x * 1e3, 1) for x in laps]}
            out[f"{mode}_B{B}"] = row
            print(f"{mode:>10} B={B}: p50 {row['p50_ms_per_1k']} ms/1k, "
                  f"{row['aggregate_tok_s']:,.0f} tok/s aggregate",
                  flush=True)
    print(json.dumps({"preset": args.preset, "tokens": args.tokens,
                      "results": out}))


if __name__ == "__main__":
    sys.exit(main())
