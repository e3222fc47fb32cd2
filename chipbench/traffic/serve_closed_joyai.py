"""Traffic kind ``serve_closed_joyai``: ``serve_closed``'s closed loop for the
``joyai_llm_flash`` family. The loop is that file's, as it is (the same
``Clients``, imported from it, ``serving.drive``, ``Window``, ``Tracer``,
``request_times`` and ``aged``, the same result keys), and the comparison is
``serve_closed_family.check``, as it is, which finds this family's reference
(``chipbench/reference_joyai_llm_flash.py``) and program by the
configuration's ``family``: route ``pallas``, no compile in the window, 8
seeded streams of what the timed run emitted, teacher-forced on the chip at
the published widths in blocks; the WORST token's gap (``logit_tol``), the
MEAN gap (``mean_gap_tol``) and the share of router decisions that differ
beyond the tie margin (``route_mismatch_tol``), any one of them.

What this file brings is what ``serve_closed_family`` has for ``exaone_moe``
alone: ``model_config`` (the keys a ``joyai_llm_flash`` configuration file
states, laid over the preset and validated, so the file is what is run: the
latent ranks and head sizes, interleaved RoPE, the leading dense layers by
``first_k_dense_replace``, the shared experts, the router's published
outputs beside the experts held) and ``step_mfu_pct``
(``flops_joyai_llm_flash.joyai_llm_flash_serve_flops``: absorbed attention
over the live context, routed pairs, not rows pushed through every held
expert).

Given ``sizes`` (by ``rehearse.py``, whose flags are GPT-2's) it takes the
family's own tiny preset at the cell's context length.

``run(ctx, control=True)`` is the comparison's CONTROL
(``chipbench/control_serve_family.py``): the same run, held to the reference
with every matrix rounded to 8 bits, the nearest precision below the
configuration's bfloat16. It has to come out not ``correct``.

Parameters of a mix: those of ``serve_closed_family``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import common, manifest, serving

#: the family's preset at test widths, for the rehearsal
TINY = "joyai-llm-flash-tiny"

#: configuration-file key -> ModelConfig field, for what a file may state
STATED = {
    "hidden_size": "n_embd", "num_attention_heads": "n_head",
    "num_hidden_layers": "n_layer", "vocab_size": "vocab_size",
    "block_size": "block_size", "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "router_outputs": "n_experts", "experts_held": "experts_held",
    "num_experts_per_tok": "experts_per_token",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "rms_norm_eps": "layernorm_eps",
    "routed_scaling_factor": "routed_scaling",
    "dtype": "dtype", "param_dtype": "param_dtype",
}

#: what the family's body computes whatever its ModelConfig says: a
#: configuration file that states another value is not what would be run
FIXED = {"scoring_func": "sigmoid", "norm_topk_prob": True,
         "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
         "moe_layer_freq": 1, "rope_interleave": True, "rope_scaling": None,
         "hidden_act": "silu", "attention_bias": False,
         "tie_word_embeddings": False}


def model_config(config: dict, sizes=None):
    """The program's ``ModelConfig`` for a configuration file."""
    from replicatinggpt_tpu.config import get_config
    try:
        base = get_config(TINY if sizes is not None
                          else config["preset"]).model
    except KeyError as e:       # a program from before the family: at once
        common.fail(f"{config['name']}: the program has no "
                    f"{config['family']} family ({e.args[0]})")
    if sizes is not None:
        return dataclasses.replace(base,
                                   block_size=config["block_size"]).validate()
    for key, value in FIXED.items():
        if config.get(key, value) != value:
            common.fail(f"{config['name']}: {key} is {config[key]!r}, the "
                        f"{config['family']} body computes {value!r}")
    checks = {
        "n_routed_experts": len(config["experts_held"]),
        "head_dim": config["qk_rope_head_dim"],
        "qk_head_dim": (config["qk_nope_head_dim"]
                        + config["qk_rope_head_dim"]),
        "num_key_value_heads": config["num_attention_heads"]}
    for key, want in checks.items():
        if config[key] != want:
            common.fail(f"{config['name']}: {key} is {config[key]}, the "
                        f"{config['family']} body reads {want} from the "
                        f"other keys")
    stated = {field: (tuple(config[key]) if isinstance(config[key], list)
                      else config[key])
              for key, field in STATED.items() if key in config}
    stated["rope_theta"] = float(config["rope_theta"])
    stated["routed_scaling"] = float(stated["routed_scaling"])
    n_dense = config["first_k_dense_replace"]
    stated["mlp_layer_types"] = ("dense",) * n_dense + ("sparse",) * (
        config["num_hidden_layers"] - n_dense)
    stated["shared_intermediate_size"] = (
        config["n_shared_experts"] * config["moe_intermediate_size"])
    return dataclasses.replace(base, **stated).validate()


def build_engine(ctx: common.Ctx, sizes=None):
    import jax
    from replicatinggpt_tpu.models.families import family
    from replicatinggpt_tpu.serve import Engine, EngineConfig
    mcfg = model_config(ctx.cell["config"], sizes)
    params = family(mcfg).init_params(jax.random.PRNGKey(ctx.seed31), mcfg)
    ecfg = EngineConfig(**ctx.cell["program"]["engine"])
    return Engine(params, mcfg, ecfg), mcfg, ecfg


def step_mfu_pct(config: dict, win: dict, sizes=None):
    """``serving.step_mfu_pct`` of the FLOPs the configuration file's share
    of the model needs for the window. The engine counts the routed pairs
    that landed on held experts in its decode steps (``moe_pairs_held``); a
    prefilled row is taken to land as many."""
    from chipbench import flops_joyai_llm_flash
    decoded, prefilled = win["tokens"], win["prefill_tokens"]
    pairs = win["counter_deltas"].get("moe_pairs_held", 0)
    return serving.step_mfu_pct(
        flops_joyai_llm_flash.joyai_llm_flash_serve_flops(
            config, tokens=decoded + prefilled, emitted=decoded,
            pairs_held=pairs * (decoded + prefilled) / max(decoded, 1),
            full_context_pairs=win["live_token_steps"]),
        win["seconds"], sizes)


def run(ctx: common.Ctx, sizes=None, control: bool = False) -> dict:
    man = manifest.Manifest(manifest.ROOT)
    closed, shared = man.kind("serve_closed"), man.kind("serve_closed_family")
    t = ctx.cell["traffic"]
    engine, mcfg, ecfg = build_engine(ctx, sizes)
    serving.warm_up(engine, mcfg, ecfg, np.random.default_rng(ctx.seed31))
    clients = closed.Clients(t, ctx.seed31, mcfg.vocab_size)
    ramp = float(t["ramp_s"])
    window = serving.Window(engine, ctx)
    finished_at = []             # (sent, whether inside the window)

    def on_finish(s: serving.Sent):
        finished_at.append((s, window.is_open))
        return [clients.next(int(s.req.id[1:4]))]

    tracer = serving.Tracer(ctx.trace_dir if ctx.trace else None,
                            float(t["trace_s"]))
    first = [clients.next(c) for c in range(clients.n)]
    sent = serving.drive(
        engine, due=first, on_finish=on_finish, t_open=ramp,
        t_close=ramp + ctx.seconds,
        t_give_up=ramp + ctx.seconds + tracer.span_s, tracer=tracer,
        at_open=window.open, at_close=window.close,
        each_step=window.sample)
    mem = common.memory_peak_bytes()
    win = window.counters()
    win["step_mfu_pct"] = step_mfu_pct(ctx.cell["config"], win, sizes)
    in_window = [s for s, inside in finished_at if inside]
    rejected = [s for s in sent if s.result is not None
                and not s.result.ok]
    times = [serving.request_times(s) for s in in_window]
    tpot = [x["tpot_ms"] for x in times if x and x["tpot_ms"] is not None]
    if not tpot:
        common.fail("no request finished inside the window")
    tail = lambda v: common.pct(v, serving.TAIL)
    common.note("serve_closed_window", clients=clients.n,
                finished_in_window=len(in_window), rejected=len(rejected),
                finished_before_window=len(finished_at) - len(in_window),
                queue_at_close=window.queue_at_close,
                kv_latent_bytes=engine.metrics_summary().get(
                    "kv_latent_bytes"),
                tpot_ms={"n": len(tpot), "p50": common.median(tpot),
                         "p80": tail(tpot)},
                engine_window=win)
    ok = [s for s in in_window if serving.request_times(s)]
    problems = shared.check(engine, mcfg, ok, win, ctx.seed31, t,
                            control=control)
    if problems:
        common.note("problems", problems=problems)
    return {
        "correct": not problems,
        "problems": problems,
        "attempted": len(in_window) + len(rejected),
        "failed": len(rejected) + len(in_window) - len(ok),
        "memory_peak_bytes": mem,
        "end_to_end": {"serve_tokens_per_s": win["tokens_per_s"],
                       "tpot_p80_ms": tail(tpot), "setup_s": window.setup_s},
        "counters": {"setup": {"compile_s": window.a.compiles["s"]},
                     "serve": win},
    }
