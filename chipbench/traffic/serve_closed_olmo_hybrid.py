"""Traffic kind ``serve_closed_olmo_hybrid``: ``serve_closed``'s closed loop
for the ``olmo_hybrid`` family. The loop is that file's, as it is (the same
``Clients``, imported from it, ``serving.drive``, ``Window``, ``Tracer``,
``request_times`` and ``aged``, the same result keys).

The comparison is ``serve_closed_family.check`` less the router, which this
family has not: route ``pallas``, no compile in the window, the 16 longest
streams of what the timed run emitted (``longest``), teacher-forced on the
chip at the published widths through ``chipbench/reference_olmo_hybrid.py``
(float32, the delta rule one token a turn); the WORST token's gap
(``logit_tol``) and the MEAN gap (``mean_gap_tol``), either of them.

What this file brings beside that: ``model_config`` (the keys an
``olmo_hybrid`` configuration file states, laid over the preset and
validated, so the file is what is run: the linear layers' heads and widths,
the conv's taps, beta's range, no rotary position, the untied head) and
``step_mfu_pct`` (``flops_olmo_hybrid.olmo_hybrid_serve_flops``).

Given ``sizes`` (by ``rehearse.py``, whose flags are GPT-2's) it takes the
family's own tiny preset at the cell's context length.

``run(ctx, control=True)`` is the comparison's CONTROL
(``chipbench/control_serve_family.py``): the same run, held to the reference
with every matrix rounded to 8 bits, the nearest precision below the
configuration's bfloat16. It has to come out not ``correct``.
``run(ctx, control="bf16_state")`` holds the same streams to the reference
with its delta-rule state rounded to bfloat16 after every token, the
nearest precision below the float32 state the configuration states: a
hand run, which has to come out not ``correct`` too (by the mean gap).

Parameters of a mix: those of ``serve_closed``, and ``mean_gap_tol`` /
``mean_gap_tol_why``.
"""

from __future__ import annotations

import dataclasses
import gc

import numpy as np

from chipbench import common, manifest, serving

#: the family's preset at test widths, for the rehearsal
TINY = "olmo-hybrid-tiny"

#: configuration-file key -> ModelConfig field, for what a file may state
STATED = {
    "hidden_size": "n_embd", "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head", "head_dim": "attn_head_dim",
    "num_hidden_layers": "n_layer", "vocab_size": "vocab_size",
    "block_size": "block_size", "intermediate_size": "intermediate_size",
    "layer_types": "layer_types", "rms_norm_eps": "layernorm_eps",
    "linear_num_key_heads": "linear_num_key_heads",
    "linear_num_value_heads": "linear_num_value_heads",
    "linear_key_head_dim": "linear_key_head_dim",
    "linear_value_head_dim": "linear_value_head_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel_dim",
    "linear_allow_neg_eigval": "linear_allow_neg_eigval",
    "dtype": "dtype", "param_dtype": "param_dtype",
}

#: what the family's body computes whatever its ModelConfig says: a
#: configuration file that states another value is not what would be run
FIXED = {"hidden_act": "silu", "attention_bias": False,
         "tie_word_embeddings": False, "rope_parameters": {"rope_theta": None}}


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
    if config["head_dim"] * config["num_attention_heads"] \
            != config["hidden_size"]:
        common.fail(f"{config['name']}: head_dim is {config['head_dim']}, "
                    f"the {config['family']} body reads hidden_size / "
                    f"num_attention_heads")
    stated = {field: (tuple(config[key]) if isinstance(config[key], list)
                      else config[key])
              for key, field in STATED.items() if key in config}
    stated["mlp_layer_types"] = ("dense",) * config["num_hidden_layers"]
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
    """``serving.step_mfu_pct`` of the FLOPs the configuration file's stage
    needs for the window (``flops_olmo_hybrid``)."""
    from chipbench import flops_olmo_hybrid
    decoded, prefilled = win["tokens"], win["prefill_tokens"]
    return serving.step_mfu_pct(flops_olmo_hybrid.olmo_hybrid_serve_flops(
        config, tokens=decoded + prefilled, emitted=decoded,
        full_context_pairs=win["live_token_steps"]), win["seconds"], sizes)


def longest(finished, seed: int, n: int):
    """The ``n`` finished streams of the most tokens, prompt and answer
    together (ties in an order drawn from ``seed``). The drift of a
    delta-rule state held below its stated precision is small against a
    token's gap and steady along a sequence: what tells it apart is the
    number of tokens the mean gap is taken over."""
    order = np.random.default_rng(seed).permutation(len(finished))
    ranked = sorted((finished[i] for i in order), key=lambda s: -(
        len(s.req.prompt) + len(s.result.tokens)))
    return ranked[:n]


def check(engine, mcfg, finished, window: dict, seed: int, traffic: dict,
          n_streams: int = 16, control=False):
    """``serve_closed_family.check`` less the router (module docstring)."""
    from chipbench import reference_olmo_hybrid as reference
    tol, mean_tol = float(traffic["logit_tol"]), float(traffic["mean_gap_tol"])
    problems = []
    t0 = common.now()
    summary = engine.metrics_summary()
    route = summary["kernel_route"]
    common.note("kernel_route", **route)
    if route["route"] != "pallas" or route["reasons"]:
        problems.append(f"kernel route is {route['route']} "
                        f"{route['reasons']}, not pallas")
    if not common.compare("compiled_in_window", window["backend_compiles"]
                          + window["engine_programs_added"], 0):
        problems.append(
            f"compiled inside the window: {window['backend_compiles']} "
            f"backend compiles, {window['engine_programs_added']} programs")
    common.note("family_counters",
                **{k: summary.get(k) for k in (
                    "kv_global_bytes", "conv_state_bytes",
                    "delta_state_bytes")})
    params = engine.params
    pick = longest(finished, seed, n_streams)
    prompts = [np.asarray(s.req.prompt) for s in pick]
    streams = [np.asarray(s.result.tokens, np.int32) for s in pick]
    engine.pool.cache = None         # the pool and the reference's
    gc.collect()                     # activations do not fit together
    spec = reference.spec_of(mcfg)
    if control is True:
        shared = manifest.Manifest(manifest.ROOT).kind("serve_closed_family")
        params = shared._round_to_8_bits(params)
    elif control == "bf16_state":
        spec["state_dtype"] = "bfloat16"
    gaps, mean_gap = reference.stream_gaps(params, spec, mcfg.block_size,
                                           prompts, streams)
    common.note("reference_streams", streams=len(gaps),
                stream_tokens=[len(s) for s in streams],
                prompt_tokens=[len(p) for p in prompts],
                worst_logit_gap=max(gaps, default=None), gaps=gaps,
                tol=tol, why=traffic["logit_tol_why"],
                mean_logit_gap=mean_gap, mean_tol=mean_tol,
                mean_why=traffic["mean_gap_tol_why"],
                control=control, check_s=common.now() - t0)
    worst_ok = common.compare("worst_logit_gap", max(gaps, default=None), tol)
    mean_ok = common.compare("mean_logit_gap", mean_gap if gaps else None,
                             mean_tol)
    if not gaps:
        problems.append("no finished stream to hold to the reference")
    elif not worst_ok:
        problems.append(f"a stream's token sits {max(gaps)} below the "
                        f"reference's best logit, tolerance {tol}")
    elif not mean_ok:
        problems.append(f"the streams' tokens sit {mean_gap} below the "
                        f"reference's best logit on average, tolerance "
                        f"{mean_tol}")
    return problems


def run(ctx: common.Ctx, sizes=None, control=False) -> dict:
    closed = manifest.Manifest(manifest.ROOT).kind("serve_closed")
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
    summary = engine.metrics_summary()
    common.note("serve_closed_window", clients=clients.n,
                finished_in_window=len(in_window), rejected=len(rejected),
                finished_before_window=len(finished_at) - len(in_window),
                queue_at_close=window.queue_at_close,
                delta_state_bytes=summary.get("delta_state_bytes"),
                conv_state_bytes=summary.get("conv_state_bytes"),
                tpot_ms={"n": len(tpot), "p50": common.median(tpot),
                         "p80": tail(tpot)},
                engine_window=win)
    ok = [s for s in in_window if serving.request_times(s)]
    problems = check(engine, mcfg, ok, win, ctx.seed31, t, control=control)
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
