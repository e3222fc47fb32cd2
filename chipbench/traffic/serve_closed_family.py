"""Traffic kind ``serve_closed_family``: ``serve_closed``'s closed loop for a
model family other than GPT-2. The loop is that file's, as it is: the same
``Clients`` (imported from it), ``serving.drive``, ``Window``, ``Tracer``,
``request_times`` and ``aged``, the same result keys. What this file brings
is the two things ``serving.py`` has for GPT-2 alone:

- ``build_engine``: the family, its widths and its weights from the
  CONFIGURATION FILE (every stated size is laid over the preset and
  validated, so the file is what is run), weights made on the device leaf by
  leaf from the seed by the family's own ``init_params``;
- ``check``: ``serving.check`` in everything (route ``pallas``, no compile in
  the window, 8 seeded streams of what the timed run emitted, teacher-forced
  on the chip at the published widths) but the reference it calls,
  ``chipbench/reference_<family>.py``, which is also handed the program's
  routing choices for the same sequences to COUNT where they differ from
  its own, inside and beyond the mix's ``tie_margin`` (its docstring); it
  always routes by its own scores.
  Three limits decide, any one of them: the WORST token's gap
  (``logit_tol``: a wrong page, position, mask or expert moves a logit by
  whole units), the MEAN gap over all the streams' tokens
  (``mean_gap_tol``: top-8 of 128 flips on rounding at a few per cent of
  the tokens whatever the precision, so the worst gap cannot tell
  precisions apart and the mean can), and the share of router decisions at
  which the program's whole-sequence forward and the reference chose other
  experts BEYOND the tie margin (``route_mismatch_tol``: the router's own
  precision).

Given ``sizes`` (by ``rehearse.py``, whose flags are GPT-2's) it takes the
family's own tiny preset at the cell's context length.

``run(ctx, control=True)`` is the comparison's CONTROL
(``chipbench/control_serve_family.py``): the same run, held to the reference
with every matrix rounded to 8 bits, the nearest precision below the
configuration's bfloat16. It has to come out not ``correct``.

Parameters of a mix: those of ``serve_closed``, and ``tie_margin`` /
``tie_margin_why``, ``mean_gap_tol`` / ``mean_gap_tol_why``,
``route_mismatch_tol`` / ``route_mismatch_tol_why``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib

import numpy as np

from chipbench import common, manifest, serving

#: a family's preset at test widths, for the rehearsal
TINY = {"exaone_moe": "exaone-moe-tiny"}

#: configuration-file key -> ModelConfig field, for what a file may state
STATED = {
    "hidden_size": "n_embd", "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head", "head_dim": "attn_head_dim",
    "num_hidden_layers": "n_layer", "vocab_size": "vocab_size",
    "block_size": "block_size", "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "router_outputs": "n_experts", "experts_held": "experts_held",
    "num_experts_per_tok": "experts_per_token",
    "sliding_window": "sliding_window", "layer_types": "layer_types",
    "mlp_layer_types": "mlp_layer_types", "rms_norm_eps": "layernorm_eps",
    "routed_scaling_factor": "routed_scaling",
    "dtype": "dtype", "param_dtype": "param_dtype",
}

#: what a family's body computes whatever its ModelConfig says: a
#: configuration file that states another value is not what would be run
FIXED = {"exaone_moe": {"scoring_func": "sigmoid", "norm_topk_prob": True}}


def model_config(config: dict, sizes=None):
    """The program's ``ModelConfig`` for a configuration file."""
    from replicatinggpt_tpu.config import get_config
    if sizes is not None:
        tiny = get_config(TINY[config["family"]]).model
        return dataclasses.replace(tiny,
                                   block_size=config["block_size"]).validate()
    stated = {field: (tuple(config[key]) if isinstance(config[key], list)
                      else config[key])
              for key, field in STATED.items() if key in config}
    stated["rope_theta"] = float(config["rope_parameters"]["rope_theta"])
    stated["shared_intermediate_size"] = (
        config["num_shared_experts"] * config["moe_intermediate_size"])
    for key, value in FIXED[config["family"]].items():
        if config.get(key, value) != value:
            common.fail(f"{config['name']}: {key} is {config[key]!r}, the "
                        f"{config['family']} body computes {value!r}")
    if len(config["experts_held"]) != config["num_experts"]:
        common.fail(f"{config['name']}: num_experts states "
                    f"{config['num_experts']} held, experts_held names "
                    f"{len(config['experts_held'])}")
    base = get_config(config["preset"]).model
    return dataclasses.replace(base, **stated).validate()


def build_engine(ctx: common.Ctx, sizes=None):
    import jax
    from replicatinggpt_tpu.models.families import family
    from replicatinggpt_tpu.serve import Engine, EngineConfig
    mcfg = model_config(ctx.cell["config"], sizes)
    params = family(mcfg).init_params(jax.random.PRNGKey(ctx.seed31), mcfg)
    ecfg = EngineConfig(**ctx.cell["program"]["engine"])
    return Engine(params, mcfg, ecfg), mcfg, ecfg


def _round_to_8_bits(params):
    """Every matrix of ``params`` rounded to 255 levels of its own largest
    magnitude, IN PLACE leaf by leaf (a second copy of 12 GB does not fit):
    the engine that owns them is done by then."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def to8(a):
        f = a.astype(jnp.float32)
        scale = jnp.abs(f).max() / 127.0
        return (jnp.round(f / scale) * scale).astype(a.dtype)

    for lp in params["layers"] + [params]:
        for name in list(lp):
            if getattr(lp[name], "ndim", 0) >= 2:
                lp[name] = to8(lp[name])
    return params


def check(engine, mcfg, finished, window: dict, seed: int, traffic: dict,
          n_streams: int = 8, control: bool = False):
    """``serving.check`` with the family's reference (module docstring)."""
    import jax
    reference = importlib.import_module(
        "chipbench.reference_" + mcfg.family)
    model = importlib.import_module(
        "replicatinggpt_tpu.models." + mcfg.family)
    tol, margin = float(traffic["logit_tol"]), float(traffic["tie_margin"])
    mean_tol = float(traffic["mean_gap_tol"])
    route_tol = float(traffic["route_mismatch_tol"])
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
    c = summary["counters"]
    steps = max(c.get("decode_steps", 0), 1)
    common.note("family_counters",
                kv_global_bytes=summary["kv_global_bytes"],
                kv_window_bytes=summary["kv_window_bytes"],
                moe_pairs_held=c.get("moe_pairs_held"),
                moe_pairs_held_per_step=c.get("moe_pairs_held", 0) / steps,
                tokens_per_held_expert_per_step=(
                    c.get("moe_pairs_held", 0) / steps
                    / max(len(mcfg.experts_held)
                          * sum(map(mcfg.is_sparse_layer,
                                    range(mcfg.n_layer))), 1)))
    params = engine.params
    rng = np.random.default_rng(seed)
    pick = [finished[i] for i in
            rng.permutation(len(finished))[:n_streams]] if finished else []
    prompts = [np.asarray(s.req.prompt) for s in pick]
    streams = [np.asarray(s.result.tokens, np.int32) for s in pick]
    engine.pool.cache = None         # the pool and the reference's
    gc.collect()                     # activations do not fit together
    pad_to = mcfg.block_size
    routing = jax.jit(lambda p, idx: model.forward(
        p, idx, mcfg, return_routing=True)[1][:, 0])
    choices = []
    for p, s in zip(prompts, streams):
        idx = np.zeros((1, pad_to), np.int32)
        idx[0, :len(p) + len(s) - 1] = np.concatenate([p, s[:-1]])
        choices.append(routing(params, idx))
    if control:
        params = _round_to_8_bits(params)
    gaps, mean_gap, ties = reference.stream_gaps(
        params, reference.spec_of(mcfg), pad_to, prompts, streams,
        choices=choices, margin=margin)
    # router decisions (token, sparse layer) of the teacher-forced rows
    decisions = sum(len(p) + len(s) - 1 for p, s in zip(prompts, streams)) \
        * sum(map(mcfg.is_sparse_layer, range(mcfg.n_layer)))
    mismatch_share = ties["mismatches"] / max(decisions, 1)
    common.note("reference_streams", streams=len(gaps),
                stream_tokens=[len(s) for s in streams],
                prompt_tokens=[len(p) for p in prompts],
                worst_logit_gap=max(gaps, default=None), gaps=gaps,
                tol=tol, why=traffic["logit_tol_why"],
                mean_logit_gap=mean_gap, mean_tol=mean_tol,
                mean_why=traffic["mean_gap_tol_why"],
                near_ties=ties["near_ties"],
                routing_mismatches=ties["mismatches"],
                routing_decisions=decisions,
                route_mismatch_share=mismatch_share, route_tol=route_tol,
                route_why=traffic["route_mismatch_tol_why"],
                tie_margin=margin, tie_margin_why=traffic["tie_margin_why"],
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
    if not common.compare("route_mismatch_share", mismatch_share, route_tol):
        problems.append(f"the program's router chose other experts than "
                        f"the reference's, beyond the tie margin, at "
                        f"{mismatch_share:.4f} of its decisions, tolerance "
                        f"{route_tol}")
    return problems


def step_mfu_pct(config: dict, mcfg, win: dict, sizes=None):
    """``serving.step_mfu_pct`` of the FLOPs the configuration file's share
    of the model needs for the window (``flops.exaone_moe_serve_flops``).
    The engine counts the routed pairs that landed on held experts in its
    decode steps (``moe_pairs_held``); a prefilled row is taken to land as
    many. A window layer's row reads ``sliding_window`` positions (a row
    younger than the window fewer: under 1% of the rows of this mix)."""
    from chipbench import flops
    decoded, prefilled = win["tokens"], win["prefill_tokens"]
    pairs = win["counter_deltas"].get("moe_pairs_held", 0)
    return serving.step_mfu_pct(getattr(flops, mcfg.family + "_serve_flops")(
        config, tokens=decoded + prefilled, emitted=decoded,
        pairs_held=pairs * (decoded + prefilled) / max(decoded, 1),
        full_context_pairs=win["live_token_steps"],
        window_context_pairs=config["sliding_window"] * win["active_steps"]),
        win["seconds"], sizes)


def run(ctx: common.Ctx, sizes=None, control: bool = False) -> dict:
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
    win["step_mfu_pct"] = step_mfu_pct(ctx.cell["config"], mcfg, win, sizes)
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
                queue_at_close=window.queue_at_close,
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
