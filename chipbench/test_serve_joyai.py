"""Quick CPU tests of what the ``joyai_llm_flash`` cell adds to the benchmark:
its two readers on a recorded chip trace
(``testdata/serve_decode_joyai_v5e.json``) and their silence on programs
that lack the latent kernel and its stat (the other families' recorded
traces: what the parent gives), the
configuration file against the catalog's numbers and the program's preset,
the FLOPs and bytes functions against counts made by hand, the mix, and the
new entries.

    python -m pytest chipbench/test_serve_joyai.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import (flops_joyai_llm_flash, manifest,  # noqa: E402
                       serving, trace_stats)

MAN = manifest.Manifest(ROOT)
DATA = os.path.join(ROOT, "chipbench", "testdata")
NEW = ["mla_attention_ms", "mla_attention_hbm_pct"]
CELL = "serve-decode-joyai"


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _read(metric, stats, kind):
    plain = trace_stats.names_only(stats)
    if os.path.exists(MAN.path("layer_metrics", metric + ".py")):
        return MAN.reader(metric)({}, plain, stats=stats, device_kind=kind)
    return MAN.reader(metric)({}, plain)


def _module(metric):
    return MAN._module(MAN.path("layer_metrics", metric + ".py"),
                       metric + "_under_test")


@pytest.fixture(scope="module")
def joyai_config():
    return MAN.cell(CELL)["config"]


#: the published configuration this cell's file cuts: JoyAI-LLM-Flash's
#: config.json (SOURCE), every key that says something of its shape
SOURCE = ("https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
          "config.json")
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}


# ------------------------------------------------- the recorded traced run

@pytest.fixture(scope="module")
def joyai():
    return _load("serve_decode_joyai_v5e.json")


def _launch(doc):
    return next(h for h in doc["stats_trace"]["host"]
                if h[0] == "serve/launch")[3]


@pytest.mark.parametrize("metric", NEW)
def test_joyai_reader_reads_the_recorded_launch(joyai, metric):
    got = _read(metric, joyai["stats_trace"], joyai["device_kind"])
    assert got == pytest.approx(joyai["expect_stats"]["metrics"][metric],
                                rel=1e-9)


def test_joyai_readers_agree_with_sums_made_by_hand(joyai, joyai_config):
    """The kernel's ten calls summed by hand, the bytes stat by the
    reader's own function, the share below 90% (576 of a row's 640
    stored lanes are the model's), and the kernel the largest device-time
    share of the launch: the cell's mechanism does most of its work."""
    st, kind = joyai["stats_trace"], joyai["device_kind"]
    ops = next(iter(st["devices"].values()))["XLA Ops"]
    kernels = [e for e in ops if e[0].startswith("paged_mla_attention")]
    assert len(kernels) == joyai_config["num_hidden_layers"]
    ms = sum(e[2] for e in kernels) / 1e6
    assert _read("mla_attention_ms", st, kind) == pytest.approx(ms)
    assert _read("pallas_kernel_ms.serve", st, kind) == pytest.approx(ms)
    launch = _launch(joyai)
    c = joyai_config
    assert launch["mla_kv_bytes"] == _module("mla_attention_hbm_pct") \
        .mla_kv_bytes(launch["live_tokens"], c["num_hidden_layers"],
                      c["kv_lora_rank"], c["qk_rope_head_dim"], 2)
    assert launch["live_kv_bytes"] == launch["live_tokens"] * 12_800
    share = _read("mla_attention_hbm_pct", st, kind)
    assert share == pytest.approx(100 * trace_stats.mean_stat(
        st, "serve/launch", "mla_kv_bytes") / 819e9 * 1e3 / ms)
    assert 0 < share < 90
    assert ms > 0.5 * joyai["expect"]["busy_s"] * 1e3
    # every named scope of the family is on the launch's ops
    by = dict(trace_stats.by_scope(st, ["attn_mla", "moe_experts",
                                        "moe_shared", "moe_router", "mlp",
                                        "head", "sample", "kv_scatter"]))
    assert all(by.get(scope, 0) > 0 for scope in (
        "attn_mla", "moe_experts", "moe_shared", "moe_router", "mlp",
        "head", "sample", "kv_scatter")), by
    assert by["attn_mla"] > by["moe_experts"]


# ------------------------------------------------- the readers

@pytest.mark.parametrize("recorded", ["serve_decode_large_v5e_named.json",
                                      "serve_decode_lfm2_v5e.json",
                                      "serve_decode_kexaone_v5e.json"])
@pytest.mark.parametrize("metric", NEW)
def test_joyai_reader_is_silent_where_the_program_lacks_the_name(metric,
                                                                 recorded):
    """Another family's trace, which is what the PARENT gives in the new
    cell: no latent kernel, no ``mla_kv_bytes`` stat, so the reader returns
    None and does not raise; nor does it in an untraced run."""
    doc = _load(recorded)
    kind = doc.get("device_kind", "TPU v5 lite")
    assert _read(metric, doc["stats_trace"], kind) is None
    assert MAN.reader(metric)({}, None) is None


def test_joyai_the_bytes_function_is_what_the_program_counts(joyai_config):
    """``mla_kv_bytes`` of the reader's file against the engine's stat:
    the model's latent rows (576 values of 2 B a layer), not the 640 lanes
    the pool stores them in."""
    from replicatinggpt_tpu.config import get_config
    c = joyai_config
    fn = _module("mla_attention_hbm_pct").mla_kv_bytes
    one = fn(live_tokens=1, layers=c["num_hidden_layers"],
             kv_lora_rank=c["kv_lora_rank"],
             qk_rope_head_dim=c["qk_rope_head_dim"], itemsize=2)
    assert one == 11_520
    cfg = get_config("joyai-llm-flash").model
    assert one == (len(cfg.paged_layers)
                   * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 2)
    assert cfg.kv_token_width * 2 * 10 == 12_800       # what the pool holds
    # the cell's fill: ~416k live tokens read 4.79 GB a step, 5.85 ms at
    # the v5e's 819 GB/s
    assert fn(416_000, 10, 512, 64, 2) / 819e9 * 1e3 == pytest.approx(
        5.85, abs=0.01)


# ------------------------------------------------- configuration, mix, cells

def test_joyai_configuration_file_keeps_every_published_number(joyai_config):
    config = joyai_config
    assert config["source"] == SOURCE
    changed = {k for k, v in PUBLISHED.items() if config.get(k) != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["published"] == {k: PUBLISHED[k]
                                   for k in config["reduced"]}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (10, 32, 16_160)
    assert config["experts_held"] == list(range(32))
    assert config["router_outputs"] == PUBLISHED["n_routed_experts"]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the guide's floors: 4 layers or more after the dense one, 8 experts
    # or more, an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert len(config["experts_held"]) >= 8
    assert "num_nextn_predict_layers" in config["not_built"]
    assert config["num_nextn_predict_layers"] == 1       # stated, not cut
    for key in ("head_dim", "norms", "router_norm_eps", "ep_size"):
        assert key in config["assumed"]
    entry = next(c for c in MAN.doc["configs"]
                 if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "chipbench/configs/joyai-llm-flash.json"


def test_joyai_the_file_laid_over_the_preset_is_the_preset(joyai_config):
    from replicatinggpt_tpu.config import get_config
    config = joyai_config
    kind = MAN.kind("serve_closed_joyai")
    mcfg = kind.model_config(config)
    assert mcfg == get_config("joyai-llm-flash").model
    assert (mcfg.n_embd, mcfg.n_head, mcfg.q_lora_rank, mcfg.kv_lora_rank,
            mcfg.qk_nope_head_dim, mcfg.qk_rope_head_dim, mcfg.v_head_dim) \
        == (2048, 32, 1536, 512, 128, 64, 128)
    assert (mcfg.n_experts, len(mcfg.experts_held), mcfg.experts_per_token,
            mcfg.moe_intermediate_size, mcfg.shared_intermediate_size,
            mcfg.intermediate_size, mcfg.vocab_size) \
        == (256, 32, 8, 768, 768, 7168, 16_160)
    assert (mcfg.rope_theta, mcfg.layernorm_eps, mcfg.routed_scaling) \
        == (3.2e7, 1e-6, 2.5)
    assert mcfg.mlp_layer_types == ("dense",) + ("sparse",) * 9
    # a file that states another size is another model, not this preset
    assert kind.model_config({**config, "kv_lora_rank": 256}).kv_lora_rank \
        == 256
    tiny = kind.model_config(config, sizes=["--preset", "test-tiny"])
    assert tiny.family == "joyai_llm_flash" and tiny.n_embd == 64
    assert tiny.block_size == config["block_size"]


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("topk_method", "greedy"), ("n_group", 8),
    ("rope_interleave", False), ("rope_scaling", {"type": "yarn"}),
    ("moe_layer_freq", 2), ("tie_word_embeddings", True),
    ("n_routed_experts", 31), ("head_dim", 128),
    ("preset", "no-such-preset")])
def test_joyai_a_file_that_states_what_the_body_does_not_compute_is_refused(
        joyai_config, key, value):
    """... and a program without the family (the parent: no such preset)
    fails at once, before any weight is made."""
    kind = MAN.kind("serve_closed_joyai")
    assert joyai_config[key] != value
    with pytest.raises(SystemExit):
        kind.model_config({**joyai_config, key: value})


def test_joyai_flops_against_a_count_made_by_hand(joyai_config):
    h, V, H = 2048, 16_160, 32
    attn = (h * 1536 + 1536 * H * 192 + h * 576 + 512 * H * 256
            + H * 128 * h)
    assert attn == flops_joyai_llm_flash.attention_weights(joyai_config) \
        == 26_345_472                    # the 26,351,616 less the 4 norms
    ctx = 2 * H * (576 + 512)
    assert ctx == flops_joyai_llm_flash.context_flops(joyai_config) == 69_632
    per_row = (10 * 2 * attn + 2 * 3 * h * 7168
               + 9 * 2 * (h * 256 + 3 * h * 768))
    pair = 2 * 3 * h * 768
    got = flops_joyai_llm_flash.joyai_llm_flash_serve_flops(
        joyai_config, tokens=10, emitted=4, pairs_held=10 * 9,
        full_context_pairs=1000)
    assert got == (10 * per_row + 90 * pair + 4 * 2 * h * V
                   + 10 * ctx * 1000)
    # a decoded row at 1 held expert a sparse layer: 0.86 GFLOP, and
    # attention over 1,600 positions of context 1.1 GFLOP more: the
    # kernel's work is most of a row's
    row = flops_joyai_llm_flash.joyai_llm_flash_serve_flops(
        joyai_config, tokens=1, emitted=1, pairs_held=9,
        full_context_pairs=0)
    assert 0.85e9 < row < 0.87e9
    assert 10 * ctx * 1600 > row


def test_joyai_the_mix_and_the_cell():
    cell = MAN.cell(CELL)
    t = cell["traffic"]
    ref = MAN.cell("serve-decode-lfm2")["traffic"]
    assert (t["kind"], t["schedule_seed"], t["clients"],
            t["requests_per_client"], t["ramp_s"]) == (
                "serve_closed_joyai", 42, 320, 4, ref["ramp_s"])
    # the lengths and batch of reasoning-closed-b256: the three expert
    # cells differ by model alone
    assert t["prompt"] == ref["prompt"] and t["output"] == ref["output"]
    n = t["clients"] * t["requests_per_client"]
    prompts = serving.sizes_of(t["prompt"], n)
    outputs = serving.sizes_of(t["output"], n)
    assert prompts.max() + outputs.max() - 1 <= cell["config"]["block_size"]
    eng = cell["program"]["engine"]
    assert eng == MAN.cell("serve-decode-lfm2")["program"]["engine"]
    assert (eng["pool_size"], eng["n_pages"], eng["decode_window"],
            eng["paged_kernel"], eng["prefix_cache"]) == (
                256, 40_960, 1, True, False)
    for word in ("logit_tol", "mean_gap_tol", "route_mismatch_tol",
                 "tie_margin", "ramp"):
        assert word in t or word + "_s" in t
        assert t[word + "_why"]


def test_joyai_new_cell_reports_what_it_must():
    e2e = {m["name"] for m in MAN.metrics("end_to_end", CELL)}
    assert e2e == {"tpot_p80_ms", "serve_tokens_per_s", "setup_s"}
    layer = {m["name"] for m in MAN.metrics("per_layer", CELL)}
    assert layer == set(NEW) | {
        "compile_s", "batch_fill", "kv_live_share", "kv_pages_reserved",
        "engine_step_ms", "host_dispatch_ms_per_token",
        "host_serial_ms_per_step", "serve_step_mfu",
        "pallas_kernel_ms.serve", "decode_sample_ms"}
    names = [m["name"] for m in MAN.doc["per_layer"]]
    for m in MAN.doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p80_ms"
            assert m["layer"] == "serve kernels"
    # appended after every entry that was there before them, in order;
    # entries added later may follow
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == NEW
    assert first > names.index("lfm2_expert_hbm_pct")
    configs = [c["name"] for c in MAN.doc["configs"]]
    assert configs.index("joyai-llm-flash") > configs.index("lfm2-24b-a2b")
    cells = [w["name"] for w in MAN.doc["workloads"]]
    assert cells.index(CELL) > cells.index("serve-chat-large-p31")
    entry = MAN.doc["workloads"][cells.index(CELL)]
    assert entry["chips"] == 1
    assert len(entry["why"]) <= 200
