"""Quick CPU tests of what PR 37 adds to the benchmark: the four readers of
the ``lfm2_moe`` cell's names on a recorded chip trace
(``testdata/serve_decode_lfm2_v5e.json``), their silence on a program that
lacks those names (GPT-2's recorded trace: the parent), the configuration
file against the catalog's numbers and the program's preset, the FLOPs and
bytes functions against counts made by hand, the mix, and the new cells'
entries.

    python -m pytest chipbench/test_serve_lfm2.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import (flops_lfm2_moe, manifest, serving,  # noqa: E402
                       trace_reduce, trace_stats)

MAN = manifest.Manifest(ROOT)
DATA = os.path.join(ROOT, "chipbench", "testdata")
NEW = ["short_conv_ms", "short_conv_hbm_pct", "lfm2_experts_ms",
       "lfm2_expert_hbm_pct"]
CELL = "serve-decode-lfm2"
HBM = 819e9


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
def lfm2():
    return _load("serve_decode_lfm2_v5e.json")


@pytest.fixture(scope="module")
def lfm2_config():
    return MAN.cell(CELL)["config"]


def _ops(doc):
    return next(iter(doc["stats_trace"]["devices"].values()))[trace_reduce.OPS]


def _self_ms(doc, scope):
    """Device self time under a scope, by hand: the recorded launch's ops
    do not nest but for the ``while`` that holds them all; an op XLA left
    without an ``op_name`` counts where the nearest ops that have one,
    before and after it, are both under the scope."""
    rx = trace_stats.scope_rx(scope)
    ops = sorted((e for e in _ops(doc) if not e[0].startswith("while")),
                 key=lambda e: e[1])
    under = [bool(rx.search(e[3])) if e[3] else None for e in ops]
    total = 0.0
    for i, e in enumerate(ops):
        if under[i] is None:
            before = next((u for u in under[i::-1] if u is not None), False)
            after = next((u for u in under[i:] if u is not None), False)
            if not (before and after):
                continue
        elif not under[i]:
            continue
        total += e[2]
    return total / 1e6


def _launch(doc):
    return next(h for h in doc["stats_trace"]["host"]
                if h[0] == "serve/launch")[3]


# ------------------------------------------------- the recorded traced run

@pytest.mark.parametrize("metric", NEW)
def test_lfm2_reader_reads_the_recorded_launch(lfm2, metric):
    got = _read(metric, lfm2["stats_trace"], lfm2["device_kind"])
    assert got == pytest.approx(lfm2["expect_stats"]["metrics"][metric],
                                rel=1e-9)


def test_lfm2_readers_agree_with_sums_made_by_hand(lfm2):
    st, kind = lfm2["stats_trace"], lfm2["device_kind"]
    launch = _launch(lfm2)
    conv_ms = _read("short_conv_ms", st, kind)
    assert conv_ms == pytest.approx(_self_ms(lfm2, "short_conv"), rel=1e-2)
    assert _read("short_conv_hbm_pct", st, kind) == pytest.approx(
        100 * launch["short_conv_bytes"] / HBM * 1e3 / conv_ms)
    experts_ms = _read("lfm2_experts_ms", st, kind)
    assert experts_ms == pytest.approx(_self_ms(lfm2, "moe_experts"),
                                       rel=1e-2)
    assert _read("lfm2_expert_hbm_pct", st, kind) == pytest.approx(
        100 * launch["expert_weight_bytes"] / HBM * 1e3 / experts_ms)
    # the readers that serve-decode-kexaone's metrics are pinned to read
    # the same scope and stat to the same numbers
    assert _read("moe_experts_ms", st, kind) == pytest.approx(experts_ms)
    assert _read("moe_expert_hbm_pct", st, kind) == pytest.approx(
        _read("lfm2_expert_hbm_pct", st, kind))


def test_lfm2_no_share_of_a_roofline_passes_100(lfm2):
    st, kind = lfm2["stats_trace"], lfm2["device_kind"]
    for metric in ("short_conv_hbm_pct", "lfm2_expert_hbm_pct",
                   "paged_attention_hbm_pct"):
        assert 0 < _read(metric, st, kind) < 100, metric


def test_lfm2_the_bytes_functions_are_what_the_program_counted(
        lfm2, lfm2_config):
    config = lfm2_config
    launch = _launch(lfm2)
    conv = _module("short_conv_hbm_pct")
    n_conv = config["layer_types"].count("conv")
    weights = conv.short_conv_bytes(
        conv_layers=n_conv, hidden=config["hidden_size"],
        reach=config["conv_L_cache"], itemsize=2, live_slots=0)
    assert weights == 8 * (2048 * 6144 + 2048 * 2048 + 2048 * 3) * 2
    live = launch["n_active"]
    assert launch["conv_state_bytes"] == live * 98_304
    assert launch["short_conv_bytes"] == conv.short_conv_bytes(
        conv_layers=n_conv, hidden=config["hidden_size"],
        reach=config["conv_L_cache"], itemsize=2, live_slots=live)
    experts = _module("moe_expert_hbm_pct").expert_bytes(
        held=len(config["experts_held"]),
        sparse_layers=config["num_hidden_layers"]
        - config["num_dense_layers"], hidden=config["hidden_size"],
        width=config["moe_intermediate_size"], itemsize=2, steps=1)
    assert experts == launch["expert_weight_bytes"] == 9_663_676_416
    assert launch["moe_rows"] == live
    # the full layers alone: 4,096 B a context token
    assert launch["live_kv_bytes"] == launch["live_tokens"] * 4096
    assert "swa_kv_bytes" not in launch


@pytest.mark.parametrize("metric", NEW)
def test_lfm2_reader_is_silent_where_the_program_lacks_the_name(metric):
    """GPT-2's trace, which is what the PARENT gives in any cell: no such
    scope or stat, so the reader returns None and does not raise; and the
    conv readers find nothing in K-EXAONE's launch either."""
    gpt = _load("serve_decode_large_v5e_named.json")
    assert _read(metric, gpt["stats_trace"], "TPU v5 lite") is None
    assert MAN.reader(metric)({}, None) is None           # untraced run
    if metric.startswith("short_conv"):
        kexaone = _load("serve_decode_kexaone_v5e.json")
        assert _read(metric, kexaone["stats_trace"],
                     kexaone["device_kind"]) is None


def test_lfm2_accepted_readers_on_the_new_family(lfm2):
    """The full layers' kernel keeps its name, so the accepted readers find
    it, and it is the only Pallas kernel of the launch."""
    st, kind = lfm2["stats_trace"], lfm2["device_kind"]
    paged = _read("paged_attention_ms", st, kind)
    assert paged == pytest.approx(lfm2["expect_stats"]["metrics"]
                                  ["paged_attention_ms"])
    assert _read("pallas_kernel_ms.serve", st, kind) == pytest.approx(paged)
    assert _read("swa_attention_ms", st, kind) is None
    assert _read("decode_sample_ms", st, kind) > 0


# ------------------------------------------------- configuration, mix, cells

def test_lfm2_configuration_file_keeps_every_published_number(lfm2_config):
    config = lfm2_config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert config["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if config.get(k) != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers",
                                                 "layer_types"}
    assert config["layer_types"] == row["config"]["layer_types"][:10]
    # after the leading dense layers two whole periods, 1 full to 3 conv
    after = config["layer_types"][config["num_dense_layers"]:]
    assert len(after) == 8 and after.count("conv") \
        == 3 * after.count("full_attention")
    assert config["published"]["num_hidden_layers"] == 40
    assert config["experts_held"] == list(range(row["config"]["num_experts"]))
    entry = next(c for c in MAN.doc["configs"]
                 if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for key in ("tie_word_embeddings", "head_dim", "router_norm_eps"):
        assert key in config["assumed"] and key not in row["config"]


def test_lfm2_the_file_laid_over_the_preset_is_the_preset(lfm2_config):
    config = lfm2_config
    from replicatinggpt_tpu.config import get_config
    kind = MAN.kind("serve_closed_lfm2")
    mcfg = kind.model_config(lfm2_config)
    assert mcfg == get_config("lfm2-24b-a2b").model
    assert (mcfg.n_embd, mcfg.n_head, mcfg.kv_heads, mcfg.head_dim) == (
        2048, 32, 8, 64)
    assert (mcfg.n_experts, len(mcfg.experts_held), mcfg.experts_per_token,
            mcfg.vocab_size, mcfg.conv_reach) == (64, 64, 4, 65_536, 3)
    # a file that states another size is another model, not this preset
    assert kind.model_config({**config, "conv_L_cache": 4}).conv_reach == 4
    tiny = kind.model_config(config, sizes=["--preset", "test-tiny"])
    assert tiny.family == "lfm2_moe" and tiny.n_embd == 64
    assert tiny.block_size == config["block_size"]


@pytest.mark.parametrize("key,value", [("norm_topk_prob", False),
                                       ("use_expert_bias", False),
                                       ("conv_bias", True)])
def test_lfm2_a_file_that_states_what_the_body_does_not_compute_is_refused(
        lfm2_config, key, value):
    config = lfm2_config
    kind = MAN.kind("serve_closed_lfm2")
    assert config[key] == (not value)
    with pytest.raises(SystemExit):
        kind.model_config({**config, key: value})
    with pytest.raises(SystemExit):          # the parent: no such preset
        kind.model_config({**config, "preset": "no-such-preset"})


def test_lfm2_flops_against_a_count_made_by_hand(lfm2_config):
    config = lfm2_config
    h, V = 2048, 65_536
    conv = 2 * (h * 3 * h + h * h + h * 3)          # in, out, three taps
    attn = 2 * (h * (2048 + 2 * 512) + 2048 * h)    # q, k, v, o
    per_row = (8 * conv + 2 * attn + 2 * 2 * 3 * h * 11_776
               + 8 * 2 * h * 64)
    pair = 2 * 3 * h * 1536
    got = flops_lfm2_moe.lfm2_moe_serve_flops(
        config, tokens=10, emitted=4, pairs_held=10 * 8 * 4,
        full_context_pairs=1000)
    assert got == (10 * per_row + 320 * pair + 4 * 2 * h * V
                   + 4 * 2048 * 2 * 1000)
    # a decoded row at 4 of 64 experts a sparse layer: 1.47 GFLOP, of
    # which the routed experts 0.60, the dense MLPs 0.29, the conv
    # layers' projections 0.27 and the head 0.27
    row = flops_lfm2_moe.lfm2_moe_serve_flops(
        config, tokens=1, emitted=1, pairs_held=32, full_context_pairs=0)
    assert row == per_row + 32 * pair + 2 * h * V
    assert 1.47e9 < row < 1.48e9
    # what the program pushes through every expert is 16 times the pairs
    assert 64 * 8 * pair == 16 * 32 * pair


def test_lfm2_the_mix_and_the_cells():
    cell = MAN.cell(CELL)
    t = cell["traffic"]
    ref = MAN.cell("serve-decode-kexaone")["traffic"]
    assert (t["kind"], t["schedule_seed"], t["clients"],
            t["requests_per_client"]) == ("serve_closed_lfm2", 37, 320, 4)
    # the lengths of reasoning-closed: the two expert cells differ by model
    # and batch, not by lengths
    assert t["prompt"] == ref["prompt"] and t["output"] == ref["output"]
    n = t["clients"] * t["requests_per_client"]
    prompts = serving.sizes_of(t["prompt"], n)
    outputs = serving.sizes_of(t["output"], n)
    assert prompts.max() + outputs.max() - 1 <= cell["config"]["block_size"]
    eng = cell["program"]["engine"]
    assert (eng["pool_size"], eng["page_size"], eng["n_pages"],
            eng["prefill_chunk"], eng["decode_window"], eng["paged_kernel"],
            eng["prefix_cache"]) == (256, 16, 40_960, 256, 1, True, False)
    assert eng["n_pages"] * eng["page_size"] == 2560 * eng["pool_size"]
    for word in ("logit_tol", "mean_gap_tol", "route_mismatch_tol",
                 "tie_margin"):
        assert word in t and t[word + "_why"]


def test_p31_is_serve_chat_large_under_another_order():
    if not any(w["name"] == "serve-chat-large-p31"
               for w in MAN.doc["workloads"]):
        pytest.skip("serve-chat-large-p31 did not meet the recipe: left out")
    a, b = MAN.cell("serve-chat-large"), MAN.cell("serve-chat-large-p31")
    assert a["program"] == b["program"] and a["config"] == b["config"]
    ta, tb = dict(a["traffic"]), dict(b["traffic"])
    assert tb.pop("schedule_seed") in (31, 37)
    assert ta.pop("schedule_seed") == 26 and ta == tb
    names = lambda c, s: {m["name"] for m in MAN.metrics(s, c)}
    for section in ("end_to_end", "per_layer"):
        assert names("serve-chat-large", section) == names(
            "serve-chat-large-p31", section)


def test_lfm2_new_cell_reports_what_it_must():
    e2e = {m["name"] for m in MAN.metrics("end_to_end", CELL)}
    assert e2e == {"tpot_p80_ms", "serve_tokens_per_s", "setup_s"}
    layer = {m["name"] for m in MAN.metrics("per_layer", CELL)}
    assert layer == set(NEW) | {
        "compile_s", "batch_fill", "kv_live_share", "kv_pages_reserved",
        "engine_step_ms", "host_dispatch_ms_per_token",
        "host_serial_ms_per_step", "serve_step_mfu",
        "pallas_kernel_ms.serve", "paged_attention_ms",
        "paged_attention_hbm_pct", "decode_sample_ms"}
    for m in MAN.doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p80_ms"
    # new entries stand at the end of their lists
    assert [m["name"] for m in MAN.doc["per_layer"]][-4:] == NEW
    assert MAN.doc["configs"][-1]["name"] == "lfm2-24b-a2b"
    entry = next(w for w in MAN.doc["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
