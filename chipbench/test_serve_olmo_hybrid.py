"""Quick CPU tests of what the ``olmo_hybrid`` cell adds to the benchmark:
its four readers on recorded chip traces (one decode launch,
``testdata/serve_decode_olmo_hybrid_v5e.json``, and one prefill launch,
``testdata/serve_prefill_olmo_hybrid_v5e.json``) and their silence on
programs that lack the recurrence's kernel, scope and stats (the other
families' recorded traces: what the parent gives), the configuration file
against the catalog's numbers and the program's preset, the FLOPs and
bytes functions against counts made by hand, the mix, and the new entries.

    python -m pytest chipbench/test_serve_olmo_hybrid.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import (flops_olmo_hybrid, manifest, serving,  # noqa: E402
                       trace_stats)

MAN = manifest.Manifest(ROOT)
DATA = os.path.join(ROOT, "chipbench", "testdata")
NEW = ["gdn_decode_ms", "gdn_decode_hbm_pct", "gdn_prefill_ms",
       "gdn_prefill_roofline_pct"]
CELL = "serve-decode-olmo-hybrid"
RECORDED = {"gdn_decode_ms": "decode", "gdn_decode_hbm_pct": "decode",
            "gdn_prefill_ms": "prefill", "gdn_prefill_roofline_pct": "prefill"}


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
def olmo_config():
    return MAN.cell(CELL)["config"]


@pytest.fixture(scope="module")
def olmo_launches():
    return {tag: _load(f"serve_{tag}_olmo_hybrid_v5e.json")
            for tag in ("decode", "prefill")}


#: the published configuration this cell's file cuts: Olmo-Hybrid-7B's
#: config.json (SOURCE), every key the catalog keeps
SOURCE = ("https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/"
          "config.json")
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


# ------------------------------------------------ the recorded traced runs

@pytest.mark.parametrize("metric", NEW)
def test_olmo_reader_reads_the_recorded_launch(olmo_launches, metric):
    doc = olmo_launches[RECORDED[metric]]
    got = _read(metric, doc["stats_trace"], doc["device_kind"])
    assert got == pytest.approx(doc["expect_stats"]["metrics"][metric],
                                rel=1e-9)


def test_olmo_prefill_kv_gather_reads_the_full_layers_page_gather(
        olmo_launches):
    """The shared ``prefill_kv_gather_ms`` reads the two full layers' gather
    of the slot's pages in a prefill chunk (``layers._prefill_full_attention``
    under ``kv_gather``), and nothing in a decode launch."""
    got = {tag: _read("prefill_kv_gather_ms", doc["stats_trace"],
                      doc["device_kind"])
           for tag, doc in olmo_launches.items()}
    assert got["prefill"] == pytest.approx(0.041179, rel=1e-9)
    assert got["decode"] is None


def _span(doc, name):
    return [h[3] for h in doc["stats_trace"]["host"] if h[0] == name]


def test_olmo_decode_readers_agree_with_sums_made_by_hand(olmo_launches,
                                                          olmo_config):
    """The recurrence's six calls summed by hand, the bytes stat by the
    reader's own function, the share under 100%, and the recurrence the
    largest device-time share of the launch: the cell's mechanism does
    most of its work."""
    doc = olmo_launches["decode"]
    st, kind = doc["stats_trace"], doc["device_kind"]
    ops = next(iter(st["devices"].values()))["XLA Ops"]
    gdn = [e for e in ops if e[0].startswith("gdn_recurrence")]
    paged = [e for e in ops if e[0].startswith("paged_window_attention")]
    c = olmo_config
    assert len(gdn) == c["layer_types"].count("linear_attention") == 6
    assert len(paged) == c["layer_types"].count("full_attention") == 2
    ms = sum(e[2] for e in gdn) / 1e6
    assert _read("gdn_decode_ms", st, kind) == pytest.approx(ms)
    assert _read("pallas_kernel_ms.serve", st, kind) == pytest.approx(
        ms + sum(e[2] for e in paged) / 1e6)
    launch = _span(doc, "serve/launch")[0]
    assert launch["delta_state_bytes"] == _module(
        "gdn_decode_hbm_pct").delta_state_bytes(
        launch["n_active"], 6, c["linear_num_value_heads"],
        c["linear_key_head_dim"], c["linear_value_head_dim"])
    # the full layers alone are pages: 2 x 2 x 30 x 128 x 2 B a token
    assert launch["live_kv_bytes"] == launch["live_tokens"] * 30_720
    assert "short_conv_bytes" not in launch
    share = _read("gdn_decode_hbm_pct", st, kind)
    assert share == pytest.approx(
        100 * launch["delta_state_bytes"] / 819e9 * 1e3 / ms)
    assert 0 < share < 100
    launch_ms = doc["stats_trace"]["devices"]["/device:TPU:0"][
        "XLA Modules"][0][2] / 1e6
    assert ms > 0.35 * launch_ms
    assert ms > sum(e[2] for e in paged) / 1e6


def test_olmo_prefill_reader_agrees_with_sums_made_by_hand(olmo_launches,
                                                           olmo_config):
    """Every serve/prefill span's stats are the reader's functions over the
    span's chunks of 256 (the last one short), summed over the 6 linear
    layers; the share is the larger floor a launch over the scope's time."""
    doc = olmo_launches["prefill"]
    st, kind = doc["stats_trace"], doc["device_kind"]
    mod = _module("gdn_prefill_roofline_pct")
    c = olmo_config
    widths = (c["linear_num_value_heads"], c["linear_key_head_dim"],
              c["linear_value_head_dim"])
    spans = _span(doc, "serve/prefill")
    assert spans
    chunk = MAN.cell(CELL)["program"]["engine"]["prefill_chunk"]
    for s in spans:
        sizes = [min(chunk, s["tokens"] - at)
                 for at in range(0, s["tokens"], chunk)]
        assert len(sizes) == s["chunks"]
        assert s["gdn_scan_bytes"] == 6 * sum(
            mod.chunk_scan_bytes(t, *widths) for t in sizes)
        assert s["gdn_scan_flops"] == 6 * sum(
            mod.chunk_scan_flops(t, *widths) for t in sizes)
    took = _read("gdn_prefill_ms", st, kind)
    n = sum(s["chunks"] for s in spans)
    floor_ms = max(sum(s["gdn_scan_bytes"] for s in spans) / n / 819e9,
                   sum(s["gdn_scan_flops"] for s in spans) / n / 197e12) \
        * 1e3
    share = _read("gdn_prefill_roofline_pct", st, kind)
    assert share == pytest.approx(100 * floor_ms / took)
    assert 0 < share < 100


@pytest.mark.parametrize("recorded", ["serve_decode_large_v5e_named.json",
                                      "serve_decode_lfm2_v5e.json",
                                      "serve_decode_kexaone_v5e.json",
                                      "serve_decode_joyai_v5e.json",
                                      "serve_chat_large_v5e_prefill.json"])
@pytest.mark.parametrize("metric", NEW)
def test_olmo_reader_is_silent_where_the_program_lacks_the_name(metric,
                                                                recorded):
    """Another family's trace, which is what the PARENT gives in the new
    cell: no recurrence kernel, no ``gdn_*`` scope or stat, so the reader
    returns None and does not raise; nor does it in an untraced run."""
    doc = _load(recorded)
    kind = doc.get("device_kind", "TPU v5 lite")
    assert _read(metric, doc["stats_trace"], kind) is None
    assert MAN.reader(metric)({}, None) is None


def test_olmo_the_functions_are_what_the_program_counts(olmo_config):
    """The readers' byte and FLOP functions against the program's own
    (``ops.gated_delta.chunk_scan_cost``, the pool's state bytes)."""
    from replicatinggpt_tpu.ops.gated_delta import chunk_scan_cost
    mod = _module("gdn_prefill_roofline_pct")
    for t in (1, 63, 64, 65, 256):
        assert chunk_scan_cost(t, 30, 96, 192) == (
            mod.chunk_scan_bytes(t, 30, 96, 192),
            mod.chunk_scan_flops(t, 30, 96, 192))
    slot = _module("gdn_decode_hbm_pct").delta_state_bytes(1, 6, 30, 96, 192)
    assert slot == 2 * 13_271_040
    # 256 live slots move 6.79 GB a step: 8.3 ms at the v5e's 819 GB/s
    assert 256 * slot / 819e9 * 1e3 == pytest.approx(8.30, abs=0.01)


# ------------------------------------------------- configuration, mix, cells

def test_olmo_configuration_file_keeps_every_published_number(olmo_config):
    config = olmo_config
    assert config["source"] == SOURCE
    changed = {k for k, v in PUBLISHED.items() if config.get(k) != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers",
                                                 "layer_types"}
    assert config["published"]["num_hidden_layers"] == 32
    assert config["num_hidden_layers"] == 8
    assert config["layer_types"] == PUBLISHED["layer_types"][:8]
    # two whole periods, the published 3:1
    assert config["layer_types"].count("linear_attention") == 3 * \
        config["layer_types"].count("full_attention")
    for key in ("norms", "rope_theta", "output_gate", "conv_bias", "beta",
                "state_dtype"):
        assert key in config["assumed"]
    assert "head" in config["reduced_why"]
    entry = next(c for c in MAN.doc["configs"]
                 if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "chipbench/configs/olmo-hybrid-7b.json"


def test_olmo_the_file_laid_over_the_preset_is_the_preset(olmo_config):
    from replicatinggpt_tpu.config import get_config
    kind = MAN.kind("serve_closed_olmo_hybrid")
    mcfg = kind.model_config(olmo_config)
    assert mcfg == get_config("olmo-hybrid-7b").model
    tiny = kind.model_config(olmo_config, sizes=["--preset", "test-tiny"])
    assert tiny.family == "olmo_hybrid" and tiny.n_embd == 64
    assert tiny.block_size == olmo_config["block_size"]


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("tie_word_embeddings", True),
    ("rope_parameters", {"rope_theta": 10000}), ("head_dim", 96),
    ("preset", "no-such-preset")])
def test_olmo_a_file_that_states_what_the_body_does_not_compute_is_refused(
        olmo_config, key, value):
    """... and a program without the family (the parent: no such preset)
    fails at once, before any weight is made."""
    kind = MAN.kind("serve_closed_olmo_hybrid")
    assert olmo_config[key] != value
    with pytest.raises(SystemExit):
        kind.model_config({**olmo_config, key: value})


def test_olmo_flops_against_a_count_made_by_hand(olmo_config):
    h, V, F = 3840, 100_352, 11_008
    linear = (2 * h * (2 * 2880 + 2 * 5760 + 60) + 2 * 5760 * h
              + 2 * 11_520 * 4 + 7 * 30 * 96 * 192)
    assert flops_olmo_hybrid.linear_layer_flops(olmo_config) == linear
    full = 2 * 4 * h * h
    got = flops_olmo_hybrid.olmo_hybrid_serve_flops(
        olmo_config, tokens=10, emitted=4, full_context_pairs=1000)
    assert got == (10 * (6 * linear + 2 * full + 8 * 2 * 3 * h * F)
                   + 4 * 2 * h * V + 4 * h * 2 * 1000)
    # a decoded row: 4.08 GFLOP, the parameters' 2 a weight (2.44B, of
    # which the embedding is read, not multiplied) and the recurrence
    row = flops_olmo_hybrid.olmo_hybrid_serve_flops(
        olmo_config, tokens=1, emitted=1, full_context_pairs=0)
    assert 4.0e9 < row < 4.2e9


def test_olmo_the_mix_and_the_cell():
    cell = MAN.cell(CELL)
    t = cell["traffic"]
    ref = MAN.cell("serve-decode-large")["traffic"]
    assert (t["kind"], t["schedule_seed"], t["clients"],
            t["requests_per_client"]) == (
                "serve_closed_olmo_hybrid", 42, 320, 4)
    # ShareGPT as sharegpt-closed fits it, clipped at 2,048 (not GPT-2's)
    for key in ("prompt", "output"):
        assert {k: t[key][k] for k in ("dist", "median", "sigma", "min")} \
            == {k: ref[key][k] for k in ("dist", "median", "sigma", "min")}
        assert t[key]["max"] == 2048
    n = t["clients"] * t["requests_per_client"]
    prompts = serving.sizes_of(t["prompt"], n)
    outputs = serving.sizes_of(t["output"], n)
    assert prompts.max() + outputs.max() - 1 <= cell["config"]["block_size"]
    eng = cell["program"]["engine"]
    assert (eng["pool_size"], eng["page_size"], eng["n_pages"],
            eng["prefill_chunk"], eng["decode_window"], eng["paged_kernel"],
            eng["prefix_cache"]) == (256, 16, 10_240, 256, 1, True, False)
    # 640 tokens a slot of full-layer KV: 5.03 GB at 30,720 B a token
    assert eng["n_pages"] * eng["page_size"] * 30_720 == 5_033_164_800
    assert eng["prefill_chunk"] % 64 == 0           # whole chunks of the scan
    for word in ("logit_tol", "mean_gap_tol", "ramp"):
        assert t[word + "_why"]
    assert 0 < t["mean_gap_tol"] < t["logit_tol"]


def test_olmo_new_cell_reports_what_it_must():
    e2e = {m["name"] for m in MAN.metrics("end_to_end", CELL)}
    assert e2e == {"tpot_p80_ms", "serve_tokens_per_s", "setup_s"}
    layer = {m["name"] for m in MAN.metrics("per_layer", CELL)}
    assert layer == set(NEW) | {
        "compile_s", "batch_fill", "kv_live_share", "kv_pages_reserved",
        "engine_step_ms", "host_dispatch_ms_per_token",
        "host_serial_ms_per_step", "serve_step_mfu",
        "pallas_kernel_ms.serve", "paged_attention_ms",
        "paged_attention_hbm_pct", "decode_sample_ms", "prefill_kv_gather_ms"}
    names = [m["name"] for m in MAN.doc["per_layer"]]
    for m in MAN.doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p80_ms"
            assert m["layer"] == "linear attention layer"
    # appended after every entry that was there before them, in order;
    # entries added later may follow
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == NEW
    assert first > names.index("mla_attention_hbm_pct")
    configs = [c["name"] for c in MAN.doc["configs"]]
    assert configs.index("olmo-hybrid-7b") > configs.index("joyai-llm-flash")
    cells = [w["name"] for w in MAN.doc["workloads"]]
    assert cells.index(CELL) > cells.index("serve-decode-joyai")
    entry = MAN.doc["workloads"][cells.index(CELL)]
    assert entry["chips"] == 1
    assert len(entry["why"]) <= 200
