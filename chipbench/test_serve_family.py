"""Quick CPU tests of what PR 29 adds to the benchmark: the five readers of
the ``exaone_moe`` family's names on a recorded chip trace
(``testdata/serve_decode_kexaone_v5e.json``), their silence on a program that
lacks those names (GPT-2's recorded trace: the parent), the configuration
file against the catalog's numbers and the program's preset, the mix's
lengths, and the new cells' entries.

    python -m pytest chipbench/test_serve_family.py -q
"""

from __future__ import annotations

import json
import os
import re
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import manifest, serving, trace_reduce, trace_stats  # noqa: E402

MAN = manifest.Manifest(ROOT)
DATA = os.path.join(ROOT, "chipbench", "testdata")
NEW = ["moe_experts_ms", "moe_router_ms", "moe_expert_hbm_pct",
       "swa_attention_ms", "swa_attention_hbm_pct"]
HBM = 819e9


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _read(metric, stats, kind):
    plain = trace_stats.names_only(stats)
    if os.path.exists(MAN.path("layer_metrics", metric + ".py")):
        return MAN.reader(metric)({}, plain, stats=stats, device_kind=kind)
    return MAN.reader(metric)({}, plain)


@pytest.fixture(scope="module")
def kexaone():
    return _load("serve_decode_kexaone_v5e.json")


def _ops(doc):
    return next(iter(doc["stats_trace"]["devices"].values()))[trace_reduce.OPS]


def _self_ms(doc, scope):
    """Device self time under a scope, by hand: the recorded launch's ops
    do not nest but for the ``while`` that holds them all."""
    rx = trace_stats.scope_rx(scope)
    return sum(e[2] for e in _ops(doc)
               if rx.search(e[3]) and not e[0].startswith("while")) / 1e6


@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_the_recorded_launch(kexaone, metric):
    got = _read(metric, kexaone["stats_trace"], kexaone["device_kind"])
    assert got == pytest.approx(
        kexaone["expect_stats"]["metrics"][metric], rel=1e-9)


def test_readers_agree_with_sums_made_by_hand(kexaone):
    st, kind = kexaone["stats_trace"], kexaone["device_kind"]
    launch = next(h for h in st["host"] if h[0] == "serve/launch")[3]
    swa_ms = sum(e[2] for e in _ops(kexaone)
                 if re.match(r"^swa_", e[0])) / 1e6
    assert len([e for e in _ops(kexaone) if e[0].startswith("swa_")]) == 6
    assert _read("swa_attention_ms", st, kind) == pytest.approx(swa_ms)
    assert _read("swa_attention_hbm_pct", st, kind) == pytest.approx(
        100 * launch["swa_kv_bytes"] / HBM * 1e3 / swa_ms)
    experts_ms = _read("moe_experts_ms", st, kind)
    assert experts_ms == pytest.approx(_self_ms(kexaone, "moe_experts"),
                                       rel=1e-4)
    assert _read("moe_expert_hbm_pct", st, kind) == pytest.approx(
        100 * launch["expert_weight_bytes"] / HBM * 1e3 / experts_ms)
    assert _read("moe_router_ms", st, kind) == pytest.approx(
        _self_ms(kexaone, "moe_router"), rel=1e-2)


def test_no_share_of_a_roofline_passes_100(kexaone):
    st, kind = kexaone["stats_trace"], kexaone["device_kind"]
    for metric in ("moe_expert_hbm_pct", "swa_attention_hbm_pct",
                   "paged_attention_hbm_pct"):
        assert 0 < _read(metric, st, kind) < 100, metric


def test_expert_bytes_is_what_the_program_counted(kexaone):
    mod = MAN._module(MAN.path("layer_metrics", "moe_expert_hbm_pct.py"),
                      "moe_expert_hbm_pct_under_test")
    cfg = _load(os.path.join("..", "configs", "k-exaone-236b-a23b.json"))
    want = mod.expert_bytes(
        held=cfg["num_experts"],
        sparse_layers=cfg["mlp_layer_types"].count("sparse"),
        hidden=cfg["hidden_size"], width=cfg["moe_intermediate_size"],
        itemsize=2, steps=1)
    launch = next(h for h in kexaone["stats_trace"]["host"]
                  if h[0] == "serve/launch")[3]
    assert want == launch["expert_weight_bytes"] == 8_455_716_864
    # the window layers' bytes are bounded by the window whatever the context
    assert launch["swa_kv_bytes"] == 64 * 128 * 6 * 2 * 1024 * 2
    assert launch["live_kv_bytes"] == launch["live_tokens"] * 2 * 2 * 1024 * 2


@pytest.mark.parametrize("metric", NEW)
def test_reader_is_silent_where_the_program_lacks_the_name(metric):
    """GPT-2's trace, which is what the PARENT gives in any cell: no such
    scope, kernel or stat, so the reader returns None and does not raise."""
    gpt = _load("serve_decode_large_v5e_named.json")
    assert _read(metric, gpt["stats_trace"], "TPU v5 lite") is None
    assert MAN.reader(metric)({}, None) is None           # untraced run


def test_accepted_readers_on_the_new_family(kexaone):
    """The kernel of the full layers keeps its name, so the accepted
    readers find it; a full layer's pages are an array of their own, so
    there is no copy under ``kv_gather`` to read."""
    st, kind = kexaone["stats_trace"], kexaone["device_kind"]
    paged = _read("paged_attention_ms", st, kind)
    assert paged == pytest.approx(kexaone["expect_stats"]["metrics"]
                                  ["paged_attention_ms"])
    assert _read("pallas_kernel_ms.serve", st, kind) == pytest.approx(
        paged + _read("swa_attention_ms", st, kind))
    assert _read("prefill_kv_gather_ms", st, kind) is None


# ------------------------------------------------- configuration, mix, cells

def test_configuration_file_keeps_every_published_number():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    cfg = _load(os.path.join("..", "configs", "k-exaone-236b-a23b.json"))
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"])
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert cfg[key] == row["config"][key][:cfg["num_hidden_layers"]]
    # depth, experts held, vocabulary, and the three per-layer lists cut
    # with the depth: no width among them
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "sliding_windows"}
    assert cfg["published"]["num_experts"] == row["config"]["num_experts"]
    assert cfg["router_outputs"] == row["config"]["num_experts"]
    assert cfg["not_built"]["num_nextn_predict_layers"] == 1


def test_the_file_is_what_the_program_is_given():
    from replicatinggpt_tpu.config import get_config
    kind = MAN.kind("serve_closed_family")
    cell = MAN.cell("serve-decode-kexaone")
    mcfg = kind.model_config(cell["config"])
    assert mcfg == get_config("k-exaone-236b-a23b").model
    assert (mcfg.n_embd, mcfg.n_head, mcfg.kv_heads, mcfg.head_dim) == (
        6144, 64, 8, 128)
    assert (mcfg.n_experts, len(mcfg.experts_held),
            mcfg.experts_per_token) == (128, 16, 8)
    tiny = kind.model_config(cell["config"], sizes=["--preset", "test-tiny"])
    assert tiny.family == "exaone_moe" and tiny.n_embd == 64
    assert tiny.block_size == cell["config"]["block_size"]


def test_a_file_that_states_what_the_body_does_not_compute_is_refused():
    kind = MAN.kind("serve_closed_family")
    config = dict(MAN.cell("serve-decode-kexaone")["config"])
    assert (config["scoring_func"], config["norm_topk_prob"]) == ("sigmoid",
                                                                  True)
    with pytest.raises(SystemExit):
        kind.model_config({**config, "scoring_func": "softmax"})
    with pytest.raises(SystemExit):
        kind.model_config({**config, "norm_topk_prob": False})


def test_the_control_rounds_every_matrix_to_8_bits_in_place():
    """``control_serve_family.py``'s 8-bit reference: at most 255 levels a
    matrix, gains left alone, the same dicts (12 GB has no second copy)."""
    import jax
    import numpy as np
    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.models import exaone_moe as xm
    kind = MAN.kind("serve_closed_family")
    cfg = get_config("exaone-moe-tiny").model
    params = xm.init_params(jax.random.PRNGKey(0), cfg)
    layer = params["layers"][1]
    before = {n: np.asarray(layer[n]) for n in ("e_gate", "norm1")}
    assert kind._round_to_8_bits(params) is params
    assert params["layers"][1] is layer
    assert np.array_equal(layer["norm1"], before["norm1"])
    for a in (layer["e_gate"], layer["router"], params["wte"],
              params["lm_head"]):
        assert len(np.unique(np.asarray(a))) <= 255
    moved = np.abs(np.asarray(layer["e_gate"]) - before["e_gate"]).max()
    assert 0 < moved <= np.abs(before["e_gate"]).max() / 254 * 1.001


def test_reasoning_mix_lengths_and_cells():
    cell = MAN.cell("serve-decode-kexaone")
    t = cell["traffic"]
    n = t["clients"] * t["requests_per_client"]
    prompts = serving.sizes_of(t["prompt"], n)
    outputs = serving.sizes_of(t["output"], n)
    assert 740 < prompts.mean() < 880 and 1200 < outputs.mean() < 1340
    assert prompts.max() + outputs.max() - 1 <= cell["config"]["block_size"]
    assert (t["kind"], t["schedule_seed"], t["clients"]) == (
        "serve_closed_family", 29, 80)
    eng = cell["program"]["engine"]
    assert (eng["pool_size"], eng["n_pages"], eng["prefill_chunk"],
            eng["decode_window"], eng["prefix_cache"]) == (64, 10240, 256, 1,
                                                           False)


def test_p27_is_serve_decode_large_under_another_order():
    a, b = MAN.cell("serve-decode-large"), MAN.cell("serve-decode-large-p27")
    assert a["program"] == b["program"] and a["config"] == b["config"]
    ta, tb = dict(a["traffic"]), dict(b["traffic"])
    assert (ta.pop("schedule_seed"), tb.pop("schedule_seed")) == (26, 27)
    assert ta == tb
    names = lambda c, s: {m["name"] for m in MAN.metrics(s, c)}
    for section in ("end_to_end", "per_layer"):
        assert names("serve-decode-large", section) == names(
            "serve-decode-large-p27", section)


def test_new_cell_reports_what_it_must():
    e2e = {m["name"] for m in MAN.metrics("end_to_end",
                                          "serve-decode-kexaone")}
    assert e2e == {"tpot_p80_ms", "serve_tokens_per_s", "setup_s"}
    layer = {m["name"] for m in MAN.metrics("per_layer",
                                            "serve-decode-kexaone")}
    assert set(NEW) <= layer and "prefill_kv_gather_ms" not in layer
    for m in MAN.doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == ["serve-decode-kexaone"]
            assert m["moves"] == "tpot_p80_ms"
