"""Quick CPU tests of ``trace_stats.py`` and the readers built on it.

    python -m pytest chipbench/test_trace_stats.py -q

A hand-made case for each reader, and the cuts of chip traces recorded under
the program's own names (``testdata/*.json`` that carry a ``stats_trace``).
"""

from __future__ import annotations

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import manifest, trace_reduce, trace_stats  # noqa: E402

MAN = manifest.Manifest(ROOT)
DATA = os.path.join(ROOT, "chipbench", "testdata")
V5E = "TPU v5 lite"
DECODE = "jit__engine_decode_window(7)"
PREFIX = "jit(_engine_decode_window)/jit(main)/while/body/"


def _us(name, start, dur, extra):
    return [name, start * 1e3, dur * 1e3, extra]


def _hand_made():
    """Two decode launches of 100 us and one prefill launch, with the ops of
    one 'layer' under their scopes, and the host phases of three engine
    steps: two that launch, one (the middle) that only expires."""
    def launch(t0, module):
        body = PREFIX if module == DECODE else \
            "jit(_engine_prefill)/jit(main)/while/body/"
        return [
            _us("while.1 while", t0, 90, body[:-6]),     # holds the layer
            _us("dynamic-slice_bitcast_fusion.4 fusion", t0, 10,
                body + "kv_gather/dynamic_slice"),
            _us("dynamic-slice_bitcast_fusion.5 fusion", t0 + 10, 10,
                body + "kv_gather/kv_gather/dynamic_slice"),
            _us("paged_window_attention.3 custom-call tpu_custom_call",
                t0 + 20, 40, body + "attn/paged_window_attention"),
            _us("fusion.9 fusion", t0 + 60, 30, body + "mlp/dot_general"),
            _us("sort.1 sort", t0 + 90, 3,
                body[:-11] + "sample/jit(argsort)/sort"),
            # what XLA expands the scatter and the cumsum into: no op_name
            # of its own, or a bare one
            _us("sort.8 sort", t0 + 93, 2, ""),
            _us("copy.88 copy", t0 + 95, 1, "reduce_window_sum"),
            _us("fusion.3 fusion", t0 + 96, 3,
                body[:-11] + "sample/jit(take_along_axis)/gather"),
            # a hoisted cast between two scopes: nobody's
            _us("convert.14 convert", t0 + 99, 1, ""),
        ]
    ops = (launch(0, DECODE) + launch(200, "jit__engine_prefill(9)")
           + launch(400, DECODE))
    modules = [[DECODE, 0.0, 100e3],
               ["jit__engine_prefill(9)", 200e3, 100e3],
               [DECODE, 400e3, 100e3]]
    kv = 1000                                          # bytes a token
    host = [
        _us("serve/step", 0, 120, {"step": 1}),
        _us("serve/expire_shed", 1, 2, {}),
        _us("serve/decode", 5, 114, {}),
        _us("serve/launch", 6, 10, {"k": 1, "n_active": 2,
                                    "live_tokens": 30,
                                    "live_kv_bytes": 30 * kv}),
        _us("serve/fetch", 16, 90, {}),
        _us("serve/commit", 106, 12, {"step": 1, "tokens": 2}),
        _us("serve/step", 130, 20, {"step": 2}),     # nothing to launch
        _us("serve/expire_shed", 131, 18, {}),
        _us("serve/step", 190, 330, {"step": 2}),
        _us("serve/admit", 195, 110, {}),
        _us("serve/prefill", 200, 100, {"tokens": 64, "chunks": 1,
                                        "cached_tokens": 0}),
        _us("serve/launch", 400, 10, {"k": 1, "n_active": 3,
                                      "live_tokens": 50,
                                      "live_kv_bytes": 50 * kv}),
        _us("serve/fetch", 410, 95, {}),
        _us("serve/commit", 505, 14, {"step": 3, "tokens": 3}),
    ]
    return {"devices": {"/device:TPU:0": {trace_reduce.OPS: ops,
                                          trace_reduce.MODULES: modules}},
            "host": host}


def _read(metric, stats, **kw):
    """The metric's reader as ``run.py`` finds it, on a stats trace."""
    return MAN.reader(metric)({}, trace_stats.names_only(stats),
                              stats=stats, **kw)


def test_hlo_scopes_reads_op_names_out_of_a_capture(tmp_path):
    """A real capture (CPU, tiny): the ``Hlo Proto`` the profiler keeps of
    the program that ran gives every instruction its ``op_name``, named
    scopes and all; a file that holds no program gives nothing."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("sample"):
            return jnp.sort(x * 2.0)

    x = jnp.arange(64.0)
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    step(x).block_until_ready()
    jax.profiler.stop_trace()
    programs = trace_stats.hlo_scopes(trace_reduce.find_xplane(str(tmp_path)))
    name = next(p for p in programs if p.startswith("jit_step("))
    rx = trace_stats.scope_rx("sample")
    scoped = {i for i, s in programs[name].items() if rx.search(s)}
    assert any(i.startswith("sort") for i in scoped), programs[name]
    assert any("multiply" in i for i in scoped), programs[name]
    t = trace_stats.load(trace_reduce.find_xplane(str(tmp_path)))
    assert t["devices"] == {} and not trace_stats.has_scopes(t)   # CPU
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    assert trace_stats.hlo_scopes(str(empty)) == {}


def test_scope_rx_matches_one_component_of_a_path():
    rx = trace_stats.scope_rx("attn")
    assert rx.search("jit(run)/transpose(jvp(attn))/dot_general")
    assert rx.search("a/attn/b") and rx.search("attn")
    assert not rx.search("a/attn_out/b") and not rx.search("a/xattn/b")


def test_decode_sample_ms_on_a_hand_made_case():
    t = _hand_made()
    # sort 3 + gather 3 us in each of the two decode launches, and the 3 us
    # of the sort between them that has no op_name; the cast after them is
    # followed by the next launch's kv_gather and is not counted, nor is the
    # prefill launch, which has the same ops
    assert _read("decode_sample_ms", t) == pytest.approx(0.009)
    assert trace_stats.scope_self_ms_per_launch(
        t, "sample", r"^jit__engine_prefill\(") == pytest.approx(0.009)


def test_prefill_kv_gather_ms_on_a_hand_made_case():
    t = _hand_made()
    # the two slices of the ONE prefill launch, 10 us each; the decode
    # launches' slices, which have the same names and scopes, are not its
    assert _read("prefill_kv_gather_ms", t) == pytest.approx(0.020)
    assert trace_stats.scope_self_ms_per_launch(
        t, "kv_gather", r"^jit__engine_decode_window\(") == \
        pytest.approx(0.020)
    # two chunks of one admission: twice the time over twice the launches
    two = _hand_made()
    dev = two["devices"]["/device:TPU:0"]
    dev[trace_reduce.OPS] += [[n, s + 400e3, d, sc]
                              for n, s, d, sc in dev[trace_reduce.OPS]
                              if 200e3 <= s < 300e3]
    dev[trace_reduce.MODULES].append(["jit__engine_prefill(9)", 600e3, 100e3])
    assert _read("prefill_kv_gather_ms", two) == pytest.approx(0.020)
    # a trace in which nothing was admitted gives nothing to read
    none = _hand_made()
    dev = none["devices"]["/device:TPU:0"]
    dev[trace_reduce.MODULES] = [m for m in dev[trace_reduce.MODULES]
                                 if m[0] == DECODE]
    assert _read("prefill_kv_gather_ms", none) is None
    # the while that holds the layer counts for its own 0 us, not for the
    # 90 us of its body: nothing is left outside the scopes
    by = dict(trace_stats.by_scope(t, ["kv_gather", "attn", "mlp",
                                      "sample"]))
    assert by["kv_gather"] == pytest.approx(60e-6)
    assert by["attn"] == pytest.approx(120e-6)
    assert by["sample"] == pytest.approx(27e-6)
    assert by["mlp"] == pytest.approx(90e-6)
    assert by["(other)"] == pytest.approx(0.0)        # the whiles
    assert by["(no op_name)"] == pytest.approx(3e-6)  # the casts


def test_paged_attention_hbm_pct_on_a_hand_made_case():
    t = _hand_made()
    # mean live_kv_bytes 40,000 over 819e9 B/s = 48.84 ns, of 40 us a launch
    want = 100.0 * (40_000 / 819e9) / 40e-6
    assert _read("paged_attention_hbm_pct", t,
                 device_kind=V5E) == pytest.approx(want)
    assert trace_stats.mean_stat(t, "serve/launch",
                                 "live_tokens") == pytest.approx(40.0)
    with pytest.raises(KeyError):
        _read("paged_attention_hbm_pct", t, device_kind="TPU v9")


def test_host_serial_ms_per_step_on_a_hand_made_case():
    t = _hand_made()
    # step 1: 120 - (10 + 90) = 20 us; step 3: 330 - (100 + 10 + 95) = 125;
    # the step that launched nothing is left out
    assert trace_stats.uncovered_ms(
        t, "serve/step", "serve/launch",
        ["serve/launch", "serve/fetch", "serve/prefill"]) == \
        pytest.approx([0.020, 0.125])
    assert _read("host_serial_ms_per_step", t) == pytest.approx(0.125)


@pytest.mark.parametrize("metric", [
    "decode_sample_ms", "prefill_kv_gather_ms", "paged_attention_hbm_pct",
    "host_serial_ms_per_step"])
def test_readers_find_nothing_in_a_program_without_the_names(metric):
    """The parent of the PR that added the names: one coarse span, no
    stats, no scopes, kernels under the enclosing function's name. Every
    reader returns None and raises nothing; so does an untraced run."""
    t = _hand_made()
    for ops in t["devices"].values():
        ops[trace_reduce.OPS] = [
            [n.replace("paged_window_attention", "closed_call"), s, d, ""]
            for n, s, d, _ in ops[trace_reduce.OPS]]
    t["host"] = [_us("serve/decode", 5, 114, {}),
                 _us("serve/prefill", 200, 100, {})]
    assert not trace_stats.has_scopes(t)
    assert _read(metric, t, device_kind=V5E) is None
    assert MAN.reader(metric)({}, None) is None


def test_the_declarative_name_patterns():
    t = trace_stats.names_only(_hand_made())
    assert MAN.reader("paged_attention_ms")({}, t) == pytest.approx(0.040)
    train = {"devices": {"/device:TPU:0": {
        trace_reduce.OPS: [
            ["jvp_flash_group_fwd_.2 custom-call tpu_custom_call", 0, 4e6],
            ["flash_group_fwd.2 custom-call tpu_custom_call", 5e6, 4e6],
            ["flash_group_bwd.2 custom-call tpu_custom_call", 10e6, 5e6],
            ["transpose_jvp_flash_bwd_dq__.1 custom-call tpu_custom_call",
             16e6, 1e6],
            ["fusion.7 fusion", 18e6, 9e6]],
        trace_reduce.MODULES: [["jit_run(3)", 0, 30e6]]}}, "host": []}
    k = {"train": {"steps_per_dispatch": 2}}
    fwd = MAN.reader("flash_fwd_ms")(k, train)
    bwd = MAN.reader("flash_bwd_ms")(k, train)
    assert fwd == pytest.approx(4.0) and bwd == pytest.approx(3.0)
    assert fwd + bwd == pytest.approx(
        MAN.reader("pallas_kernel_ms.train")(k, train))


def test_current_finds_the_run_by_its_workload_argument(tmp_path):
    assert trace_stats.run_logdirs(["run.py", "--seed", "1"]) == []
    dirs = trace_stats.run_logdirs(["run.py", "--workload", "a-cell",
                                    "--trace", "1"])
    assert [os.path.relpath(d, ROOT) for d in dirs] == [
        os.path.join("chipbench_out", "trace", "a-cell"),
        os.path.join("chipbench_out", "rehearse", "a-cell")]
    assert trace_stats.current(["run.py", "--workload",
                                "no-such-cell"]) is None


def test_cut_keeps_stats_and_scopes():
    t = _hand_made()
    c = trace_stats.cut(t, max_events=7)          # the first launch
    ops = c["devices"]["/device:TPU:0"][trace_reduce.OPS]
    assert len(ops) == 7 and ops[1][3].endswith("kv_gather/dynamic_slice")
    assert len(c["devices"]["/device:TPU:0"][trace_reduce.MODULES]) == 1
    # the step open during those ops, whole: its commit came after them
    assert [h[0] for h in c["host"]] == [
        "serve/step", "serve/expire_shed", "serve/decode", "serve/launch",
        "serve/fetch", "serve/commit"]
    assert c["host"][3][3]["live_kv_bytes"] == 30_000
    assert trace_reduce.reduce(trace_stats.names_only(c))["busy_s"] > 0
    # from the middle: the third launch and the step that made it
    c = trace_stats.cut(t, max_events=3, first=21)
    dev = c["devices"]["/device:TPU:0"]
    assert [e[1] for e in dev[trace_reduce.OPS]] == [400e3, 410e3, 420e3]
    assert [m[1] for m in dev[trace_reduce.MODULES]] == [400e3]
    assert [h[0] for h in c["host"]] == [
        "serve/step", "serve/admit", "serve/prefill", "serve/launch",
        "serve/fetch", "serve/commit"]


RECORDED = sorted(f[:-5] for f in os.listdir(DATA) if f.endswith(".json")
                  and '"stats_trace"' in open(os.path.join(DATA, f)).read())


@pytest.mark.parametrize("name", RECORDED)
def test_readers_on_a_trace_recorded_on_the_chip(name):
    """A cut of a ``--trace 1`` run on the v5e under the program's own
    names, with what the readers made of it when it was recorded."""
    with open(os.path.join(DATA, name + ".json")) as f:
        doc = json.load(f)
    t, want = doc["stats_trace"], doc["expect_stats"]
    assert trace_stats.names_only(t) == doc["trace"]
    ops = next(iter(t["devices"].values()))[trace_reduce.OPS]
    kernels = [e[0] for e in ops if e[0].endswith("tpu_custom_call")]
    # a prefill chunk runs no Pallas kernel: its cut says so
    assert bool(kernels) == want.get("kernels", True) and not [
        k for k in kernels if k.startswith(("closed_call", "checkpoint",
                                            "rematted_computation"))]
    assert trace_stats.has_scopes(t) == want["has_scopes"]
    assert sorted({h[0] for h in t["host"]}) == want["host_spans"]
    for metric, value in want["metrics"].items():
        # a reader with a .py of its own takes the stats trace; a
        # declarative one reads the names alone
        own = os.path.exists(MAN.path("layer_metrics", metric + ".py"))
        kw = {"stats": t, "device_kind": V5E} if own else {}
        got = MAN.reader(metric)(want.get("counters", {}), doc["trace"], **kw)
        assert got == pytest.approx(value, rel=1e-9), metric
    # a scope that no metric reads in this launch any more, held to what it
    # read when the trace was recorded (``decode_kv_gather_ms`` until PR 36)
    for scope, w in want.get("scope_ms", {}).items():
        assert trace_stats.scope_self_ms_per_launch(
            t, scope, w["per"]) == pytest.approx(w["ms"], rel=1e-9), scope


def test_scopes_name_the_second_familys_launch():
    """``python3 -m chipbench.trace_stats`` prints a launch by ``SCOPES``:
    with PR 29's scopes in it K-EXAONE's decode launch shows its experts,
    router and shared expert and its two kinds of attention layer, and
    leaves next to nothing under ``(other)``."""
    with open(os.path.join(DATA, "serve_decode_kexaone_v5e.json")) as f:
        t = json.load(f)["stats_trace"]
    family = {"moe_experts", "moe_router", "moe_shared", "attn_swa",
              "attn_global"}
    by = dict(trace_stats.by_scope(t, list(trace_stats.SCOPES)))
    for scope in sorted(family):
        assert scope in trace_stats.SCOPES and by.get(scope, 0.0) > 0, scope
    total = sum(by.values())
    assert by["moe_experts"] > 0.3 * total         # the step's first cost
    assert by.get("(other)", 0.0) < 0.1 * total
    # GPT-2's launch has none of them and reads as it did
    with open(os.path.join(DATA, "serve_decode_large_v5e_named.json")) as f:
        gpt = json.load(f)["stats_trace"]
    assert not family & set(dict(trace_stats.by_scope(
        gpt, list(trace_stats.SCOPES))))
