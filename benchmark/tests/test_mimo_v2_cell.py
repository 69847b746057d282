"""The MiMo-V2-Flash cell's files end to end at a tiny size on the CPU
(`lib/run_serveany.py` as it is, the tiny configuration in the cell's
place), the configuration's file against the catalog's rule, and the
cost functions and readers the cell brought, on synthetic traces."""
import json
import os
import sys
import time

import pytest

from benchmark.lib import (harness, mimo_cost, peaks, program_spans,
                           scope_time, stats, trace_reduce)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "mimo-v2-flash.serve-closed"
READERS = ("decode_step_roofline_swa.serve", "prefill_mfu_pct_swa.serve",
           "decode_attn_uneven_roofline.serve")
SHARES = ("full_layers_time_pct_swa.serve",
          "window_layers_time_pct_swa.serve", "experts_time_pct_swa.serve")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture
def cfg():
    return _json(harness.BENCH_DIR, "configs", "mimo-v2-flash.json")


@pytest.fixture
def lifted(monkeypatch, tmp_path):
    """As `test_run_serveany.py` lifts the device check."""
    monkeypatch.setattr(harness, "REQUIRE_PLATFORM", None)
    monkeypatch.setattr(harness, "OUT_ROOT", str(tmp_path / "out"))
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path / "work"))
    monkeypatch.setitem(peaks.PEAKS, "cpu", (1e12, 1e11, 2 ** 34, "test"))
    monkeypatch.setattr(stats, "BEYOND", 0)
    real = trace_reduce.load_xplane
    monkeypatch.setattr(
        trace_reduce, "load_xplane", lambda d: real(
            d, lambda n: n == "/host:CPU",
            ("tf_XLAPjRtCpuClient", "tf_XLAEigen")))
    monkeypatch.setattr(harness, "setup_env",
                        lambda root: str(tmp_path / "cache"))
    bench = _json(harness.ROOT, "BENCHMARK.json")

    def load_cell(root, name):
        cell = {w["name"]: w for w in bench["workloads"]}[CELL]
        return (bench, dict(cell, chips=1),
                _json(HERE, "tiny", "mimo-v2-tiny.json"),
                _json(HERE, "tiny", "mixed-tiny-any.json"))

    monkeypatch.setattr(harness, "load_cell", load_cell)


@pytest.mark.parametrize("trace", [0, 1])
def test_mimo_v2_cell_runs_tiny(lifted, capsys, trace):
    rc = harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 5),
                       "--seconds", "1.5", "--trace", str(trace)],
                      time.time())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    if trace:
        assert {"slot_occupancy_pct.serve", "decode_tokens_per_s.serve",
                "request_ms_p90.serve", "moe_load_max_over_mean.serve",
                } <= set(res["metrics"])
    else:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    checks = [ln for ln in out if ln.startswith("check ")]
    assert len(checks) == 4 and all(ln.endswith("ok") for ln in checks)


def test_comparison_sees_each_part_left_out(lifted, monkeypatch, tmp_path):
    """`tools/variants_serveany.py` at the tiny size: the program passes
    against the reference and fails against a reference with the sink,
    the value scale, the per-kind key/value heads, the rotation or the
    expert layer left out or put wrong. A prompt of 70 tokens wraps the
    window of 8 eight times."""
    from benchmark.reference import mimo_v2
    from benchmark.tools import variants_serveany as tool

    recs = []
    monkeypatch.setattr(tool, "_emit", recs.append)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "argv", [
        "variants_serveany.py", "--workload", CELL, "--seed",
        str(2 ** 31 + 7), "--variants", ",".join(mimo_v2.VARIANTS),
        "--prompt-lens", "70"])
    tool.main()
    assert len(recs) == 1 + len(mimo_v2.VARIANTS)
    for rec in recs:
        whole = "+" not in rec["reference"]
        assert rec["ok"] is whole, rec
        assert (rec["program_vs_reference"] <= rec["limit"]) is whole


def test_configuration_keeps_the_catalogs_numbers(cfg):
    """Every key of the catalog's `config` under the same key; what
    differs is named in `reduced`; the share, the assumed fields and
    the cell's sizes are written down."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = [json.loads(ln) for ln in f
               if '"name": "MiMo-V2-Flash"' in ln][0]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"} == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]
    bench = _json(harness.ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["mimo-v2-flash"]
    assert set(entry["reduced"]) == differs
    assert entry["source"] == row["source_url"]
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 8]
    assert (cfg["n_routed_experts_scored"]
            == row["config"]["n_routed_experts"] == 256)
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == row[
        "config"]["vocab_size"]
    assert (cfg["num_hidden_layers"],
            cfg["num_hidden_layers_published"]) == (7, 48)
    # the published widths
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"],
            cfg["head_dim"], cfg["v_head_dim"], cfg["sliding_window"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"]) == (
                4096, 64, 4, 8, 192, 128, 128, 16384, 2048, 8)
    a = cfg["assumed"]
    assert (a["rope_layout"], a["window_counts_self"],
            a["value_scale_on"]) == ("half_split", True, "v")
    assert all(a[k + "_why"].startswith("ASSUMED") for k in (
        "rope_layout", "window_counts_self", "value_scale_on"))
    assert "log 128" in a["weights"] and "32" in cfg["deployment"]
    assert (cfg["serve"]["slots"], cfg["serve"]["max_seq"]) == (16, 16384)
    assert cfg["check"]["serve"]["prompt_lens"] == [100, 12000]
    mix = _json(harness.BENCH_DIR, "traffic", "mixed-closed-2x-any.json")
    # sigma 0.8: ISSUE 54's named fall-back for the spread, taken
    assert mix["prompt_len"] == {"median": 2048, "sigma": 0.8, "min": 256,
                                 "max": 15360}
    assert mix["max_new"]["median"] == 256 and mix["max_new"]["sigma"] == 0.6
    assert (mix["max_new"]["min"], mix["max_new"]["max"]) == (64, 768)
    assert (mix["clients_per_slot"], mix["requests"]) == (2, 136)
    assert mix["warm_admit_sizes"] == [1, 2, 4]
    assert (mix["ramp_group"], mix["settle_seconds"],
            mix["trace_seconds"]) == (4, 3.0, 3.0)
    assert mix["prompt_len"]["max"] + mix["max_new"]["max"] <= 16128


def test_builder_reads_the_published_keys(cfg):
    import numpy as np

    from benchmark.models import mimo_v2_lm

    dc = mimo_v2_lm.decode_config(cfg, "serve_closed")
    assert dc.layer_kinds() == ["attention"] + ["sliding"] * 4 + [
        "attention", "sliding"]
    assert dc.ffn_kinds() == ["dense"] + ["experts"] * 6
    assert (dc.n_head, dc.d_head, dc.v_head, dc.d_model, dc.window) == (
        64, 192, 128, 4096, 128)
    assert (dc.kv_heads("attention"), dc.kv_heads("sliding")) == (4, 8)
    assert dc.attn_sink == ["sliding"] and dc.attn_value_scale == 0.707
    assert dc.rope == {"full": {"rotary_dim": 64, "theta": 5e6},
                       "sliding": {"rotary_dim": 64, "theta": 1e4}}
    assert (dc.n_expert, dc.expert_top_k, dc.d_expert, dc.d_shared_expert,
            dc.held, dc.router_score, dc.router_bias, dc.router_scale) == (
        256, 8, 2048, 0, (0, 8), "sigmoid", True, 1.0)
    assert dc.attn_gate is None and not dc.positions
    specs = mimo_v2_lm.parameter_specs(cfg, "serve_closed")
    total = sum(int(np.prod(s)) for _, s, _ in specs)
    assert round(total / 1e9, 3) == 2.222                # parameters held
    assert sum(n.endswith(".sink") for n, _, _ in specs) == 5
    assert not [n for n, _, _ in specs if ".shared." in n]
    # the bytes a slot keeps: two slabs of flat rows, five rings
    from paddle_tpu.serving.decode import cache_spec

    per_slot = sum(e.nbytes for e in cache_spec(dc, 1, 16384))
    assert per_slot == (2 * 16384 * 4 * 320 + 5 * 128 * 8 * 320) * 4
    assert round(per_slot / 1e6, 1) == 174.3
    # an assumed field of another value is refused
    for field, other in (("rope_layout", "interleaved"),
                         ("window_counts_self", False),
                         ("value_scale_on", "output")):
        bad = dict(cfg, assumed=dict(cfg["assumed"], **{field: other}))
        with pytest.raises(ValueError, match=field):
            mimo_v2_lm.decode_config(bad, "serve_closed")


def test_cost_functions_of_the_published_widths(cfg):
    assert mimo_cost.kinds(cfg) == ["full"] + ["sliding"] * 4 + [
        "full", "sliding"]
    assert round(mimo_cost.attn_params(cfg, "full") / 1e6, 2) == 89.13
    assert round(mimo_cost.attn_params(cfg, "sliding") / 1e6, 2) == 94.37
    assert mimo_cost.expert_params(cfg) == 3 * 4096 * 2048   # 100.7 MB
    assert round(4 * mimo_cost.dense_params(cfg) / 1e9, 2) == 3.74
    assert mimo_cost.kv_row_bytes(cfg) == 10240
    assert mimo_cost.ring_row_bytes(cfg) == 51200
    assert mimo_cost.step_bytes(cfg, 20, 50000, 2000) == (
        4 * mimo_cost.dense_params(cfg) + 20 * 100663296 + 50000 * 10240
        + 2000 * 51200)
    # 1.79 GFLOP of products a token with half an expert pair a layer
    assert round(mimo_cost.prefill_flops(
        cfg, 1, 6 * 8 * 8 / 256.0, 0, 0, 0) / 1e9, 2) == 1.79
    assert mimo_cost.prefill_flops(cfg, 1, 1, 1, 1, 1) == (
        2.0 * mimo_cost.row_params(cfg) + 2.0 * 3 * 4096 * 2048
        + 2.0 * 64 * 320 * (2 + 5) + 2.0 * 4096 * 19072)


def _run_of(cfg, ops, modules, host):
    return {"cfg": cfg, "peaks": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"path": "synthetic"}, "cell": {"name": CELL},
            "_spans": {"ops": {"/device:TPU:0": ops},
                       "modules": {"/device:TPU:0": modules}, "host": host}}


@pytest.fixture
def synthetic(monkeypatch):
    monkeypatch.setattr(program_spans, "of_run",
                        lambda run: run.get("_spans"))
    monkeypatch.setattr(scope_time, "of_run", lambda run: None)


def test_readers_on_a_synthetic_trace(cfg, synthetic):
    """One decode step of 10 ms and one prefill of 200 ms: the decode
    attention is found by its calls' own name, and each share counts
    what must be done."""
    ms = 1e6
    ops = [("ptpu.decode_attn_uneven.1", 0.0, 1.0 * ms, ""),
           ("ptpu.decode_attn_uneven.2", 1.0 * ms, 1.0 * ms, ""),
           ("fusion.4", 2.0 * ms, 8.0 * ms, ""),
           ("ptpu.attn_window.5", 20 * ms, 30 * ms, ""),
           ("ptpu.flash_fwd.6", 50 * ms, 20 * ms, ""),
           ("fusion.9", 70 * ms, 150 * ms, "")]
    modules = [("jit_ptpu_decode_b16_s16384(1)", 0.0, 10 * ms),
               ("jit_ptpu_prefill_b4_s1024(2)", 20 * ms, 200 * ms)]
    host = [(program_spans.DISPATCH, -0.1 * ms, 0.05 * ms,
             {"active": 16, "attended": 60000, "streamed": 65536,
              "ring_rows": 2000, "expert_pairs": 3, "experts_active": 3},
             "loop"),
            (program_spans.LOOP + "scatter", 221 * ms, 1 * ms,
             {"entries": 14, "prompt_rows": 3000, "bucket_rows": 4096,
              "prompts": 4, "attn_pairs": 4 * 750 * 751 // 2,
              "window_pairs": 4 * (128 * 129 // 2 + 622 * 128),
              "ring_rows": 512, "expert_pairs": 700}, "loop")]
    run = _run_of(cfg, ops, modules, host)
    read = lambda name: harness.load_layer_metric(name).read(run)  # noqa
    # the kernel's calls by name, with no scope map at all
    assert read("decode_attn_uneven_roofline.serve") is None
    import benchmark.lib.scope_time as st

    evs = [(n, s, d) for n, s, d, _ in ops[:3]]
    m = {"ops": {}, "scoped": True, "params": {}}
    joined = [("jit_ptpu_decode_b16_s16384", 1, 1e7, evs, m, 1.0)]
    st.of_run = lambda run: ({"busy_s": 0.21}, joined)
    assert read("decode_attn_uneven_roofline.serve") == pytest.approx(
        100 * 60000 * 10240 / 819e9 / 2e-3)
    # the lax path: no call of the name, the scope in the map
    m["ops"] = {"fusion.4": {"scope": ["fl.decode_attention_uneven:x",
                                       "ptpu.decode_attn_uneven"],
                             "members": [], "users": [], "reads": [],
                             "pass": "fwd"}}
    lax = _run_of(cfg, ops[2:], modules, host)
    joined[0] = ("jit_ptpu_decode_b16_s16384", 1, 1e7, evs[2:], m, 1.0)
    assert harness.load_layer_metric(
        "decode_attn_uneven_roofline.serve").read(lax) == pytest.approx(
            100 * 60000 * 10240 / 819e9 / 8e-3)
    assert read("decode_step_roofline_swa.serve") == pytest.approx(
        100 * mimo_cost.step_bytes(cfg, 3, 60000, 2000) / 819e9 / 10e-3)
    flops = mimo_cost.prefill_flops(
        cfg, 3000, 700, 4 * 750 * 751 // 2,
        4 * (128 * 129 // 2 + 622 * 128), 4)
    assert read("prefill_mfu_pct_swa.serve") == pytest.approx(
        100 * flops / (197e12 * 0.2))
    joined[0] = ("jit_ptpu_decode_b16_s16384", 1, 1e7, evs, m, 1.0)
    for name in READERS:
        assert 0 < read(name) < 100, name
    # a configuration of another family, or a program without the
    # counts (the parent), reads nothing and does not raise
    other = dict(run, cfg={"layer_types": [], "sliding_window": 512,
                           "serve": {}})
    for name in READERS + SHARES:
        assert harness.load_layer_metric(name).read(other) is None
    bare = _run_of(cfg, ops, modules, [
        (program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, {"active": 16},
         "loop"),
        (program_spans.LOOP + "scatter", 221 * ms, 1 * ms,
         {"entries": 14, "ring_rows": 3}, "loop")])
    for name in READERS:
        assert harness.load_layer_metric(name).read(bare) is None


def test_time_shares_tell_the_layer_kinds_by_scope_and_weight(cfg,
                                                              monkeypatch):
    """The three shares over a synthetic join: an event is a layer
    kind's by its scope, a fused member's, the scope its result goes to
    or a weight it reads; an elementwise event anchored at a temporary
    is nobody's."""
    def entry(scope=(), members=(), users=(), reads=()):
        return {"scope": list(scope), "members": list(members),
                "users": list(users), "reads": list(reads), "pass": "fwd"}

    ops_map = {
        "fusion.1": entry(["fl.mul:lm.l5.attention.q.w"]),
        "ptpu.flash_fwd.2": entry(["fl.prefill_attention:x",
                                   "ptpu.flash_fwd"]),
        "fusion.3": entry(members=["fl.mul:lm.l2.attention.o.w"]),
        "ptpu.attn_window.4": entry(["fl.prefill_attention:y",
                                     "ptpu.attn_window"]),
        "slice-done.5": entry(users=["fl.moe_route:lm.l2.moe.router.w"],
                              reads=["state['lm.l2.moe.router.w']"]),
        "fusion.6": entry(["fl.moe_experts:lm.l2.moe.experts.gate.w",
                           "ptpu.moe_experts"]),
        "fusion.7": entry(["fl.elementwise_add:tmp_9"]),
        "fusion.8": entry(["fl.mul:lm.l0.mlp.up.w"]),
    }
    m = {"ops": ops_map, "scoped": True, "params": {}}
    evs = [(n, 0.0, 1e9) for n in ops_map]
    joined = [("jit_ptpu_prefill_b1_s512", 1, 8e9, evs, m, 1.0)]
    monkeypatch.setattr(scope_time, "of_run",
                        lambda run: ({"busy_s": 10.0}, joined))
    run = {"cfg": cfg}
    got = [harness.load_layer_metric(n).read(run) for n in SHARES]
    assert got == [pytest.approx(20.0)] * 3
    monkeypatch.setattr(scope_time, "of_run", lambda run: None)
    assert [harness.load_layer_metric(n).read(run) for n in SHARES] == [
        None] * 3


def test_benchmark_json_lists_the_cell_where_a_reader_reads_it():
    bench = _json(harness.ROOT, "BENCHMARK.json")
    mine = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    assert set(READERS + SHARES) <= mine
    assert {"moe_load_max_over_mean.serve", "state_scatter_ms.serve",
            "prefill_busy_pct.serve", "scope_named_pct.serve",
            "prefill_dense_pct.serve", "decode_dense_roofline.serve",
            "slot_occupancy_pct.serve", "token_gap_ms_p95.serve"} <= mine
    assert len({"request_ms_p90.serve", "request_ms_p80.serve"} & mine) == 1
    # these read Laguna's keys (`layer_types`, `num_experts`,
    # `mlp_layer_types`): they would need an edit, so they are not listed
    assert not {"moe_time_pct.serve", "attn_time_pct.serve",
                "moe_experts_roofline.serve",
                "decode_step_roofline_moe.serve",
                "decode_attn_grouped_roofline.serve"} & mine
    for m in bench["per_layer"]:
        if m["name"] in READERS + SHARES:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in harness.metrics_of(
        bench, "end_to_end", CELL)} == {"serve_tokens_per_s", "setup_s"}
    assert len(bench["workloads"]) >= 13 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
