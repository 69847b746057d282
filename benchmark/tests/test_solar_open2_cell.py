"""The Solar-Open2-250B cell's files end to end at a tiny size on the CPU
(`lib/run_serveany.py` as it is, the tiny configuration in the cell's
place), the configuration's file against the catalog's rule, and the
cost functions and readers the cell brought, on synthetic traces."""
import json
import os
import sys
import time

import pytest

from benchmark.lib import (harness, peaks, program_spans, scope_time,
                           solar_cost, stats, trace_reduce)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "solar-open2-250b.serve-closed"
READERS = ("decode_step_roofline_kdagqa.serve",
           "prefill_mfu_pct_kdagqa.serve",
           "kda_guarded_scan_roofline.serve",
           "kda_wide_step_roofline.serve",
           "decode_attn_nope_roofline.serve")
SHARES = ("kda_layers_time_pct_kdagqa.serve",
          "gqa_layer_time_pct_kdagqa.serve",
          "experts_time_pct_kdagqa.serve")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture
def cfg():
    return _json(harness.BENCH_DIR, "configs", "solar-open2-250b.json")


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
                _json(HERE, "tiny", "solar-open2-tiny.json"),
                _json(HERE, "tiny", "think-tiny-any.json"))

    monkeypatch.setattr(harness, "load_cell", load_cell)


@pytest.mark.parametrize("trace", [0, 1])
def test_solar_open2_cell_runs_tiny(lifted, capsys, trace):
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
    against the reference and fails against a reference with one part
    of the delta rule, its gates, the softmax layer or the expert layer
    left out or put wrong, or whose state is stale at the hand-over."""
    from benchmark.reference import solar_open2
    from benchmark.tools import variants_serveany as tool

    recs = []
    monkeypatch.setattr(tool, "_emit", recs.append)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    left_out = [v for v in solar_open2.VARIANTS if v]
    monkeypatch.setattr(sys, "argv", [
        "variants_serveany.py", "--workload", CELL, "--seed",
        str(2 ** 31 + 7), "--variants", ",".join(left_out),
        "--prompt-lens", "100"])
    tool.main()
    assert len(recs) == 1 + len(left_out)
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
               if '"name": "Solar-Open2-250B"' in ln][0]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"} == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]
    bench = _json(harness.ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["solar-open2-250b"]
    assert set(entry["reduced"]) == differs
    assert entry["source"] == row["source_url"]
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 20]
    assert (cfg["n_routed_experts_scored"]
            == row["config"]["n_routed_experts"] == 320)
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == row[
        "config"]["vocab_size"]
    assert (cfg["num_hidden_layers"],
            cfg["num_hidden_layers_published"]) == (4, 48)
    # the published widths
    lin = cfg["linear_attn_config"]
    assert (cfg["hidden_size"], lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]) == (
                4096, 64, 128, 4, 64, 8, 128, 1280, 8)
    a = cfg["assumed"]
    assert (a["kda_gate"], a["kda_rank"], a["kda_output_gate"],
            a["beta_max"], a["attention_gate"], a["router_score"],
            a["qk_norm"]) == (
        "softplus", 128, "low_rank_per_channel", 2, "elementwise",
        "softmax", "kda_l2_only")
    assert all(a[k + "_why"].startswith("ASSUMED") for k in (
        "kda_gate", "kda_rank", "kda_output_gate", "beta_max",
        "attention_gate", "router_score", "qk_norm"))
    assert "weights" in a and "16" in cfg["deployment"]
    assert cfg["serve"]["max_seq"] == 8192
    assert cfg["check"]["serve"]["prompt_lens"] == [700, 3800]
    mix = _json(harness.BENCH_DIR, "traffic", "think-closed-2x-any.json")
    assert mix["prompt_len"] == {"median": 1536, "sigma": 0.8, "min": 512,
                                 "max": 4096}
    assert mix["max_new"] == {"median": 512, "sigma": 0.6, "min": 128,
                              "max": 2048}
    assert (mix["clients_per_slot"], mix["requests"]) == (2, 136)
    assert mix["warm_admit_sizes"] == [1, 2, 4, 8]
    assert (mix["ramp_group"], mix["settle_seconds"], mix["trace_seconds"],
            mix["tail"]) == (8, 3.0, 3.0, 0.9)
    assert mix["prompt_len"]["max"] + mix["max_new"]["max"] <= 6144


def test_builder_reads_the_published_keys(cfg):
    import numpy as np

    from benchmark.models import solar_open2_lm

    dc = solar_open2_lm.decode_config(cfg, "serve_closed")
    assert dc.layer_kinds() == ["attention"] + ["kda"] * 3
    assert dc.ffn_kinds() == ["experts"] * 4
    assert (dc.n_head, dc.n_kv_head, dc.d_head, dc.d_model) == (
        64, 8, 128, 4096)
    assert (dc.kda_heads, dc.kda_head_dim, dc.kda_conv, dc.kda_gate,
            dc.kda_beta_max, dc.kda_decay_rank) == (
        64, 128, 4, "softplus", 2.0, 128)
    assert (dc.n_expert, dc.expert_top_k, dc.d_expert, dc.d_shared_expert,
            dc.held, dc.router_score, dc.router_scale) == (
        320, 8, 1280, 1280, (0, 20), "softmax", 1)
    assert dc.attn_gate == "per_channel" and dc.rope is None
    assert not dc.positions
    specs = solar_open2_lm.parameter_specs(cfg, "serve_closed")
    total = sum(int(np.prod(s)) for _, s, _ in specs)
    assert round(total / 1e9, 3) == 2.050                # parameters held
    # the bytes a slot keeps: three matrix states, nine windows, one
    # K/V slab of 8 heads
    from paddle_tpu.serving.decode import cache_spec

    per_slot = sum(e.nbytes for e in cache_spec(dc, 1, 8192))
    assert per_slot == (3 * 64 * 128 * 128 + 9 * 3 * 8192
                        + 8192 * 2 * 8 * 128) * 4
    assert round(per_slot / 1e6, 1) == 80.6
    # what the rule makes of a token's decay, as the file states it
    pct, share = solar_open2_lm.decay_percentiles(cfg, draws=200_000)
    assert 0.9 < pct[50] < 1.0 and pct[0.1] < 0.01
    assert 0.001 < share < 0.05


def test_cost_functions_of_the_published_widths(cfg):
    assert (solar_cost.n_kda(cfg), solar_cost.n_gqa(cfg)) == (3, 1)
    assert round(solar_cost.kda_params(cfg) / 1e6, 2) == 137.73
    assert round(solar_cost.gqa_params(cfg) / 1e6, 2) == 109.05
    assert solar_cost.expert_params(cfg) == 3 * 4096 * 1280   # 62.9 MB
    assert round(solar_cost.layer_rest_params(cfg) / 1e6, 2) == 17.04
    assert round(4 * solar_cost.dense_params(cfg) / 1e9, 2) == 2.76
    assert solar_cost.kv_row_bytes(cfg) == 8192
    assert solar_cost.state_bytes_per_slot(cfg) == 3 * 64 * 128 * 128 * 4
    state = 32 * 12582912
    assert solar_cost.step_bytes(cfg, 50, 100000, state) == (
        4 * solar_cost.dense_params(cfg) + 50 * 62914560 + 2 * state
        + 100000 * 8192)
    assert solar_cost.kda_step_bytes(cfg, state, 32) == (
        2 * state + 5 * 8192 * 4 * 3 * 32)
    assert solar_cost.kda_scan_flops_per_token(cfg) == 184320
    flops, nbytes = solar_cost.kda_scan_cost(cfg, 1000, 2)
    assert flops == 3 * 64 * 184320 * 1000
    assert nbytes == 3 * 5 * 8192 * 4 * 1000 + 2 * 12582912
    assert solar_cost.prefill_flops(cfg, 1, 1, 1, 1) == (
        2.0 * solar_cost.row_params(cfg) + 2.0 * 3 * 4096 * 1280
        + 3 * 64 * 184320 + 2.0 * 64 * 256 + 2.0 * 4096 * 24576)


def _run_of(cfg, ops, modules, host):
    return {"cfg": cfg, "peaks": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"path": "synthetic"}, "cell": {"name": CELL},
            "_spans": {"ops": {"/device:TPU:0": ops},
                       "modules": {"/device:TPU:0": modules}, "host": host}}


@pytest.fixture
def synthetic(monkeypatch):
    monkeypatch.setattr(program_spans, "of_run",
                        lambda run: run.get("_spans"))


def test_readers_on_a_synthetic_trace(cfg, synthetic):
    """One decode step of 10 ms and one prefill of 200 ms: the readers
    find the kernels by their calls' own names, and each share counts
    what must be done."""
    ms = 1e6
    ops = [("ptpu.kda_step.1", 0.0, 1.0 * ms, "%ptpu.kda_step.1 = ..."),
           ("ptpu.kda_step.2", 1.0 * ms, 1.0 * ms, "%ptpu.kda_step.2 = "),
           ("ptpu.decode_attn_grouped.3", 2.0 * ms, 2.0 * ms, ""),
           ("fusion.4", 4.0 * ms, 6.0 * ms,
            "%fusion.4 = f32[32,24576] fusion(%ptpu.kda_step.1)"),
           ("ptpu.kda_scan.5", 20 * ms, 30 * ms, ""),
           ("ptpu.flash_fwd.6", 50 * ms, 20 * ms, ""),
           ("fusion.9", 70 * ms, 150 * ms, "")]
    modules = [("jit_ptpu_decode_b32_s8192(1)", 0.0, 10 * ms),
               ("jit_ptpu_prefill_b4_s1024(2)", 20 * ms, 200 * ms)]
    state = 30 * 12582912
    host = [(program_spans.DISPATCH, -0.1 * ms, 0.05 * ms,
             {"active": 30, "attended": 90000, "streamed": 92160,
              "expert_pairs": 240, "experts_active": 50,
              "kda_state_bytes": state}, "loop"),
            (program_spans.LOOP + "scatter", 221 * ms, 1 * ms,
             {"entries": 14, "prompt_rows": 3000, "bucket_rows": 4096,
              "prompts": 4, "attn_pairs": 4 * 750 * 751 // 2,
              "expert_pairs": 1500, "kda_tokens": 3000,
              "kda_pad_tokens": 1096}, "loop")]
    run = _run_of(cfg, ops, modules, host)
    read = lambda name: harness.load_layer_metric(name).read(run)  # noqa
    assert read("kda_wide_step_roofline.serve") == pytest.approx(
        100 * solar_cost.kda_step_bytes(cfg, state, 30) / 819e9 / 2e-3)
    assert read("decode_attn_nope_roofline.serve") == pytest.approx(
        100 * 90000 * 8192 / 819e9 / 2e-3)
    flops, nbytes = solar_cost.kda_scan_cost(cfg, 3000, 4)
    assert read("kda_guarded_scan_roofline.serve") == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 30e-3)
    assert read("decode_step_roofline_kdagqa.serve") == pytest.approx(
        100 * solar_cost.step_bytes(cfg, 50, 90000, state) / 819e9 / 10e-3)
    flops = solar_cost.prefill_flops(cfg, 3000, 1500, 4 * 750 * 751 // 2, 4)
    assert read("prefill_mfu_pct_kdagqa.serve") == pytest.approx(
        100 * flops / (197e12 * 0.2))
    for name in READERS:
        assert 0 < read(name) < 100, name
    # a configuration of another family, or a program without the
    # counts (the parent), reads nothing and does not raise
    other = dict(run, cfg={"kda_lower_bound": -5, "kv_lora_rank": 256,
                           "serve": {}})
    for name in READERS + SHARES:
        assert harness.load_layer_metric(name).read(other) is None
    bare = _run_of(cfg, ops, modules, [
        (program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, {"active": 32},
         "loop"),
        (program_spans.LOOP + "scatter", 221 * ms, 1 * ms,
         {"entries": 4, "kda_tokens": 3000}, "loop")])
    for name in READERS:
        assert harness.load_layer_metric(name).read(bare) is None
    # and a program whose kernels took the lax forms: no call of the name
    lax = _run_of(cfg, [ops[3], ops[6]], modules, host)
    for name in READERS[2:]:
        assert harness.load_layer_metric(name).read(lax) is None


def test_time_shares_tell_the_mixers_by_scope_and_weight(cfg, monkeypatch):
    """The three shares over a synthetic join: an event is a mixer's by
    its scope, a fused member's, the scope its result goes to or a
    weight it reads; an elementwise event anchored at a temporary is
    nobody's."""
    def entry(scope=(), members=(), users=(), reads=()):
        return {"scope": list(scope), "members": list(members),
                "users": list(users), "reads": list(reads), "pass": "fwd"}

    ops_map = {
        "fusion.1": entry(["fl.mul:lm.l1.kda.q.w"]),
        "ptpu.kda_scan.2": entry(["fl.kda_scan:kda_scan_0.tmp_0",
                                  "ptpu.kda_scan"]),
        "fusion.3": entry(members=["fl.mul:lm.l0.attention.gate.w"]),
        "ptpu.flash_fwd.4": entry(["fl.prefill_attention:x",
                                   "ptpu.flash_fwd"]),
        "slice-done.5": entry(users=["fl.mul:lm.l2.moe.shared.up.w"],
                              reads=["state['lm.l2.moe.shared.up.w']"]),
        "fusion.6": entry(["fl.moe_experts:lm.l2.moe.experts.gate.w",
                           "ptpu.moe_experts"]),
        "fusion.7": entry(["fl.elementwise_add:tmp_9"]),
    }
    m = {"ops": ops_map, "scoped": True, "params": {}}
    evs = [(n, 0.0, 1e9) for n in ops_map]
    joined = [("jit_ptpu_prefill_b1_s512", 1, 7e9, evs, m, 1.0)]
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
            "request_ms_p90.serve"} <= mine
    # these read Ling's or Laguna's keys (`kda_lower_bound`,
    # `layer_types`, `sliding_window`): they would need an edit, so they
    # are not listed
    assert not {"kda_time_pct.serve", "kda_scan_roofline.serve",
                "kda_step_roofline.serve", "decode_step_roofline_kda.serve",
                "prefill_mfu_pct_kda.serve", "moe_time_pct.serve",
                "moe_experts_roofline.serve",
                "decode_attn_grouped_roofline.serve"} & mine
    for m in bench["per_layer"]:
        if m["name"] in READERS + SHARES:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in harness.metrics_of(
        bench, "end_to_end", CELL)} == {"serve_tokens_per_s", "setup_s"}
    assert len(bench["workloads"]) == 12 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
