"""The Ling-3.0-flash cell's files end to end at a tiny size on the CPU
(`lib/run_serveany.py` as it is, the tiny configuration in the cell's
place), the configuration's file against the catalog's rule, and the
cost functions and readers the cell brought, on synthetic traces."""
import json
import os
import sys
import time

import pytest

from benchmark.lib import (harness, ling_cost, peaks, program_spans, stats,
                           trace_reduce)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "ling-3.0-flash.serve-closed"
READERS = ("kda_time_pct.serve", "kda_scan_roofline.serve",
           "kda_step_roofline.serve", "decode_step_roofline_kda.serve",
           "prefill_mfu_pct_kda.serve")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture
def cfg():
    return _json(harness.BENCH_DIR, "configs", "ling-3.0-flash.json")


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
                _json(HERE, "tiny", "ling3-tiny.json"),
                _json(HERE, "tiny", "chat-tiny-any.json"))

    monkeypatch.setattr(harness, "load_cell", load_cell)


@pytest.mark.parametrize("trace", [0, 1])
def test_ling3_cell_runs_tiny(lifted, capsys, trace):
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
    of the delta rule, the latent layer or the router left out, or whose
    state is stale at the hand-over or zeroed at a chunk boundary."""
    from benchmark.reference import ling3
    from benchmark.tools import variants_serveany as tool

    recs = []
    monkeypatch.setattr(tool, "_emit", recs.append)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    left_out = [v for v in ling3.VARIANTS if v]
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
               if '"name": "Ling-3.0-flash"' in ln][0]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "num_experts",
                       "vocab_size"} == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]
    bench = _json(harness.ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["ling-3.0-flash"]
    assert set(entry["reduced"]) == differs
    assert cfg["experts_held"] == [0, cfg["num_experts"]] == [0, 64]
    assert cfg["num_experts_scored"] == row["config"]["num_experts"] == 512
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert cfg["num_hidden_layers"] == 6
    # the published widths
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["qk_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["short_conv_kernel_size"], cfg["num_experts_per_tok"],
            cfg["n_group"], cfg["topk_group"]) == (
                2560, 32, 128, 192, 128, 512, 768, 6144, 4, 8, 8, 4)
    a = cfg["assumed"]
    assert (a["kda_gate"], a["kda_decay_rank"], a["output_gate"],
            a["qk_norm_scope"], a["group_score"]) == (
        "lower_bound_sigmoid", "full", "per_head", "kda_l2", "top2_sum")
    assert all(a[k + "_why"].startswith("ASSUMED") for k in (
        "kda_gate", "kda_decay_rank", "output_gate", "qk_norm_scope",
        "group_score"))
    assert "weights" in a
    assert cfg["serve"]["max_seq"] == 16384
    assert cfg["check"]["serve"]["prompt_lens"] == [1500, 12000]
    mix = _json(harness.BENCH_DIR, "traffic", "agent-closed-2x-any.json")
    assert mix["prompt_len"] == {"median": 2048, "sigma": 0.9, "min": 512,
                                 "max": 12288}
    assert mix["max_new"]["sigma"] == 0.6 and (
        mix["max_new"]["min"], mix["max_new"]["max"]) == (128, 2048)
    assert (mix["clients_per_slot"], mix["requests"]) == (2, 136)
    assert mix["warm_admit_sizes"] == [1, 2, 4, 8]


def test_builder_reads_the_published_keys(cfg):
    import numpy as np

    from benchmark.models import ling3_lm

    dc = ling3_lm.decode_config(cfg, "serve_closed")
    assert dc.layer_kinds() == ["kda"] * 5 + ["latent"]
    assert dc.ffn_kinds() == ["dense"] * 2 + ["experts"] * 4
    assert dc.latent_row == 576
    assert (dc.q_lora_rank, dc.kv_lora_rank, dc.qk_nope_dim, dc.qk_rope_dim,
            dc.v_head_dim, dc.n_head, dc.d_model, dc.d_inner) == (
                0, 512, 128, 64, 128, 32, 2560, 6144)
    assert (dc.kda_heads, dc.kda_head_dim, dc.kda_conv, dc.kda_gate,
            dc.kda_gate_bound) == (32, 128, 4, "lower_bound_sigmoid", -5.0)
    assert (dc.n_expert, dc.expert_top_k, dc.d_expert, dc.d_shared_expert,
            dc.held, dc.router_groups, dc.router_topk_groups,
            dc.router_bias) == (512, 8, 768, 768, (0, 64), 8, 4, True)
    assert dc.router_score == "sigmoid" and dc.router_scale == 2.5
    assert dc.softmax_scale is None and dc.attn_gate == "per_head"
    assert dc.rope == {"latent": {"theta": 6e6, "interleave": True}}
    specs = ling3_lm.parameter_specs(cfg, "serve_closed")
    total = sum(int(np.prod(s)) for _, s, _ in specs)
    assert round(total / 1e9, 3) == 2.029                # parameters held
    # the bytes a slot keeps: five matrix states, fifteen windows, one
    # latent slab
    from paddle_tpu.serving.decode import cache_spec

    per_slot = sum(e.nbytes for e in cache_spec(dc, 1, 16384))
    assert per_slot == (5 * 32 * 128 * 128 + 15 * 3 * 4096
                        + 16384 * 576) * 4
    assert round(64 * per_slot / 1e9, 2) == 3.13


def test_cost_functions_of_the_published_widths(cfg):
    assert (ling_cost.n_kda(cfg), ling_cost.n_latent(cfg),
            ling_cost.n_sparse(cfg)) == (5, 1, 4)
    assert round(ling_cost.kda_params(cfg) / 1e6, 1) == 52.6
    assert round(ling_cost.latent_params(cfg) / 1e6, 1) == 32.0
    assert ling_cost.expert_params(cfg) == 3 * 2560 * 768   # 23.6 MB
    assert round(ling_cost.sparse_rest_params(cfg) / 1e6, 2) == 7.21
    assert round(4 * ling_cost.dense_params(cfg) / 1e9, 2) == 1.87
    assert ling_cost.latent_row_bytes(cfg) == 2304
    assert ling_cost.state_bytes_per_slot(cfg) == 5 * 32 * 128 * 128 * 4
    assert ling_cost.step_bytes(cfg, 160, 200000, 64 * 10485760) == (
        4 * ling_cost.dense_params(cfg) + 160 * 23592960
        + 2 * 64 * 10485760 + 200000 * 2304)
    assert ling_cost.kda_step_bytes(cfg, 64 * 10485760, 64) == (
        2 * 64 * 10485760 + 5 * 4096 * 4 * 5 * 64)
    assert ling_cost.kda_scan_flops_per_token(cfg) == 184320
    flops, nbytes = ling_cost.kda_scan_cost(cfg, 1000, 2)
    assert flops == 5 * 32 * 184320 * 1000
    assert nbytes == 5 * 5 * 4096 * 4 * 1000 + 2 * 10485760
    assert ling_cost.prefill_flops(cfg, 1, 1, 1, 1) == (
        2.0 * ling_cost.row_params(cfg) + 2.0 * 3 * 2560 * 768
        + 5 * 32 * 184320 + 2.0 * 32 * 320 + 2.0 * 2560 * 19648)


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
    tell what touches a matrix state and a chunk's tensors from the rest
    by the shapes an event's text holds, and each share counts what must
    be done."""
    ms = 1e6
    update = ("%fusion.3 = f32[64,32,128,128] fusion(f32[64,32,128,128] "
              "%feeds__kda_0__, f32[64,32,128] %x)")
    head = "%fusion.4 = f32[64,19648] fusion(f32[2560,19648] %state__lm_head_w__)"
    loop = ("%while.7 = (s32[], f32[1,32,128,128], f32[16,1,32,64,128]) "
            "while()")
    gram = "%fusion.8 = f32[1,16,32,64,64] fusion(f32[1,16,4,16,32,128] %y)"
    experts = "%while.9 = (s32[], f32[4096,2560]) while()"
    ops = [("fusion.3", 0.0, 3.0 * ms, update),
           ("fusion.4", 3.0 * ms, 7.0 * ms, head),
           ("fusion.8", 20 * ms, 10 * ms, gram),
           ("while.7", 30 * ms, 30 * ms, loop),
           ("while.9", 60 * ms, 60 * ms, experts),
           ("fusion.9", 120 * ms, 100 * ms, head)]
    modules = [("jit_ptpu_decode_b64_s16384(1)", 0.0, 10 * ms),
               ("jit_ptpu_prefill_b1_s4096(2)", 20 * ms, 200 * ms)]
    state = 40 * 10485760
    host = [(program_spans.DISPATCH, -0.1 * ms, 0.05 * ms,
             {"active": 40, "attended": 140000, "latent_rows": 140000,
              "latent_row_bytes": 2304, "expert_pairs": 150,
              "experts_active": 120, "kda_state_bytes": state}, "loop"),
            (program_spans.LOOP + "scatter", 221 * ms, 1 * ms,
             {"entries": 21, "prompt_rows": 3000, "bucket_rows": 4096,
              "prompts": 1, "attn_pairs": 3000 * 3001 // 2,
              "expert_pairs": 6000, "kda_tokens": 3000,
              "kda_pad_tokens": 1096}, "loop")]
    run = _run_of(cfg, ops, modules, host)
    read = lambda name: harness.load_layer_metric(name).read(run)  # noqa
    busy = 10 + 200
    assert read("kda_time_pct.serve") == pytest.approx(
        100 * (3 + 10 + 30) / busy)
    assert read("kda_step_roofline.serve") == pytest.approx(
        100 * ling_cost.kda_step_bytes(cfg, state, 40) / 819e9 / 3e-3)
    flops, nbytes = ling_cost.kda_scan_cost(cfg, 3000, 1)
    assert read("kda_scan_roofline.serve") == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 40e-3)
    assert read("decode_step_roofline_kda.serve") == pytest.approx(
        100 * ling_cost.step_bytes(cfg, 120, 140000, state) / 819e9 / 10e-3)
    flops = ling_cost.prefill_flops(cfg, 3000, 6000, 3000 * 3001 // 2, 1)
    assert read("prefill_mfu_pct_kda.serve") == pytest.approx(
        100 * flops / (197e12 * 0.2))
    for name in READERS[1:]:
        assert 0 < read(name) < 100, name
    # a configuration of another family, or a program without the
    # counts (the parent), reads nothing and does not raise
    other = dict(run, cfg={"mamba_d_state": 16, "kv_lora_rank": 256})
    for name in READERS:
        assert harness.load_layer_metric(name).read(other) is None
    bare = _run_of(cfg, ops, modules, [
        (program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, {"active": 32},
         "loop"),
        (program_spans.LOOP + "scatter", 221 * ms, 1 * ms, {"entries": 4},
         "loop")])
    for name in READERS[1:]:
        assert harness.load_layer_metric(name).read(bare) is None
    assert harness.load_layer_metric(READERS[0]).read(
        _run_of(cfg, [], [], [])) is None


def test_benchmark_json_lists_the_cell_where_a_reader_reads_it():
    bench = _json(harness.ROOT, "BENCHMARK.json")
    mine = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    assert set(READERS) <= mine
    assert {"moe_load_max_over_mean.serve", "state_scatter_ms.serve",
            "prefill_busy_pct.serve"} <= mine
    # these count every layer latent (`lib/mla_cost.py` multiplies by the
    # depth) or format keys this configuration lacks: they would need an
    # edit, so they are not listed
    assert not {"mla_decode_roofline.serve", "decode_step_roofline_mla.serve",
                "mla_time_pct.serve", "prefill_mfu_pct_mla.serve",
                "moe_time_pct.serve", "moe_experts_roofline.serve",
                "ssm_scan_time_pct.serve"} & mine
    assert {m["name"] for m in harness.metrics_of(
        bench, "end_to_end", CELL)} == {"serve_tokens_per_s", "setup_s"}
    assert len(bench["workloads"]) >= 9 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
