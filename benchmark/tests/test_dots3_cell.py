"""The dots3-note-prev cell's files end to end at a tiny size on the CPU
(`lib/run_serveany.py` as it is, the tiny configuration in the cell's
place), the configuration's file against the catalog's rule, and the
cost functions and readers the cell brought, on synthetic traces."""
import json
import os
import sys
import time

import pytest

from benchmark.lib import (dsa_cost, harness, peaks, program_spans, stats,
                           trace_reduce)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "dots3-note-prev.serve-closed"
READERS = ("dsa_time_pct.serve", "latent_ring_time_pct.serve",
           "dsa_decode_roofline.serve", "decode_step_roofline_dsa.serve",
           "prefill_mfu_pct_dsa.serve", "dsa_selected_pct.serve",
           "request_ms_p80.serve")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture
def cfg():
    return _json(harness.BENCH_DIR, "configs", "dots3-note-prev.json")


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
                _json(HERE, "tiny", "dots3-tiny.json"),
                _json(HERE, "tiny", "notes-tiny-any.json"))

    monkeypatch.setattr(harness, "load_cell", load_cell)


def test_the_cells_files_load_by_name():
    bench, cell, cfg, mix = harness.load_cell(harness.ROOT, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dots3-note-prev", "notes-closed-2x-any", 1)
    assert (cfg["builder"], cfg["reference"]) == ("dots3_lm", "dots3")
    assert mix["kind"] == "serveany_closed"
    for name in READERS:
        mod = harness.load_layer_metric(name)
        assert mod.MOVES == "serve_tokens_per_s" and callable(mod.read)


@pytest.mark.parametrize("trace", [0, 1])
def test_dots3_cell_runs_tiny(lifted, capsys, trace):
    rc = harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 5),
                       "--seconds", "1.5", "--trace", str(trace)],
                      time.time())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    if trace:
        # the counters' readers answer on the CPU too; the device-trace
        # readers find no kernel or shape of the cell's size here
        assert {"slot_occupancy_pct.serve", "decode_tokens_per_s.serve",
                "request_ms_p80.serve", "moe_load_max_over_mean.serve",
                "dsa_selected_pct.serve"} <= set(res["metrics"])
        assert "request_ms_p90.serve" not in res["metrics"]
        assert 0 < res["metrics"]["dsa_selected_pct.serve"]["value"] < 100
    else:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    checks = [ln for ln in out if ln.startswith("check ")]
    assert len(checks) == 4 and all(ln.endswith("ok") for ln in checks)


def test_comparison_sees_each_part_changed(lifted, monkeypatch, tmp_path):
    """`tools/variants_serveany.py` at the tiny size: the program passes
    against the reference and fails against a reference with no
    selection, half of it, stale or unrotated index keys, a short
    window, or no rescale, gate, rotation of k_r, shared expert or
    bias."""
    from benchmark.reference import dots3
    from benchmark.tools import variants_serveany as tool

    recs = []
    monkeypatch.setattr(tool, "_emit", recs.append)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    changed = [v for v in dots3.VARIANTS if v]
    monkeypatch.setattr(sys, "argv", [
        "variants_serveany.py", "--workload", CELL, "--seed",
        str(2 ** 31 + 7), "--variants", ",".join(changed),
        "--prompt-lens", "100"])
    tool.main()
    assert len(recs) == 1 + len(changed)
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
               if '"name": "dots3-note-prev"' in ln][0]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"} == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]
    bench = _json(harness.ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["dots3-note-prev"]
    assert set(entry["reduced"]) == differs
    assert entry["source"] == row["source_url"]
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 8]
    assert (cfg["n_routed_experts_scored"]
            == row["config"]["n_routed_experts"] == 256)
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"],
            cfg["vocab_size_published"]) == (5, 46, 152064)
    assert cfg["layer_types"][:5] == ["full_attention"] * 2 + [
        "sliding_attention"] * 3
    # the published widths
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["swa_num_attention_heads"], cfg["swa_q_lora_rank"],
            cfg["swa_kv_lora_rank"], cfg["swa_qk_nope_head_dim"],
            cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"],
            cfg["sliding_window_size"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"], cfg["num_experts_per_tok"]) == (
                5120, 128, 1024, 512, 128, 64, 128, 64, 1024, 1024, 192, 64,
                128, 2048, 513, 1536, 13824, 8)
    a = cfg["assumed"]
    assert (a["mla_qkv_lora_rescale"], a["attention_gate"], a["index_rope"],
            a["sliding_window"]) == ("sqrt_hidden_over_rank", "per_head",
                                     "first_half_split", "includes_query")
    assert all(a[k + "_why"].startswith("ASSUMED") for k in (
        "mla_qkv_lora_rescale", "attention_gate", "index_rope",
        "sliding_window"))
    assert "weights" in a and len(cfg["departures"]) >= 5
    assert all(k in cfg for k in ("precision", "deployment", "bytes"))
    assert cfg["serve"]["slots"] == 32 and cfg["serve"]["max_seq"] == 16384
    chk = cfg["check"]["serve"]
    assert chk["prompt_lens"] == [3000, 12000] and chk["decode_steps"] == 8
    assert chk["reference_precision"] == "bf16_ops"
    mix = _json(harness.BENCH_DIR, "traffic", "notes-closed-2x-any.json")
    assert mix["prompt_len"] == {"median": 5120, "sigma": 0.5, "min": 2560,
                                 "max": 15360}
    assert mix["max_new"] == {"median": 192, "sigma": 0.4, "min": 64,
                              "max": 512}
    assert (mix["clients_per_slot"], mix["requests"], mix["ramp_group"]) == (
        2, 136, 4)
    assert mix["warm_admit_sizes"] == [1, 2, 4]
    assert (mix["tail"], mix["tail_metric"]) == (0.8, "request_ms_p80")
    assert (mix["settle_seconds"], mix["trace_seconds"]) == (3.0, 3.0)


def test_builder_reads_the_published_keys(cfg):
    import numpy as np

    from benchmark.models import dots3_lm

    dc = dots3_lm.decode_config(cfg, "serve_closed")
    assert dc.layer_kinds() == ["latent_dsa"] * 2 + ["latent_ring"] * 3
    assert dc.ffn_kinds() == ["dense"] + ["experts"] * 4
    assert dc.latent_row == 576 and dc.window == 513
    assert (dc.q_lora_rank, dc.kv_lora_rank, dc.qk_nope_dim, dc.qk_rope_dim,
            dc.v_head_dim, dc.n_head, dc.d_model, dc.d_inner) == (
                1024, 512, 128, 64, 128, 128, 5120, 13824)
    assert dc.latent_ring == {
        "n_head": 64, "q_lora_rank": 1024, "kv_lora_rank": 1024,
        "qk_nope_dim": 192, "qk_rope_dim": 64, "v_head_dim": 128}
    assert (dc.index_heads, dc.index_head_dim, dc.index_topk,
            dc.latent_rescale) == (64, 128, 2048, True)
    assert (dc.n_expert, dc.expert_top_k, dc.d_expert, dc.d_shared_expert,
            dc.held, dc.router_groups, dc.router_bias) == (
                256, 8, 1536, 1536, (0, 8), 1, True)
    assert dc.router_score == "sigmoid" and dc.router_scale == 1
    assert dc.rope == {
        "latent": {"theta": 8e7, "interleave": True},
        "latent_ring": {"theta": 5e4, "interleave": True},
        "index": {"theta": 8e7, "rotary_dim": 64}}
    specs = dots3_lm.parameter_specs(cfg, "serve_closed")
    total = sum(int(np.prod(s)) for _, s, _ in specs)
    assert total == dsa_cost.weight_params(cfg)
    assert round(4 * total / 1e9, 2) == 7.29             # ISSUE 44's bytes


def test_cost_functions_of_the_published_widths(cfg):
    """The figures of ISSUE 44: 7.29 GB of weights, 99.0 MB a slot, 3.88
    GB a step outside the routed experts."""
    assert (dsa_cost.n_full(cfg), dsa_cost.n_sliding(cfg),
            dsa_cost.n_sparse(cfg)) == (2, 3, 4)
    assert round(dsa_cost.mixer_params(cfg, "full") / 1e6, 2) == 144.05
    assert round(dsa_cost.mixer_params(cfg, "sliding") / 1e6, 2) == 90.83
    assert dsa_cost.expert_params(cfg) == 3 * 5120 * 1536    # 94.4 MB
    assert round(dsa_cost.sparse_rest_params(cfg) / 1e6, 2) == 24.90
    assert round(4 * dsa_cost.weight_params(cfg) / 1e9, 2) == 7.29
    assert round(dsa_cost.slot_bytes(cfg) / 1e6, 1) == 99.0
    assert round(32 * dsa_cost.slot_bytes(cfg) / 1e9, 2) == 3.17
    assert round(4 * dsa_cost.dense_params(cfg) / 1e9, 2) == 3.88
    assert (dsa_cost.latent_row_bytes(cfg), dsa_cost.index_key_bytes(cfg),
            dsa_cost.ring_row_bytes(cfg)) == (2304, 512, 4352)
    # a live slot of 6,000 positions: 2 x (6,000 x 512 + 2,048 x 2,304)
    assert dsa_cost.dsa_step_bytes(cfg, 6000, 2048) == 2 * (
        6000 * 512 + 2048 * 2304)
    assert dsa_cost.ring_step_bytes(cfg, 513) == 3 * 513 * 4352
    assert dsa_cost.step_bytes(cfg, 30, 6000, 2048, 513) == (
        4 * dsa_cost.dense_params(cfg) + 30 * 94371840
        + 2 * (6000 * 512 + 2048 * 2304) + 3 * 513 * 4352)
    assert dsa_cost.prefill_flops(cfg, 1, 1, 1, 1, 1, 1) == (
        2.0 * dsa_cost.row_params(cfg) + 2.0 * 3 * 5120 * 1536
        + 2.0 * 2 * 64 * 128 + 2.0 * 2 * 128 * 320 + 2.0 * 3 * 64 * 384
        + 2.0 * 5120 * 19008)


def _run_of(cfg, ops, modules, host):
    return {"cfg": cfg, "peaks": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"path": "synthetic"}, "cell": {"name": CELL},
            "mix": {"tail_metric": "request_ms_p80"},
            "end_to_end": {"request_ms_p80": 1234.5},
            "_spans": {"ops": {"/device:TPU:0": ops},
                       "modules": {"/device:TPU:0": modules}, "host": host}}


@pytest.fixture
def synthetic(monkeypatch):
    monkeypatch.setattr(program_spans, "of_run",
                        lambda run: run.get("_spans"))


def test_readers_on_a_synthetic_trace(cfg, synthetic):
    """One decode step of 12 ms and one prefill of 400 ms: the readers
    tell the indexer, the choice, the attention under it and what
    touches a ring from the rest by the kernels' names and the shapes an
    event's text holds, and each share counts what must be done."""
    ms = 1e6
    index = ("%fusion.3 = f32[32,16,16384] fusion(f32[32,16384,128] "
             "%feeds__index_0__, f32[32,1,16,128] %q)")
    choose = "%fusion.4 = u32[32,1] fusion(u32[32,16384] %key)"
    attend = ("%fusion.5 = f32[32,128,16384] fusion(f32[32,16384,576] "
              "%feeds__latent_0__, f32[32,128,576] %q)")
    ring = ("%fusion.6 = f32[32,64,513] fusion(f32[32,513,1088] "
            "%feeds__lring_2__)")
    head = "%fusion.7 = f32[32,19008] fusion(f32[5120,19008] %state__lm_head_w__)"
    pairs = "%fusion.8 = f32[1,256,16,8192] fusion(f32[1,256,16,128] %qi)"
    select = "%fusion.9 = u32[1,256,1] fusion(u32[1,256,8192] %key)"
    groups = ("%while.12 = (s32[], f32[1,8192,5120], s8[1,8192,8192]) "
              "while()")
    mask = "%fusion.10 = s8[1,16,8192,512] fusion(pred[1,8192,8192] %m)"
    ops = [("fusion.3", 0.0, 1.0 * ms, index),
           ("fusion.4", 1.0 * ms, 0.5 * ms, choose),
           ("fusion.5", 1.5 * ms, 1.5 * ms, attend),
           ("fusion.6", 3.0 * ms, 0.5 * ms, ring),
           ("fusion.7", 3.5 * ms, 8.5 * ms, head),
           ("fusion.8", 20 * ms, 40 * ms, pairs),
           ("fusion.9", 60 * ms, 30 * ms, select),
           ("while.12", 95 * ms, 320 * ms, groups),  # a loop is not told
           ("fusion.10", 90 * ms, 5 * ms, mask),
           ("ptpu.dsa_attend.2", 95 * ms, 65 * ms, "%ptpu.dsa_attend.2 = "
            "custom-call()"),
           ("ptpu.latent_ring_attend.1", 160 * ms, 20 * ms,
            "%ptpu.latent_ring_attend.1 = custom-call()"),
           ("fusion.11", 180 * ms, 240 * ms, head)]
    modules = [("jit_ptpu_decode_b32_s16384(1)", 0.0, 12 * ms),
               ("jit_ptpu_prefill_b1_s8192(2)", 20 * ms, 400 * ms)]
    step = {"active": 30, "attended": 180000, "rows_live": 180000,
            "rows_scored": 32 * 16384, "rows_chosen": 30 * 2048,
            "ring_rows": 30 * 513, "expert_pairs": 120, "experts_active": 28}
    host = [(program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, step, "loop"),
            (program_spans.LOOP + "scatter", 421 * ms, 1 * ms,
             {"entries": 7, "prompt_rows": 6000, "bucket_rows": 8192,
              "prompts": 1, "attn_pairs": 6000 * 6001 // 2,
              "index_pairs": 6000 * 6001 // 2,
              "chosen_pairs": 2048 * 2049 // 2 + 3952 * 2048,
              "window_pairs": 513 * 514 // 2 + 5487 * 513,
              "expert_pairs": 1500}, "loop")]
    run = _run_of(cfg, ops, modules, host)
    read = lambda name: harness.load_layer_metric(name).read(run)  # noqa
    busy = 12 + 400
    assert read("dsa_time_pct.serve") == pytest.approx(
        100 * (1 + 0.5 + 1.5 + 40 + 30 + 5 + 65) / busy)
    assert read("latent_ring_time_pct.serve") == pytest.approx(
        100 * (0.5 + 20) / busy)
    assert read("dsa_selected_pct.serve") == pytest.approx(
        100 * 30 * 2048 / 180000)
    assert read("dsa_decode_roofline.serve") == pytest.approx(
        100 * dsa_cost.dsa_step_bytes(cfg, 180000, 30 * 2048) / 819e9 / 3e-3)
    assert read("decode_step_roofline_dsa.serve") == pytest.approx(
        100 * dsa_cost.step_bytes(cfg, 28, 180000, 30 * 2048, 30 * 513)
        / 819e9 / 12e-3)
    c = host[1][3]
    flops = dsa_cost.prefill_flops(
        cfg, 6000, 1500, c["index_pairs"], c["chosen_pairs"],
        c["window_pairs"], 1)
    assert read("prefill_mfu_pct_dsa.serve") == pytest.approx(
        100 * flops / (197e12 * 0.4))
    assert read("request_ms_p80.serve") == 1234.5
    for name in READERS[:6]:
        assert 0 < read(name) < 100, name
    # a configuration of another family, or a program without the
    # counts (the parent), reads nothing and does not raise
    other = dict(run, cfg={"mamba_d_state": 16, "kv_lora_rank": 256},
                 mix={"tail_metric": "request_ms_p90"})
    other["_spans"] = dict(run["_spans"], host=[
        (program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, {"active": 32},
         "loop")])
    for name in READERS:
        assert harness.load_layer_metric(name).read(other) is None, name
    bare = _run_of(cfg, ops, modules, [
        (program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, {"active": 32},
         "loop"),
        (program_spans.LOOP + "scatter", 421 * ms, 1 * ms, {"entries": 4},
         "loop")])
    for name in ("dsa_decode_roofline.serve",
                 "decode_step_roofline_dsa.serve",
                 "prefill_mfu_pct_dsa.serve", "dsa_selected_pct.serve"):
        assert harness.load_layer_metric(name).read(bare) is None
    for name in READERS[:2]:
        assert harness.load_layer_metric(name).read(
            _run_of(cfg, [], [], [])) is None


def test_benchmark_json_lists_the_cell_where_a_reader_reads_it():
    bench = _json(harness.ROOT, "BENCHMARK.json")
    mine = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    assert set(READERS) <= mine
    assert {"moe_load_max_over_mean.serve", "state_scatter_ms.serve",
            "prefill_busy_pct.serve",
            "token_gap_ms_p95.serve", "setup_executables.serve"} <= mine
    # these would print an 80th percentile under a 90's name, find
    # under a hundred admissions in the window where a 90th percentile
    # needs a hundred (`admit_ms_p90.serve` reads nothing then, and a
    # listed metric has to be in the traced line), price one latent
    # geometry and every live row, or format keys this configuration
    # spells otherwise: they are not listed
    assert not {"request_ms_p90.serve", "admit_ms_p90.serve",
                "mla_decode_roofline.serve",
                "decode_step_roofline_mla.serve", "mla_time_pct.serve",
                "prefill_mfu_pct_mla.serve", "moe_time_pct.serve",
                "moe_experts_roofline.serve"} & mine
    assert {m["name"] for m in harness.metrics_of(
        bench, "end_to_end", CELL)} == {"serve_tokens_per_s", "setup_s"}
    assert len(bench["workloads"]) >= 10 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["configs"]) >= 9
