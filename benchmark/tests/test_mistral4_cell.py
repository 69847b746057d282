"""The Mistral-Small-4 cell's files end to end at a tiny size on the CPU
(`lib/run_serveany.py` as it is, the tiny configuration in the cell's
place), the configuration's file against the catalog's rule, and the
cost functions and readers the cell brought, on synthetic traces."""
import json
import os
import sys
import time

import pytest

from benchmark.lib import (harness, mla_cost, peaks, program_spans, stats,
                           trace_reduce)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "mistral-small-4.serve-closed"
READERS = ("mla_time_pct.serve", "mla_decode_roofline.serve",
           "decode_step_roofline_mla.serve", "prefill_mfu_pct_mla.serve")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture
def cfg():
    return _json(harness.BENCH_DIR, "configs", "mistral-small-4.json")


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
                _json(HERE, "tiny", "mistral4-tiny.json"),
                _json(HERE, "tiny", "chat-tiny-any.json"))

    monkeypatch.setattr(harness, "load_cell", load_cell)


@pytest.mark.parametrize("trace", [0, 1])
def test_mistral4_cell_runs_tiny(lifted, capsys, trace):
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
    against the reference and fails against a reference without the
    shared expert, the renormalisation, the routed experts, the
    rotation of k_r, the query scale (the tiny original context is 16
    positions: the 40-token prompt passes it), the norm on c_kv or one
    cache row."""
    from benchmark.tools import variants_serveany as tool

    recs = []
    monkeypatch.setattr(tool, "_emit", recs.append)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    left_out = ["no_shared", "no_renorm", "no_routed", "kr_not_rotated",
                "no_query_scale", "no_kv_norm", "cache_row_short"]
    monkeypatch.setattr(sys, "argv", [
        "variants_serveany.py", "--workload", CELL, "--seed",
        str(2 ** 31 + 7), "--variants", ",".join(left_out),
        "--prompt-lens", "40"])
    tool.main()
    assert len(recs) == 1 + len(left_out)
    for rec in recs:
        whole = "+" not in rec["reference"]
        assert rec["ok"] is whole, rec
        assert (rec["program_vs_reference"] <= rec["limit"]) is whole


def test_configuration_keeps_the_catalogs_numbers(cfg):
    """Every key of the catalog's `config` under the same key; what
    differs is named in `reduced`; the share, the three assumed fields
    and the cell's sizes are written down."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = [json.loads(ln) for ln in f
               if '"Mistral-Small-4-119B-2603"' in ln][0]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"} == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]
    bench = _json(harness.ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["mistral-small-4"]
    assert set(entry["reduced"]) == differs
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 16]
    assert cfg["n_routed_experts_scored"] == row["config"][
        "n_routed_experts"] == 128
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert cfg["num_hidden_layers"] == 4
    a = cfg["assumed"]
    assert (a["router_score"], a["softmax_scale"], a["query_scale"]) == (
        "softmax", "yarn_mscale_all_dim", "llama4")
    assert all(a[k + "_why"].startswith("ASSUMED") for k in (
        "router_score", "softmax_scale", "query_scale"))
    assert (cfg["serve"]["slots"], cfg["serve"]["max_seq"]) == (32, 16384)
    assert cfg["check"]["serve"]["prompt_lens"] == [1500, 12000]
    mix = _json(harness.BENCH_DIR, "traffic", "doc-closed-2x-any.json")
    assert mix["prompt_len"] == {"median": 3072, "sigma": 0.8, "min": 512,
                                 "max": 15360}
    assert mix["max_new"] == {"median": 128, "sigma": 0.6, "min": 32,
                              "max": 512}
    assert (mix["clients_per_slot"], mix["requests"]) == (2, 136)


def test_builder_reads_the_published_keys(cfg):
    from benchmark.models import mistral4_lm

    dc = mistral4_lm.decode_config(cfg, "serve_closed")
    assert dc.layer_kinds() == ["latent"] * 4 and dc.latent_row == 320
    assert (dc.q_lora_rank, dc.kv_lora_rank, dc.qk_nope_dim, dc.qk_rope_dim,
            dc.v_head_dim, dc.n_head, dc.d_model) == (
                1024, 256, 64, 64, 128, 32, 4096)
    assert (dc.n_expert, dc.expert_top_k, dc.d_expert, dc.d_shared_expert,
            dc.held) == (128, 4, 2048, 2048, (0, 16))
    assert dc.router_score == "softmax" and dc.router_scale == 1
    assert round(dc.softmax_scale, 4) == 0.1950
    rot = dc.rope["latent"]
    assert rot["interleave"] and rot["scale_beta"] == 0.1
    assert rot["attention_factor"] == 1.0 and rot["theta"] == 10000.0
    assert rot["yarn"] == {"factor": 128.0, "original_max_position": 8192,
                           "beta_fast": 32.0, "beta_slow": 1.0}
    specs = mistral4_lm.parameter_specs(cfg, "serve_closed")
    total = sum(int(__import__("numpy").prod(s)) for _, s, _ in specs)
    assert round(total / 1e9, 3) == 1.960                # parameters held


def test_cost_functions_of_the_published_widths(cfg):
    assert mla_cost.latent_row(cfg) == 320
    assert mla_cost.row_bytes(cfg) == 5120               # 4 layers x 1,280
    assert mla_cost.absorbed_flops_per_row(cfg) == 4 * 32 * 576 * 2
    # attention 28.05 M a layer with its two inner gains
    assert round(mla_cost.attention_params(cfg) / 1e6, 2) == 28.05
    assert mla_cost.expert_params(cfg) == 3 * 4096 * 2048  # 100.7 MB
    assert round(4 * mla_cost.dense_params(cfg) / 1e9, 2) == 1.13
    # a slot: 16,384 rows of 5,120 B = 83.9 MB; 32 slots 2.68 GB
    assert round(32 * 16384 * mla_cost.row_bytes(cfg) / 1e9, 2) == 2.68
    assert mla_cost.step_bytes(cfg, 40, 100000) == (
        4 * mla_cost.dense_params(cfg) + 40 * 100663296 + 100000 * 5120)
    # one live row of a prompt of one: projections, a held pair, itself
    # as its only key, the head
    per_row = mla_cost.attention_params(cfg) + 4096 * 128 + 3 * 4096 * 2048
    assert mla_cost.prefill_flops(cfg, 1, 1, 1, 1) == (
        2.0 * 4 * per_row + 2.0 * 3 * 4096 * 2048
        + 2.0 * 4 * 32 * 256 + 2.0 * 4096 * 16384)


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
    tell what touches a latent slab, the flash calls and W_kvb's product
    from the rest by what an event reads, and each share counts what
    must be done."""
    ms = 1e6
    slab = ("%fusion.3 = f32[32,32,16384] fusion(f32[32,16384,320]{1,2,0} "
            "%feeds__latent_0__)")
    append = ("%dynamic-update-slice.9 = f32[32,16384,320]{1,2,0} "
              "dynamic-update-slice(f32[32,16384,320] %fusion.1)")
    head = "%fusion.4 = f32[32,16384] fusion(f32[4096,16384] %state__lm_head_w__)"
    expand = ("%fusion.7 = f32[1,4096,6144] fusion(f32[256,6144] "
              "%state__lm_l0_attention_kv_b_w__)")
    rows = "%fusion.8 = f32[1,4096,320]{1,2,0} fusion(f32[1,4096,4096] %x)"
    ops = [("fusion.3", 0.0, 3.0 * ms, slab),
           ("dynamic-update-slice.9", 3.0 * ms, 0.1 * ms, append),
           ("fusion.4", 3.1 * ms, 6.9 * ms, head),
           ("ptpu.flash_fwd.3", 20 * ms, 40 * ms,
            "%ptpu.flash_fwd.3 = custom-call()"),
           ("fusion.7", 60 * ms, 10 * ms, expand),
           ("fusion.8", 70 * ms, 2 * ms, rows),
           ("while.7", 72 * ms, 48 * ms, "%while.7 = while()"),
           ("fusion.9", 120 * ms, 100 * ms, head)]
    modules = [("jit_ptpu_decode_b32_s16384(1)", 0.0, 10 * ms),
               ("jit_ptpu_prefill_b1_s4096(2)", 20 * ms, 200 * ms)]
    host = [(program_spans.DISPATCH, -0.1 * ms, 0.05 * ms,
             {"active": 32, "attended": 140000, "latent_rows": 140000,
              "latent_row_bytes": 1280, "expert_pairs": 16,
              "experts_active": 40}, "loop"),
            (program_spans.LOOP + "scatter", 221 * ms, 1 * ms,
             {"entries": 4, "prompt_rows": 3000, "bucket_rows": 4096,
              "prompts": 1, "attn_pairs": 3000 * 3001 // 2,
              "expert_pairs": 6000}, "loop")]
    run = _run_of(cfg, ops, modules, host)
    read = lambda name: harness.load_layer_metric(name).read(run)  # noqa
    busy = 10 + 200
    assert read("mla_time_pct.serve") == pytest.approx(
        100 * (3.1 + 40 + 10 + 2) / busy)
    least = max(140000 * 5120 / 819e9, 140000 * 147456 / 197e12)
    assert read("mla_decode_roofline.serve") == pytest.approx(
        100 * least / 3.1e-3)
    assert read("decode_step_roofline_mla.serve") == pytest.approx(
        100 * mla_cost.step_bytes(cfg, 40, 140000) / 819e9 / 10e-3)
    flops = mla_cost.prefill_flops(cfg, 3000, 6000, 3000 * 3001 // 2, 1)
    assert read("prefill_mfu_pct_mla.serve") == pytest.approx(
        100 * flops / (197e12 * 0.2))
    assert 0 < read("mla_decode_roofline.serve") < 100
    assert 0 < read("prefill_mfu_pct_mla.serve") < 100
    # a configuration of another family, or a program without the
    # counts (the parent), reads nothing and does not raise
    other = dict(run, cfg={"mamba_d_state": 16})
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
    assert "moe_load_max_over_mean.serve" in mine
    # these two format `sliding_window` into a pattern, and the source
    # publishes it null: they would need an edit, so they are not listed
    assert not {"moe_time_pct.serve", "moe_experts_roofline.serve"} & mine
    assert {m["name"] for m in harness.metrics_of(
        bench, "end_to_end", CELL)} == {"serve_tokens_per_s", "setup_s"}
    assert len(bench["workloads"]) == 8 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
