"""The EvaByte cell's files end to end at a tiny size on the CPU
(`lib/run_serveany.py` as it is, the tiny configuration in the cell's
place), the configuration's file against the catalog's row, and the
cost functions and readers the cell brought, on synthetic traces."""
import json
import os
import sys
import time

import pytest

from benchmark.lib import (eva_cost, harness, peaks, program_spans, stats,
                           trace_reduce)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "evabyte.serve-closed"
READERS = ("eva_time_pct.serve", "eva_decode_roofline.serve",
           "decode_step_roofline_eva.serve", "prefill_mfu_pct_eva.serve",
           "eva_summary_rows_pct.serve")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture
def cfg():
    return _json(harness.BENCH_DIR, "configs", "evabyte.json")


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
                _json(HERE, "tiny", "evabyte-tiny.json"),
                _json(HERE, "tiny", "bytes-tiny-any.json"))

    monkeypatch.setattr(harness, "load_cell", load_cell)


def test_the_cells_files_load_by_name():
    bench, cell, cfg, mix = harness.load_cell(harness.ROOT, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evabyte", "bytes-closed-2x-any", 1)
    assert (cfg["builder"], cfg["reference"]) == ("evabyte_lm", "evabyte")
    assert mix["kind"] == "serveany_closed"
    for name in READERS:
        mod = harness.load_layer_metric(name)
        assert mod.MOVES == "serve_tokens_per_s" and callable(mod.read)


@pytest.mark.parametrize("trace", [0, 1])
def test_evabyte_cell_runs_tiny(lifted, capsys, trace):
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
                "request_ms_p80.serve", "state_scatter_ms.serve",
                "eva_summary_rows_pct.serve"} <= set(res["metrics"])
        # every tiny prompt is past the window of 32
        assert 0 < res["metrics"]["eva_summary_rows_pct.serve"]["value"] < 100
    else:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    checks = [ln for ln in out if ln.startswith("check ")]
    assert len(checks) == 4 and all(ln.endswith("ok") for ln in checks)


def test_comparison_sees_each_part_changed(lifted, monkeypatch, tmp_path):
    """`tools/variants_serveany.py` at the tiny size: the program passes
    against the reference and fails against a reference with the
    summaries left out, `mu` left out, pooling by the mean, unrotated
    keys pooled, a block that does not restart, summaries a window
    early, or the unit offset left out."""
    from benchmark.reference import evabyte
    from benchmark.tools import variants_serveany as tool

    recs = []
    monkeypatch.setattr(tool, "_emit", recs.append)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "argv", [
        "variants_serveany.py", "--workload", CELL, "--seed",
        str(2 ** 31 + 7), "--variants", ",".join(evabyte.VARIANTS),
        "--prompt-lens", "130"])
    tool.main()
    assert len(recs) == 1 + len(evabyte.VARIANTS)
    for rec in recs:
        whole = "+" not in rec["reference"]
        assert rec["ok"] is whole, rec
        assert (rec["program_vs_reference"] <= rec["limit"]) is whole


def test_configuration_keeps_the_catalogs_numbers(cfg):
    """Every key of the catalog's `config` under the same key; what
    differs is `num_hidden_layers` alone, named in `reduced`; the cut,
    the deployment, the assumed fields and the cell's sizes are written
    down."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = [json.loads(ln) for ln in f if '"name": "EvaByte"' in ln][0]
    differs = {k for k, v in row["config"].items()
               if k not in cfg or cfg[k] != v}
    assert differs == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]
    bench = _json(harness.ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["evabyte"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == row["source_url"]
    assert entry["file"] == "benchmark/configs/evabyte.json"
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"]) == (
        4, 32)
    # the published widths
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["window_size"], cfg["chunk_size"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["vocab_size"], cfg["num_pred_heads"],
            cfg["max_position_embeddings"]) == (
                4096, 32, 32, 11008, 2048, 16, 100000, 1e-05, 320, 8, 32768)
    assert cfg["pred_heads_built"] == 1
    m = cfg["model"]
    assert (m["eva_pool_logit"], m["eva_key_offset"], m["eva_pool_rotated"],
            m["head"]) == ("scaled_phi_dot_key", "added_to_pooled_key",
                           "after_rotation", "first_vocab_size_columns")
    assert all(m[k + "_why"].startswith("ASSUMED") for k in (
        "eva_pool_logit", "eva_key_offset", "eva_pool_rotated", "head"))
    assert "weights" in cfg["assumed"] and len(cfg["departures"]) >= 5
    assert all(k in cfg for k in ("precision", "deployment", "bytes"))
    assert "eight pipeline stages of four layers" in cfg["deployment"]
    assert cfg["serve"]["max_seq"] == 16384 and "slots_why" in cfg["serve"]
    chk = cfg["check"]["serve"]
    assert chk["prompt_lens"] == [1500, 4090, 12300]
    assert chk["decode_steps"] == 8
    assert chk["reference_precision"] == "bf16_ops"
    mix = _json(harness.BENCH_DIR, "traffic", "bytes-closed-2x-any.json")
    assert mix["prompt_len"] == {"median": 5120, "sigma": 0.4, "min": 2304,
                                 "max": 12288}
    assert mix["max_new"] == {"median": 256, "sigma": 0.4, "min": 96,
                              "max": 1024}
    assert (mix["clients_per_slot"], mix["requests"], mix["ramp_group"]) == (
        2, 160, 4)
    assert mix["warm_admit_sizes"] == [1, 2, 4]
    assert (mix["tail"], mix["tail_metric"], mix["rate_metric"]) == (
        0.8, "request_ms_p80", "serve_tokens_per_s")
    assert (mix["settle_seconds"], mix["trace_seconds"]) == (3.0, 3.0)


def test_builder_reads_the_published_keys(cfg):
    import numpy as np

    from benchmark.models import evabyte_lm

    dc = evabyte_lm.decode_config(cfg, "serve_closed")
    assert dc.layer_kinds() == ["eva"] * 4
    assert dc.ffn_kinds() == ["dense"] * 4
    assert (dc.n_head, dc.n_kv_head, dc.d_head, dc.d_model, dc.d_inner,
            dc.vocab_size, dc.window, dc.eva_chunk, dc.max_len) == (
                32, 32, 128, 4096, 11008, 320, 2048, 16, 16384)
    assert dc.eva_rows == (1024, 2048)
    assert dc.rope == {"full": {"rotary_dim": 128, "theta": 1e5}}
    assert (dc.norm, dc.norm_eps, dc.norm_offset, dc.head_precision,
            dc.tie_embeddings) == ("rms_norm", 1e-5, True, "highest", False)
    specs = evabyte_lm.parameter_specs(cfg, "serve_closed")
    total = sum(int(np.prod(s)) for _, s, _ in specs)
    assert total == eva_cost.weight_params(cfg)
    assert round(4 * total / 1e9, 2) == 3.25             # ISSUE 47's bytes
    assert evabyte_lm.init_rule("lm.l0.norm_in.w", (4096,)) == (0.0, 0.1)
    assert evabyte_lm.init_rule("lm.l0.attention.phi", (32, 128)) == (
        0.0, evabyte_lm.PHI_STD)
    assert evabyte_lm.init_rule("lm.l0.attention.mu", (32, 128)) == (
        0.0, evabyte_lm.MU_STD)
    assert evabyte_lm.init_rule("lm.l0.attention.q.w", ()) == (0.0, 0.02)


def test_cost_functions_of_the_published_widths(cfg):
    """The figures of ISSUE 47: a layer 202.39 M = 809.6 MB, 3.25 GB of
    weights, 100.7 MB a slot and layer, 6.44 GB for 16 slots."""
    assert round(eva_cost.layer_params(cfg) / 1e6, 2) == 202.39
    assert round(4 * eva_cost.layer_params(cfg) / 1e6, 1) == 809.6
    assert round(4 * eva_cost.weight_params(cfg) / 1e9, 2) == 3.25
    assert round(4 * eva_cost.dense_params(cfg) / 1e9, 2) == 3.24
    assert eva_cost.row_bytes(cfg) == 16384
    assert eva_cost.entry_rows(cfg) == 3072
    assert round(eva_cost.slot_bytes(cfg) / 4 / 1e6, 1) == 100.7
    assert round(16 * eva_cost.slot_bytes(cfg) / 1e9, 2) == 6.44
    # a live slot at position 5,000: 905 rows of its window, 256 summaries
    assert eva_cost.eva_step_bytes(cfg, 905, 256) == 4 * 2 * 16384 * 1161
    assert eva_cost.step_bytes(cfg, 905, 256) == (
        4 * eva_cost.dense_params(cfg) + 4 * 2 * 16384 * 1161)
    assert eva_cost.prefill_flops(cfg, 1, 1, 1) == (
        2.0 * 4 * eva_cost.matmul_params(cfg) + 2.0 * 4 * 32 * 256
        + 2.0 * 4096 * 320)
    assert eva_cost.patterns(cfg) == [",3072,32,128]", ",16,32,128]"]


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
    """One decode step of 12 ms and one prefill of 200 ms: the readers
    tell the kernels by name and the pooling, the appends and the
    packing by the shapes an event's text holds, and each share counts
    what must be done."""
    ms = 1e6
    append = ("%fusion.3 = f32[16,3072,32,128] fusion(f32[16,3072,32,128] "
              "%feeds__keva_0__, f32[16,32,128] %k)")
    pool = "%fusion.4 = f32[16,32,128] fusion(f32[16,16,32,128] %rows)"
    head = "%fusion.7 = f32[16,320] fusion(f32[4096,320] %state__lm_head_w__)"
    chunks = ("%fusion.8 = f32[1,512,32,128] fusion(f32[1,512,16,32,128] "
              "%kc)")
    pack = "%fusion.9 = f32[1,3072,32,128] fusion(f32[1,8192,32,128] %k)"
    loop = "%while.12 = (s32[], f32[1,3072,32,128]) while()"
    call = "%%%s = custom-call()"
    ops = [("fusion.3", 0.0, 0.5 * ms, append),
           ("fusion.4", 0.5 * ms, 0.5 * ms, pool),
           ("ptpu.eva_attn.2", 1.0 * ms, 4.0 * ms, call % "ptpu.eva_attn.2"),
           ("fusion.7", 5.0 * ms, 7.0 * ms, head),
           ("fusion.8", 20 * ms, 2 * ms, chunks),
           ("ptpu.eva_prefill.1", 22 * ms, 10 * ms,
            call % "ptpu.eva_prefill.1"),
           ("ptpu.eva_prefill.3", 32 * ms, 4 * ms,
            call % "ptpu.eva_prefill.3"),
           ("while.12", 36 * ms, 10 * ms, loop),  # a loop is not told
           ("fusion.9", 36 * ms, 2 * ms, pack),
           ("fusion.11", 46 * ms, 174 * ms, head)]
    modules = [("jit_ptpu_decode_b16_s16384(1)", 0.0, 12 * ms),
               ("jit_ptpu_prefill_b1_s8192(2)", 20 * ms, 200 * ms)]
    step = {"active": 16, "attended": 16 * 1300, "eva_window_rows": 16 * 1000,
            "eva_summary_rows": 16 * 300, "eva_chunks_closed": 1}
    pairs = 2 * 2048 * 2049 // 2 + 904 * 905 // 2 + 128 * (2048 + 2 * 904)
    host = [(program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, step, "loop"),
            (program_spans.LOOP + "scatter", 221 * ms, 1 * ms,
             {"entries": 8, "prompt_rows": 5000, "bucket_rows": 8192,
              "prompts": 1, "attn_pairs": pairs, "eva_window_rows": 904,
              "eva_summary_rows": 256}, "loop")]
    run = _run_of(cfg, ops, modules, host)
    read = lambda name: harness.load_layer_metric(name).read(run)  # noqa
    busy = 12 + 200
    assert read("eva_time_pct.serve") == pytest.approx(
        100 * (0.5 + 0.5 + 4 + 2 + 10 + 4 + 2) / busy)
    assert read("eva_summary_rows_pct.serve") == pytest.approx(
        100 * 300 / 1300)
    assert read("eva_decode_roofline.serve") == pytest.approx(
        100 * eva_cost.eva_step_bytes(cfg, 16000, 4800) / 819e9 / 4e-3)
    assert read("decode_step_roofline_eva.serve") == pytest.approx(
        100 * eva_cost.step_bytes(cfg, 16000, 4800) / 819e9 / 12e-3)
    assert read("prefill_mfu_pct_eva.serve") == pytest.approx(
        100 * eva_cost.prefill_flops(cfg, 5000, pairs, 1) / (197e12 * 0.2))
    for name in READERS:
        assert 0 < read(name) < 100, name
    # a configuration of another family, or a program without the
    # counts (the parent), reads nothing and does not raise
    other = dict(run, cfg={"mamba_d_state": 16, "kv_lora_rank": 256})
    other["_spans"] = dict(run["_spans"], host=[
        (program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, {"active": 16},
         "loop")])
    for name in READERS:
        assert harness.load_layer_metric(name).read(other) is None, name
    bare = _run_of(cfg, ops, modules, [
        (program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, {"active": 16},
         "loop"),
        (program_spans.LOOP + "scatter", 221 * ms, 1 * ms, {"entries": 8},
         "loop")])
    for name in READERS[1:]:
        assert harness.load_layer_metric(name).read(bare) is None
    assert harness.load_layer_metric(READERS[0]).read(
        _run_of(cfg, [], [], [])) is None


def test_benchmark_json_lists_the_cell_where_a_reader_reads_it():
    bench = _json(harness.ROOT, "BENCHMARK.json")
    mine = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    assert set(READERS) <= mine
    assert {"slot_occupancy_pct.serve", "decode_tokens_per_s.serve",
            "device_idle_pct.serve", "idle_step_host_pct.serve",
            "idle_admit_pct.serve", "idle_unattributed_pct.serve",
            "token_gap_ms_p95.serve", "prefill_busy_pct.serve",
            "state_scatter_ms.serve", "setup_acquire_s.serve",
            "setup_load_s.serve", "setup_executables.serve",
            "request_ms_p80.serve"} <= mine
    assert len(mine) == 18
    assert {m["name"] for m in harness.metrics_of(
        bench, "end_to_end", CELL)} == {"serve_tokens_per_s", "setup_s"}
    assert len(bench["workloads"]) == 11 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["configs"]) == 10
