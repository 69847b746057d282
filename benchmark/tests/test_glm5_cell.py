"""The GLM-5 cell's files end to end at a tiny size on the CPU
(`lib/run_serveround.py`: the rounds' both positions, the prediction
layer's logits and the first layer's kept rows against the reference,
then `lib/run_serveany.py` as it is; the tiny configuration in the
cell's place: its server runs ROUNDS over the prediction layer without
being asked), the configuration's file against the catalog's rule, and the
cost functions and readers the cell brought, on synthetic traces."""
import json
import os
import sys
import time

import pytest

from benchmark.lib import (glm5_cost, harness, peaks, program_spans, stats,
                           trace_reduce)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "glm-5.serve-closed"
READERS = ("spec_accept_pct.serve", "spec_tokens_per_round.serve",
           "mtp_time_pct.serve", "decode_step_roofline_mtp.serve",
           "dsa_window_roofline.serve", "prefill_mfu_pct_mtp.serve")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture
def cfg():
    return _json(harness.BENCH_DIR, "configs", "glm-5.json")


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
                _json(HERE, "tiny", "glm5-tiny.json"),
                _json(HERE, "tiny", "tool-tiny-any.json"))

    monkeypatch.setattr(harness, "load_cell", load_cell)


def test_the_cells_files_load_by_name():
    bench, cell, cfg, mix = harness.load_cell(harness.ROOT, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-5", "tool-closed-2x-any", 1)
    assert (cfg["builder"], cfg["reference"]) == ("glm5_lm", "glm5")
    assert mix["kind"] == "serveround_closed"
    for name in READERS:
        mod = harness.load_layer_metric(name)
        assert mod.MOVES == "serve_tokens_per_s" and callable(mod.read)


@pytest.mark.parametrize("trace", [0, 1])
def test_glm5_cell_runs_tiny(lifted, capsys, trace):
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
        # readers find no kernel or program of the cell's size here
        assert {"decode_tokens_per_s.serve", "request_ms_p80.serve",
                "moe_load_max_over_mean.serve", "dsa_selected_pct.serve",
                "spec_accept_pct.serve",
                "spec_tokens_per_round.serve",
                "slot_occupancy_pct.serve"} <= set(res["metrics"])
        assert 0 <= res["metrics"]["spec_accept_pct.serve"]["value"] < 5
        assert 1.0 <= res["metrics"]["spec_tokens_per_round.serve"][
            "value"] < 1.05
    else:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    checks = [ln for ln in out if ln.startswith("check ")]
    assert len(checks) == 7 and all(ln.endswith("ok") for ln in checks)
    assert [ln.split()[1] for ln in checks[:3]] == [
        "slab_rows_rel_l2", "round_logits_rel_l2", "draft_logits_rel_l2"]


def test_comparison_sees_each_part_changed(lifted, monkeypatch, tmp_path):
    """`tools/variants_serveany.py` at the tiny size: the program passes
    against the reference and fails against a reference with no
    selection, half of it, unrotated or half-split index keys, a value
    head of half the width, an unrotated k_r or no shared expert."""
    from benchmark.tools import variants_serveany as tool

    recs = []
    monkeypatch.setattr(tool, "_emit", recs.append)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    changed = ["all_rows", "topk_half", "index_keys_not_rotated",
               "index_half_split", "v_half", "kr_not_rotated", "no_shared"]
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


def test_the_rounds_comparisons_see_their_controls(lifted, monkeypatch,
                                                   tmp_path):
    """`tools/calibrate_serveround.py` at the tiny size: the program is
    inside the three limits of `lib/run_serveround.py`; slabs one
    precision lower fail `slab_rows_rel_l2` and leave the logits where
    the limit cannot tell; a prediction layer with a part changed fails
    `draft_logits_rel_l2` and moves nothing else."""
    from benchmark.tools import calibrate_serveround as tool

    recs = []
    monkeypatch.setattr(tool, "_emit", recs.append)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "argv", [
        "calibrate_serveround.py", "--workload", CELL, "--seeds", "1",
        "--first-seed", str(2 ** 31 + 9)])
    tool.main()
    (rec,) = recs
    limits = _json(HERE, "tiny", "glm5-tiny.json")["check"]["serve"]
    names = ("slab_rows_rel_l2", "round_logits_rel_l2",
             "draft_logits_rel_l2")
    assert all(rec["program"][n] <= limits[n] for n in names), rec
    assert rec["program_vs_bf16"]["slab_rows_rel_l2"] > 20 * limits[
        "slab_rows_rel_l2"]
    for part in ("mtp_concat_reversed", "mtp_hidden_before_norm"):
        got = rec["program_vs_highest+" + part]
        assert got["draft_logits_rel_l2"] > 20 * limits[
            "draft_logits_rel_l2"], rec
        assert got["round_logits_rel_l2"] <= limits["round_logits_rel_l2"]
        assert got["slab_rows_rel_l2"] <= limits["slab_rows_rel_l2"]


def test_configuration_keeps_the_catalogs_numbers(cfg):
    """Every key of the catalog's `config` under the same key; what
    differs is named in `reduced`; the share, the assumed fields and
    the cell's sizes are written down."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = [json.loads(ln) for ln in f if '"name": "GLM-5"' in ln][0]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"} == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]
    bench = _json(harness.ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["glm-5"]
    assert set(entry["reduced"]) == differs
    assert entry["source"] == row["source_url"]
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 8]
    assert (cfg["n_routed_experts_scored"]
            == row["config"]["n_routed_experts"] == 256)
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"],
            cfg["vocab_size_published"], cfg["layers_built"],
            cfg["dense_layers_built"]) == (5, 78, 154880, [0, 3, 4, 5, 6], 1)
    assert cfg["num_nextn_predict_layers"] == 1   # the layer is KEPT
    # the published widths
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["index_n_heads"],
            cfg["index_head_dim"], cfg["index_topk"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == (
                6144, 64, 2048, 512, 192, 64, 256, 32, 128, 2048, 2048,
                12288, 8, 2.5)
    a = cfg["assumed"]
    assert (a["mtp_concat"], a["mtp_hidden"], a["mtp_shares"], a["mtp_layer"],
            a["index_rope_channels"], a["draft_tokens"]) == (
                "embedding_first", "after_final_norm", "table_and_head",
                "sparse_with_indexer", "first", 1)
    assert all(a[k + "_why"].startswith("ASSUMED") for k in (
        "mtp_concat", "mtp_hidden", "mtp_shares", "mtp_layer",
        "index_rope_channels", "draft_tokens"))
    assert "weights" in a and len(cfg["departures"]) >= 6
    assert any("accept at chance" in d for d in cfg["departures"])
    assert cfg["precision"]["matrices"] == "bfloat16"
    assert all(k in cfg for k in ("deployment", "bytes"))
    assert "32 chips" in cfg["deployment"]
    assert cfg["serve"]["max_seq"] == 16384
    assert cfg["serve"]["slots"] in (12, 16)  # ISSUE 58's named fall-back
    chk = cfg["check"]["serve"]
    assert chk["prompt_lens"] == [3000, 12000] and chk["decode_steps"] == 8
    assert chk["reference_precision"] == "bf16_ops"
    assert chk["logits_rel_l2"] == 0.08 and chk["control"].startswith("bf16:")
    # the runner's three: the storage control's reading lies over the
    # slabs' limit, the weakest changed part's over the draft's
    assert (chk["slab_rows_rel_l2"], chk["round_logits_rel_l2"],
            chk["draft_logits_rel_l2"]) == (2e-4, 0.08, 0.05)
    mix = _json(harness.BENCH_DIR, "traffic", "tool-closed-2x-any.json")
    assert mix["prompt_len"]["median"] == 3072
    assert mix["prompt_len"]["min"] == 2304 > cfg["index_topk"]
    assert mix["prompt_len"]["max"] in (12288, 8192)
    assert mix["max_new"]["median"] == 384 and mix["max_new"]["min"] == 128
    assert mix["max_new"]["max"] in (1024, 768)
    assert (mix["clients_per_slot"], mix["requests"], mix["ramp_group"]) == (
        2, 64, 4)
    assert mix["warm_admit_sizes"] == [1, 2, 4]
    assert (mix["tail"], mix["tail_metric"]) == (0.8, "request_ms_p80")
    assert (mix["settle_seconds"], mix["trace_seconds"]) == (3.0, 3.0)
    assert (mix["prompt_len"]["max"] + mix["max_new"]["max"]
            <= cfg["serve"]["max_seq"])


def test_builder_reads_the_published_keys(cfg):
    import numpy as np

    from benchmark.models import glm5_lm

    dc = glm5_lm.decode_config(cfg, "serve_closed")
    assert dc.layer_kinds() == ["latent_dsa"] * 5
    assert dc.ffn_kinds() == ["dense"] + ["experts"] * 4
    assert dc.n_predict_layers == 1 and dc.matrix_dtype == "bfloat16"
    assert dc.latent_row == 576 and not dc.latent_rescale
    assert dc.attn_gate is None
    assert (dc.q_lora_rank, dc.kv_lora_rank, dc.qk_nope_dim, dc.qk_rope_dim,
            dc.v_head_dim, dc.n_head, dc.d_model, dc.d_inner) == (
                2048, 512, 192, 64, 256, 64, 6144, 12288)
    assert (dc.index_heads, dc.index_head_dim, dc.index_topk) == (
        32, 128, 2048)
    assert (dc.n_expert, dc.expert_top_k, dc.d_expert, dc.d_shared_expert,
            dc.held, dc.router_groups, dc.router_bias) == (
                256, 8, 2048, 2048, (0, 8), 1, True)
    assert dc.router_score == "sigmoid" and dc.router_scale == 2.5
    assert dc.rope == {
        "latent": {"theta": 1e6, "interleave": True},
        "index": {"theta": 1e6, "rotary_dim": 64, "interleave": True}}
    specs = glm5_lm.parameter_specs(cfg, "serve_closed")
    nbytes = sum(int(np.prod(s)) * np.dtype(t).itemsize for _, s, t in specs)
    assert nbytes == glm5_cost.weight_bytes(cfg)
    assert round(nbytes / 1e9, 2) == 6.60                # ISSUE 58: 6.59
    total = sum(int(np.prod(s)) for _, s, _ in specs)
    assert round(total / 1e9, 3) == 3.293                # ISSUE 58
    assert {str(np.dtype(t)) for _, _, t in specs} == {"bfloat16",
                                                       "float32"}


def test_cost_functions_of_the_published_widths(cfg):
    """The figures of ISSUE 58: a mixer 174.39 M, the dense MLP 226.49
    M, an expert 37.75 M = 75.5 MB in bfloat16, 276.8 MB a slot, 3.31 GB
    a round outside the routed experts."""
    assert (glm5_cost.n_dense(cfg), glm5_cost.n_sparse(cfg),
            glm5_cost.n_mixers(cfg)) == (1, 4, 6)
    assert round(glm5_cost.mixer_matrices(cfg) / 1e6, 2) == 174.39
    assert round(glm5_cost.mlp_matrices(cfg) / 1e6, 2) == 226.49
    assert glm5_cost.expert_matrices(cfg) == 3 * 6144 * 2048
    assert 2 * glm5_cost.expert_matrices(cfg) == 75497472    # 75.5 MB
    assert round(glm5_cost.slot_bytes(cfg) / 1e6, 1) == 276.8
    assert round(16 * glm5_cost.slot_bytes(cfg) / 1e9, 2) == 4.43
    assert (glm5_cost.latent_row_bytes(cfg),
            glm5_cost.index_key_bytes(cfg)) == (2304, 512)
    head = 6144 * 19360
    dense = 2 * (glm5_cost.row_matrices(cfg) + head)
    assert round(dense / 1e9, 2) == 3.31
    assert glm5_cost.window_bytes(cfg, 6000, 2048) == 6 * (
        6000 * 512 + 2048 * 2304)
    assert glm5_cost.round_bytes(cfg, 30, 6000, 2048) == (
        dense + 30 * 75497472 + 4 * glm5_cost.row_floats(cfg)
        + 6 * (6000 * 512 + 2048 * 2304))
    per_row = glm5_cost.row_matrices(cfg) + 5 * 6144 * 256
    assert glm5_cost.prefill_flops(cfg, 1, 1, 1, 1, 1) == (
        2.0 * per_row + 2.0 * 3 * 6144 * 2048 + 2.0 * 6 * 32 * 128
        + 2.0 * 6 * 64 * 512 + 2.0 * 6144 * 19360 * 2)


def _run_of(cfg, ops, modules, host, spans=()):
    return {"cfg": cfg, "peaks": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"path": "synthetic"}, "cell": {"name": CELL},
            "mix": {"tail_metric": "request_ms_p80"}, "spans": list(spans),
            "end_to_end": {"request_ms_p80": 1234.5},
            "_spans": {"ops": {"/device:TPU:0": ops},
                       "modules": {"/device:TPU:0": modules}, "host": host}}


@pytest.fixture
def synthetic(monkeypatch):
    monkeypatch.setattr(program_spans, "of_run",
                        lambda run: run.get("_spans"))


def test_readers_on_a_synthetic_trace(cfg, synthetic):
    """One round of 12 ms and one prefill of 400 ms: the readers find
    the round by its program's name, its window kernels by theirs, and
    each share counts what must be done."""
    ms = 1e6
    call = "%%%s = custom-call()"
    ops = [("ptpu.dsa_index_step.1", 0.0, 0.5 * ms,
            call % "ptpu.dsa_index_step.1"),
           ("fusion.4", 0.5 * ms, 0.5 * ms, "%fusion.4 = u32[16,2,1]"),
           ("ptpu.dsa_attend_step.1", 1.0 * ms, 2.0 * ms,
            call % "ptpu.dsa_attend_step.1"),
           ("fusion.7", 3.0 * ms, 9.0 * ms, "%fusion.7 = f32[32,19360]"),
           ("ptpu.dsa_attend.2", 20 * ms, 100 * ms,
            call % "ptpu.dsa_attend.2"),
           ("fusion.11", 120 * ms, 300 * ms, "%fusion.11 = f32[1,8192]")]
    modules = [("jit_ptpu_round_b16_s16384(1)", 0.0, 12 * ms),
               ("jit_ptpu_prefill_b1_s8192(2)", 20 * ms, 400 * ms)]
    step = {"active": 15, "attended": 90000, "rows_live": 90000,
            "rows_scored": 16 * 8192, "rows_chosen": 15 * 2048,
            "expert_pairs": 240, "experts_active": 33,
            "round_positions": 30, "round_committed": 15}
    host = [(program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, step, "loop"),
            (program_spans.LOOP + "scatter", 421 * ms, 1 * ms,
             {"entries": 12, "prompt_rows": 6000, "bucket_rows": 8192,
              "prompts": 1, "attn_pairs": 6000 * 6001 // 2,
              "index_pairs": 6000 * 6001 // 2,
              "chosen_pairs": 2048 * 2049 // 2 + 3952 * 2048,
              "expert_pairs": 1500}, "loop")]
    rounds = [{"name": "decode.spec_round", "accepted": a, "proposed": 1}
              for a in (0, 0, 0, 1)] + [{"name": "decode.admit"}]
    run = _run_of(cfg, ops, modules, host, rounds)
    read = lambda name: harness.load_layer_metric(name).read(run)  # noqa
    assert read("spec_accept_pct.serve") == 25.0
    assert read("spec_tokens_per_round.serve") == 1.25
    assert read("dsa_window_roofline.serve") == pytest.approx(
        100 * glm5_cost.window_bytes(cfg, 90000, 15 * 2048) / 819e9 / 2.5e-3)
    assert read("decode_step_roofline_mtp.serve") == pytest.approx(
        100 * glm5_cost.round_bytes(cfg, 33, 90000, 15 * 2048)
        / 819e9 / 12e-3)
    c = host[1][3]
    flops = glm5_cost.prefill_flops(cfg, 6000, 1500, c["index_pairs"],
                                    c["chosen_pairs"], 1)
    assert read("prefill_mfu_pct_mtp.serve") == pytest.approx(
        100 * flops / (197e12 * 0.4))
    for name in READERS[3:]:
        assert 0 < read(name) < 100, name
    # the accepted readers that list the cell read it too
    assert harness.load_layer_metric("dsa_selected_pct.serve").read(
        run) == pytest.approx(100 * 15 * 2048 / 90000)
    assert harness.load_layer_metric("request_ms_p80.serve").read(
        run) == 1234.5
    # a configuration of another family, or a program that runs no round
    # and records no such span (the parent), reads nothing and does not
    # raise
    other = dict(run, cfg={"mamba_d_state": 16, "kv_lora_rank": 256},
                 spans=[{"name": "decode.admit"}])
    for name in READERS[:2] + READERS[3:]:
        assert harness.load_layer_metric(name).read(other) is None, name
    bare = _run_of(cfg, ops, [("jit_ptpu_decode_b16_s16384(1)", 0.0,
                               12 * ms), modules[1]], [
        (program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, {"active": 16},
         "loop"),
        (program_spans.LOOP + "scatter", 421 * ms, 1 * ms, {"entries": 4},
         "loop")])
    for name in READERS[:2] + READERS[3:]:
        assert harness.load_layer_metric(name).read(bare) is None, name


def test_the_prediction_layers_time_is_told_by_its_parameters(monkeypatch):
    """`mtp_time_pct.serve` on `lib/scope_time`'s join: an event under a
    scope anchored on a `<prefix>.mtp.*` parameter, or fused with one,
    is the prediction layer's."""
    from benchmark.lib import scope_time

    mod = harness.load_layer_metric("mtp_time_pct.serve")
    entry = lambda scope, members=(): {  # noqa: E731
        "scope": list(scope), "members": list(members), "users": [],
        "reads": [], "pass": "fwd"}
    assert mod._mtp(entry(["fl.matmul:lm.mtp.eh_proj.w"]), None)
    assert mod._mtp(entry(["fl.mla_decode:lm.mtp.l5.attention.kv_b.w",
                           "ptpu.dsa_attend"]), None)
    assert mod._mtp(entry([], ["fl.rms_norm:lm.mtp.hnorm.w"]), None)
    assert not mod._mtp(entry(["fl.matmul:lm.l4.attention.o.w"]), None)
    assert not mod._mtp(None, None)
    seen = {}

    def share(run, prefix, want):
        seen["prefix"] = prefix
        return 17.5

    monkeypatch.setattr(scope_time, "share_of_busy", share)
    assert mod.read({}) == 17.5 and seen["prefix"] == "jit_ptpu_"
    monkeypatch.setattr(scope_time, "share_of_busy", lambda *a: None)
    assert mod.read({}) is None


def test_benchmark_json_lists_the_cell_where_a_reader_reads_it():
    bench = _json(harness.ROOT, "BENCHMARK.json")
    mine = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    assert set(READERS) <= mine
    assert {"moe_load_max_over_mean.serve", "state_scatter_ms.serve",
            "prefill_busy_pct.serve", "token_gap_ms_p95.serve",
            "setup_executables.serve", "dsa_selected_pct.serve",
            "request_ms_p80.serve", "decode_tokens_per_s.serve",
            "device_idle_pct.serve", "slot_occupancy_pct.serve"} <= mine
    # these look for `ptpu_decode_` programs a server of rounds never
    # runs, or format keys this configuration spells otherwise (`swa_*`,
    # `layer_types`): they are not listed
    assert not {"decode_dense_roofline.serve", "dsa_time_pct.serve", "dsa_decode_roofline.serve",
                "decode_step_roofline_dsa.serve",
                "prefill_mfu_pct_dsa.serve", "request_ms_p90.serve",
                "admit_ms_p90.serve", "moe_time_pct.serve"} & mine
    assert {m["name"] for m in harness.metrics_of(
        bench, "end_to_end", CELL)} == {"serve_tokens_per_s", "setup_s"}
    every = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert all(m["workloads"] == [CELL] for m in every)
    assert len(bench["workloads"]) >= 14 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
