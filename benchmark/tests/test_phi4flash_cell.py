"""The Phi-4-mini-flash cell's files end to end at a tiny size on the
CPU (`lib/run_serveany.py` as it is, the tiny configuration in the
cell's place), the configuration's file against the catalog's rule, and
the cost functions and the three readers the cell brought, on synthetic
traces."""
import json
import os
import sys
import time

import pytest

from benchmark.lib import (harness, peaks, program_spans, shared_kv_cost,
                           stats, trace_reduce, traffic)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "phi4-mini-flash.serve-closed"
NEW = ("decode_step_roofline_shared.serve", "shared_kv_time_pct.serve",
       "prefill_tail_rows_pct.serve", "diff_attn_rows_roofline.serve")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture
def cfg():
    return _json(harness.BENCH_DIR, "configs", "phi4-mini-flash.json")


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
                _json(HERE, "tiny", "phi4flash-tiny.json"),
                _json(HERE, "tiny", "chat-tiny-any.json"))

    monkeypatch.setattr(harness, "load_cell", load_cell)


@pytest.mark.parametrize("trace", [0, 1])
def test_phi4flash_cell_runs_tiny(lifted, capsys, trace):
    rc = harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 5),
                       "--seconds", "1.5", "--trace", str(trace)],
                      time.time())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    if trace:
        # the host-side readers read on the CPU too, the program's
        # scatter counts among them
        assert {"slot_occupancy_pct.serve", "decode_tokens_per_s.serve",
                "request_ms_p90.serve", "state_scatter_ms.serve",
                "prefill_tail_rows_pct.serve"} <= set(res["metrics"])
        assert 0 < res["metrics"]["prefill_tail_rows_pct.serve"][
            "value"] < 25  # one row of a tiny prompt of 4-24
    else:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    checks = [ln for ln in out if ln.startswith("check ")]
    assert len(checks) == 4 and all(ln.endswith("ok") for ln in checks)


def test_comparison_sees_each_variant(lifted, monkeypatch, tmp_path):
    """`tools/variants_serveany.py` at the tiny size: the program
    passes against the reference, and fails against a reference with
    lam = 0, without the heads' norm, with the memory taken after the
    gate, or with cross layers one key short."""
    from benchmark.reference import phi4flash
    from benchmark.tools import variants_serveany as tool

    recs = []
    monkeypatch.setattr(tool, "_emit", recs.append)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    variants = list(phi4flash.VARIANTS[1:])
    assert variants == ["no_lambda", "no_subln", "gmu_gated_memory",
                        "cross_one_short"]
    monkeypatch.setattr(sys, "argv", [
        "variants_serveany.py", "--workload", CELL, "--seed",
        str(2 ** 31 + 7), "--variants", ",".join(variants)])
    tool.main()
    assert len(recs) == 2 * (1 + len(variants))
    for rec in recs:
        whole = "+" not in rec["reference"]
        assert rec["ok"] is whole, rec
        assert (rec["program_vs_reference"] <= rec["limit"]) is whole


def test_configuration_keeps_the_catalogs_numbers(cfg):
    """Every key of the catalog's `config` under the same key; what
    differs is named in `reduced`; what the catalog lacks is assumed,
    one field each."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = [json.loads(ln) for ln in f
               if '"Phi-4-mini-flash-reasoning"' in ln][0]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == row["config"][
        "num_hidden_layers"] // 2
    assert cfg["source"] == row["source_url"]
    bench = _json(harness.ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["phi4-mini-flash"]
    assert set(entry["reduced"]) == differs
    assert entry["source"] == cfg["source"]
    a = cfg["assumed_sizes"]
    assert a["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    for key in ("head_dim", "differential_attention", "attention_bias",
                "mamba"):
        assert a[key + "_why"].startswith("ASSUMED")
    assert cfg["mamba_dt_rank"] * 16 == cfg["hidden_size"]


def test_the_mix_is_the_issues(cfg):
    mix = _json(harness.BENCH_DIR, "traffic", "reason-closed-2x-any.json")
    assert mix["kind"] == "serveany_closed" and mix["clients_per_slot"] == 2
    assert mix["prompt_len"] == {"median": 256, "sigma": 0.8, "min": 32,
                                 "max": 1024}
    assert mix["max_new"] == {"median": 512, "sigma": 0.8, "min": 128,
                              "max": 3072}
    assert (mix["requests"], mix["warm_admit_sizes"], mix["ramp_group"],
            mix["tail"]) == (136, [1, 2, 4, 8], 8, 0.9)
    reqs = traffic.serve_requests(mix, cfg["vocab_size"], 2 ** 31 + 1)
    assert max(len(p) + n for p, n in reqs) <= cfg["serve"]["max_seq"]


def test_cost_functions_of_the_published_widths(cfg):
    kinds = shared_kv_cost.layer_kinds(cfg)
    assert [kinds.count(k) for k in ("mamba", "sliding", "attention",
                                     "gmu", "cross")] == [5, 4, 1, 3, 3]
    assert shared_kv_cost.full_layer(cfg) == 9
    assert shared_kv_cost.slab_readers(cfg) == 4
    assert shared_kv_cost.kv_row_bytes(cfg) == 10240
    assert shared_kv_cost.ring_row_bytes(cfg) == 4 * 10240
    per = shared_kv_cost.layer_params(cfg)
    mlp = 3 * 2560 * 10240 + 4 * 2560
    assert round((per["mamba"] - mlp) / 1e6, 2) == 41.24
    assert round((per["attention"] - mlp) / 1e6, 2) == 19.67
    assert round((per["gmu"] - mlp) / 1e6, 2) == 26.21
    assert round((per["cross"] - mlp) / 1e6, 2) == 13.11
    w = shared_kv_cost.decode_weight_params(cfg)
    assert round(w / 1e9, 3) == 2.193                   # 8.77 GB
    # a slot: the ONE slab of 4096 rows and four rings of 512: 62.9 MB
    slot = (4096 * shared_kv_cost.kv_row_bytes(cfg)
            + 512 * shared_kv_cost.ring_row_bytes(cfg))
    assert round(slot / 1e6, 1) == 62.9
    step = {"state_bytes": 2.6e8, "attended": 60000, "slab_readers": 4,
            "ring_rows": 30000}
    assert shared_kv_cost.step_bytes(cfg, step) == (
        4 * w + 2.6e8 + 60000 * 10240 * 4 + 30000 * 40960)


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
    """One decode step of 20 ms and one prefill of 4 ms: the slab's
    events are told by feed name or shape, a ring's are not the slab's,
    the step's roofline counts live rows times readers, and the tail
    rows come from the admissions' scatter phases."""
    ms = 1e6
    slab_v = "%fusion.5 = f32[64,4,128] fusion(f32[64,4096,1280]{2,1,0} %p)"
    ring = "%fusion.6 = f32[64,4,512] fusion(f32[64,512,1280] %feeds__kring_1__)"
    head = "%fusion.4 = f32[64,200064] fusion(f32[200064,2560] %state__lm_tok_emb__)"
    call = ("%ptpu.diff_attn_rows.2 = f32[64,1,40,128] custom-call(s32[64] "
            "%n, f32[64,1,40,128] %q, f32[64,4096,1280] %feeds__kcache_9__)")
    ops = [("ptpu.diff_attn_rows.2", 0.0, 3 * ms, call),
           ("ptpu.diff_attn_rows.3", 3 * ms, 2 * ms, call),
           ("fusion.5", 5 * ms, 3 * ms, slab_v),
           ("fusion.6", 8 * ms, 2 * ms, ring),
           ("fusion.4", 10 * ms, 10 * ms, head),
           ("ptpu.flash_fwd.3", 30 * ms, 1 * ms, "%ptpu.flash_fwd.3 = custom-call()"),
           ("ptpu.attn_window.2", 31 * ms, 1 * ms, "%ptpu.attn_window.2 = custom-call()"),
           ("while.7", 32 * ms, 2 * ms, "%while.7 = while()")]
    modules = [("jit_ptpu_decode_b64_s4096(1)", 0.0, 20 * ms),
               ("jit_ptpu_prefill_b1_s512(2)", 30 * ms, 4 * ms)]
    step = {"active": 64, "attended": 60000, "streamed": 64 * 4096,
            "state_bytes": 2.6e8, "ring_rows": 30000, "slab_readers": 4}
    scatter = program_spans.LOOP + "scatter"
    host = [(program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, step, "loop"),
            (scatter, 34 * ms, 0.5 * ms, {"entries": 20, "prompt_rows": 300,
                                          "tail_rows": 1}, "loop"),
            (scatter, 36 * ms, 0.5 * ms, {"entries": 20, "prompt_rows": 500,
                                          "tail_rows": 3}, "loop")]
    run = _run_of(cfg, ops, modules, host)
    read = lambda name: harness.load_layer_metric(name).read(run)  # noqa
    assert read("shared_kv_time_pct.serve") == pytest.approx(100 * 9 / 24)
    least = shared_kv_cost.step_bytes(cfg, step) / 819e9
    assert read("decode_step_roofline_shared.serve") == pytest.approx(
        100 * least / 20e-3)
    assert 50 < 100 * least / 20e-3 < 100
    assert read("prefill_tail_rows_pct.serve") == pytest.approx(0.5)
    # two calls of the kernel, each streams the step's live rows again
    assert read("diff_attn_rows_roofline.serve") == pytest.approx(
        100 * (2 * 60000 * 10240 / 819e9) / 5e-3)
    lax_only = _run_of(cfg, [o for o in ops if "diff_attn_rows" not in o[0]],
                       modules, host)
    assert harness.load_layer_metric(
        "diff_attn_rows_roofline.serve").read(lax_only) is None
    # a configuration of another family, or a program without the
    # counts (the parent), reads nothing and does not raise
    other = dict(run, cfg={"mamba_d_state": 16, "model_type": "jamba"})
    for name in NEW[:2] + NEW[3:]:
        assert harness.load_layer_metric(name).read(other) is None
    bare = _run_of(cfg, ops, modules, [
        (program_spans.DISPATCH, -0.1 * ms, 0.05 * ms, {"active": 64}, "loop"),
        (scatter, 34 * ms, 0.5 * ms, {"entries": 20}, "loop")])
    assert harness.load_layer_metric(NEW[0]).read(bare) is None
    assert harness.load_layer_metric(NEW[2]).read(bare) is None
    empty = dict(run, trace=None, _spans=None)
    for name in NEW:
        assert harness.load_layer_metric(name).read(empty) is None


def test_the_new_metrics_are_declared_as_they_read():
    bench = _json(harness.ROOT, "BENCHMARK.json")
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        mod = harness.load_layer_metric(name)
        m = by[name]
        assert (m["layer"], m["unit"], m["moves"], m["source"]) == (
            mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE)
        assert m["workloads"] == [CELL]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert not listed & {"decode_attn_roofline.serve", "attn_time_pct.serve",
                         "decode_attn_grouped_roofline.serve",
                         "decode_step_roofline.serve",
                         "decode_step_roofline_moe.serve"}
    assert len(listed) == 16
    assert len(bench["workloads"]) == 7 and len(bench["configs"]) == 6
