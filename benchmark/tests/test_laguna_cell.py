"""The Laguna cell's files end to end at a tiny size on the CPU
(`lib/run_serveany.py` as it is, the tiny configuration in the cell's
place), the configuration's file against the catalog's rule, and the
cost functions and readers the cell brought, on synthetic traces."""
import json
import os
import sys
import time

import pytest

from benchmark.lib import (harness, moe_cost, peaks, program_spans, stats,
                           trace_reduce)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "laguna-xs.2.serve-closed"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture
def cfg():
    return _json(harness.BENCH_DIR, "configs", "laguna-xs.2.json")


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
                _json(HERE, "tiny", "laguna-tiny.json"),
                _json(HERE, "tiny", "chat-tiny-any.json"))

    monkeypatch.setattr(harness, "load_cell", load_cell)


@pytest.mark.parametrize("trace", [0, 1])
def test_laguna_cell_runs_tiny(lifted, capsys, trace):
    rc = harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 5),
                       "--seconds", "1.5", "--trace", str(trace)],
                      time.time())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    if trace:
        # the host-side readers read on the CPU too; the program's own
        # gauge is there whatever the device
        assert {"slot_occupancy_pct.serve", "decode_tokens_per_s.serve",
                "request_ms_p90.serve", "moe_load_max_over_mean.serve",
                } <= set(res["metrics"])
        assert res["metrics"]["moe_load_max_over_mean.serve"]["value"] >= 1
    else:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    checks = [ln for ln in out if ln.startswith("check ")]
    assert len(checks) == 4 and all(ln.endswith("ok") for ln in checks)


def test_comparison_sees_each_part_left_out(lifted, monkeypatch, tmp_path):
    """`tools/variants_serveany.py` at the tiny size: the program
    passes against the reference, and fails against a reference without
    the shared expert, the routed experts, the pairs past a capacity,
    the gate or the renormalisation."""
    from benchmark.tools import variants_serveany as tool

    recs = []
    monkeypatch.setattr(tool, "_emit", recs.append)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    left_out = ["no_shared", "no_routed", "capacity_drop", "no_gate",
                "no_renorm"]
    monkeypatch.setattr(sys, "argv", [
        "variants_serveany.py", "--workload", CELL, "--seed",
        str(2 ** 31 + 7), "--variants", ",".join(left_out)])
    tool.main()
    assert len(recs) == 2 * (1 + len(left_out))
    for rec in recs:
        whole = "+" not in rec["reference"]
        assert rec["ok"] is whole, rec
        assert (rec["program_vs_reference"] <= rec["limit"]) is whole


def test_configuration_keeps_the_catalogs_numbers(cfg):
    """Every key of the catalog's `config` under the same key; what
    differs is named in `reduced`."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = [json.loads(ln) for ln in f if '"Laguna-XS.2"' in ln][0]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "num_experts"} == set(
        cfg["reduced"])
    assert cfg["source"] == row["source_url"]
    bench = _json(harness.ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["laguna-xs.2"]
    assert set(entry["reduced"]) == differs
    assert cfg["experts_held"] == [0, cfg["num_experts"]]
    assert cfg["num_experts_routed"] == row["config"]["num_experts"]


def test_cost_functions_of_the_published_widths(cfg):
    assert moe_cost.pair_flops(cfg) == 6291456           # 6.29 MFLOP
    assert moe_cost.expert_params(cfg) == 3 * 2048 * 512
    assert moe_cost.sparse_layers(cfg) == [1, 2, 3, 4]
    assert moe_cost.kv_row_bytes(cfg) == 2 * 2 * 8 * 128 * 4
    assert moe_cost.ring_row_bytes(cfg) == 3 * 2 * 8 * 128 * 4
    # a slot: 2 full layers of 4096 rows and 3 rings of 512: 79.7 MB
    slot = (4096 * moe_cost.kv_row_bytes(cfg)
            + 512 * moe_cost.ring_row_bytes(cfg))
    assert round(slot / 1e6, 1) == 79.7
    # a decode step streams at most every weight but the table, 4.99
    # GB, and of the held experts only those that received a pair
    w = moe_cost.decode_weight_params(cfg)
    assert 4.95e9 < 4 * w < 5.05e9
    assert w - moe_cost.decode_weight_params(cfg, 220) == (
        (4 * 64 - 220) * 3 * 2048 * 512)
    total = w + cfg["vocab_size"] * cfg["hidden_size"]
    assert round(total / 1e9, 3) == 1.454                # parameters held
    flops, nbytes = moe_cost.routed_product(cfg, 128, 100, 64)
    assert flops == 128 * 6291456
    assert nbytes == 100 * 3 * 2048 * 512 * 4 + 2 * 64 * 2048 * 4 * 4


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
    """One decode step of 1 ms and one prefill of 2 ms: the readers
    tell experts, attention and the rest apart by what an event reads,
    and a roofline share counts what must be read."""
    ms = 1e6
    expert = ("%fusion.1 = f32[64,64,512] fusion(f32[64,2048,512]{2,1,0} "
              "%state__lm_l1_moe_experts_gate_w__)")
    router = "%fusion.2 = f32[64,256] fusion(f32[2048,256] %state__lm_l1_moe_router_w__)"
    slab = "%fusion.3 = f32[64,8,6,4096] fusion(f32[64,4096,8,128] %feeds__kcache_0__)"
    head = "%fusion.4 = f32[64,100352] fusion(f32[2048,100352] %state__lm_head_w__)"
    ops = [("fusion.1", 0.0, 0.4 * ms, expert),
           ("fusion.2", 0.4 * ms, 0.1 * ms, router),
           ("fusion.3", 0.5 * ms, 0.3 * ms, slab),
           ("fusion.4", 0.8 * ms, 0.2 * ms, head),
           ("ptpu.attn_window.3", 2 * ms, 0.5 * ms, "%ptpu.attn_window.3 = custom-call()"),
           ("while.7", 2.5 * ms, 1.0 * ms, "%while.7 = while()"),
           ("ragged-dot-none.2", 2.6 * ms, 0.2 * ms, "%ragged-dot-none.2 = custom-call()"),
           ("fusion.9", 3.5 * ms, 0.5 * ms, head)]
    modules = [("jit_ptpu_decode_b64_s4096(1)", 0.0, 1.0 * ms),
               ("jit_ptpu_prefill_b1_s1024(2)", 2 * ms, 2.0 * ms)]
    host = [(program_spans.DISPATCH, -0.1 * ms, 0.05 * ms,
             {"active": 64, "attended": 100000, "ring_rows": 30000,
              "expert_pairs": 512, "experts_active": 220}, "loop")]
    run = _run_of(cfg, ops, modules, host)
    read = lambda name: harness.load_layer_metric(name).read(run)  # noqa
    assert read("moe_time_pct.serve") == pytest.approx(100 * 1.5 / 3.0)
    assert read("attn_time_pct.serve") == pytest.approx(100 * 0.8 / 3.0)
    least = max(220 * 3 * 2048 * 512 * 4 / 819e9 + 2 * 64 * 2048 * 16 / 819e9,
                512 * 6291456 / 197e12)
    assert read("moe_experts_roofline.serve") == pytest.approx(
        100 * least / 0.4e-3)
    step = (4 * moe_cost.decode_weight_params(cfg, 220) + 100000 * 16384
            + 30000 * 24576) / 819e9
    assert read("decode_step_roofline_moe.serve") == pytest.approx(
        100 * step / 1e-3)
    # a configuration of another family, or a program without the
    # counts, reads nothing and does not raise
    other = dict(run, cfg={"mamba_d_state": 16})
    for name in ("moe_time_pct.serve", "attn_time_pct.serve",
                 "moe_experts_roofline.serve",
                 "decode_step_roofline_moe.serve"):
        assert harness.load_layer_metric(name).read(other) is None
    bare = _run_of(cfg, ops, modules, [(program_spans.DISPATCH, -0.1 * ms,
                                        0.05 * ms, {"active": 64}, "loop")])
    assert harness.load_layer_metric(
        "moe_experts_roofline.serve").read(bare) is None
    assert harness.load_layer_metric(
        "decode_step_roofline_moe.serve").read(bare) is None
