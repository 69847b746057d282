"""`ssm_scan_roofline.serve` (PR 41) on synthetic traces: the same bytes
over the same time whichever anchor finds the scans' events (`while`
loops of the lax form, or Mosaic calls named `ptpu.ssm_scan.*`), a
window that holds both forms, and nothing where the `scatter` phases
carry no `ssm_tokens` (the parent), where no admission fell in the
window, or for another family's configuration."""
import json
import os

import pytest

from benchmark.lib import harness, program_spans

NAME = "ssm_scan_roofline.serve"
CELL = "jamba2-3b.serve-closed"
SCATTER = program_spans.LOOP + "scatter"
PEAKS = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1e6  # a trace's times are in ns


@pytest.fixture
def cfg():
    with open(os.path.join(harness.ROOT, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def synthetic(monkeypatch):
    monkeypatch.setattr(program_spans, "of_run",
                        lambda run: run.get("_spans"))


def _run_of(cfg, ops, modules, host):
    return {"cfg": cfg, "peaks": PEAKS, "trace": {"path": "synthetic"},
            "cell": {"name": CELL},
            "_spans": {"ops": {"/device:TPU:0": ops},
                       "modules": {"/device:TPU:0": modules}, "host": host}}


def _read(run):
    return harness.load_layer_metric(NAME).read(run)


def _events(form, at, each, n=13):
    """``n`` scan events of ``each`` ns from ``at`` on, 1 ms apart."""
    if form == "while":
        return [("while.%d" % i, at + i * MS, each,
                 "%%while.%d = (s32[], f32[2,16,5120]) while()" % i)
                for i in range(n)]
    return [("ptpu.ssm_scan.%d" % i, at + i * MS, each,
             "%%ptpu.ssm_scan.%d = (f32[2,512,5120], f32[2,16,5120]) "
             "custom-call(s32[2] %%lens)" % i) for i in range(n)]


MODULES = [("jit_ptpu_decode_b64_s2048(1)", 0.0, 10 * MS),
           ("jit_ptpu_prefill_b2_s512(2)", 20 * MS, 40 * MS)]
OTHER = [("fusion.4", 0.0, 9 * MS, "%fusion.4 = f32[64,65536] fusion()"),
         ("while.99", 1 * MS, 2 * MS, "%while.99 = (f32[]) while()"),
         ("fusion.8", 34 * MS, 20 * MS,
          "%fusion.8 = f32[2,512,5120] fusion(f32[2,512,5120] "
          "%ptpu.ssm_scan.3)")]
HOST = [(SCATTER, 61 * MS, 1 * MS,
         {"entries": 28, "state_slots": 2, "ssm_tokens": 700,
          "ssm_pad_tokens": 324}, "loop")]


@pytest.mark.parametrize("form", ["while", "scope"])
def test_the_same_bytes_over_the_same_time_by_either_anchor(cfg, form,
                                                            capsys):
    """13 events of 0.4 ms inside the prefill's program: 700 live tokens
    x 13 layers x (3 x 5,120 + 2 x 16) x 4 B over the HBM peak against
    5.2 ms. The decode program's loop and a fusion that names a scan as
    its OPERAND are not the scan."""
    mod = harness.load_layer_metric(NAME)
    assert mod.ssm_layers(cfg) == 13
    assert mod.layer_bytes_per_token(cfg) == (3 * 5120 + 2 * 16) * 4
    run = _run_of(cfg, OTHER + _events(form, 21 * MS, 0.4 * MS), MODULES,
                  HOST)
    want = 100.0 * (700 * 13 * 61568 / 819e9) / (13 * 0.4e-3)
    assert _read(run) == pytest.approx(want)
    assert 0 < want < 100
    line = capsys.readouterr().out
    assert ("13 events by scope and 0 by while" if form == "scope"
            else "0 events by scope and 13 by while") in line
    assert "700 live and 324 padded tokens" in line


def test_a_window_with_both_forms_reads_each_program_by_its_own(cfg):
    """A bucket under one block of positions keeps the lax form: its
    `while` events are read beside the other program's kernel calls."""
    modules = MODULES + [("jit_ptpu_prefill_b1_s64(3)", 70 * MS, 20 * MS)]
    ops = (OTHER + _events("scope", 21 * MS, 0.4 * MS)
           + _events("while", 71 * MS, 0.1 * MS))
    host = HOST + [(SCATTER, 91 * MS, 1 * MS,
                    {"entries": 28, "ssm_tokens": 50,
                     "ssm_pad_tokens": 14}, "loop")]
    want = 100.0 * (750 * 13 * 61568 / 819e9) / (13 * 0.5e-3)
    assert _read(_run_of(cfg, ops, modules, host)) == pytest.approx(want)


def test_nothing_without_the_counts_or_an_admission(cfg):
    ops = OTHER + _events("while", 21 * MS, 0.4 * MS)
    # the parent: its phases carry no `ssm_tokens`
    bare = [(SCATTER, 61 * MS, 1 * MS, {"entries": 28, "state_slots": 2},
             "loop")]
    assert _read(_run_of(cfg, ops, MODULES, bare)) is None
    # no admission in the window: decode steps alone, or no scatter
    # opened after the prefill started
    assert _read(_run_of(cfg, OTHER[:2], MODULES[:1], HOST)) is None
    early = [(SCATTER, 5 * MS, 1 * MS, dict(HOST[0][3]), "loop")]
    assert _read(_run_of(cfg, ops, MODULES, early)) is None
    assert _read(_run_of(cfg, [], [], [])) is None
    assert _read(dict(_run_of(cfg, ops, MODULES, HOST), _spans=None)) is None
    # a prefill program with neither a loop nor a named call
    assert _read(_run_of(cfg, OTHER, MODULES, HOST)) is None
    # another family's configuration
    for other in ({"hidden_size": 8}, {"mamba_d_state": 16,
                                       "kv_lora_rank": 256}):
        assert _read(dict(_run_of(cfg, ops, MODULES, HOST),
                          cfg=other)) is None


def test_benchmark_json_lists_the_metric_for_the_hybrid_cell_alone():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert bench["per_layer"][-1] == entry
    mod = harness.load_layer_metric(NAME)
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        "kernels", "%", "serve_tokens_per_s", "device_trace")
