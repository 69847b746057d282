"""`lib/acquire_records.py` over hand-made records, and the four
`setup_*` per-layer entries against the files and cells they name."""
import json
import os

import pytest

from benchmark.lib import acquire_records, harness

T = 1_000.0   # the window opens


def _rec(name, path, ts, wall_ms, **parts):
    return dict({"type": "compile", "kind": "prefill", "name": name,
                 "path": path, "ts": ts, "wall_ms": wall_ms}, **parts)


RECORDS = [
    _rec("ptpu_prefill_b1_s64", "warm", T - 50, 7000.0, build_ms=400.0,
         load_ms=6500.0, blob_bytes=4_000_000, phase="decode.loop.admit"),
    _rec("ptpu_decode_b64_s2048", "warm", T - 40, 300.0, load_ms=250.0),
    _rec("ptpu_prefill_b2_s64", "cold", T - 30, 9000.0, trace_ms=2000.0,
         xla_ms=6000.0, store_ms=500.0, load_ms=11.0),
    _rec("parallel/abcd1234", "lazy", T - 20, 12000.0),
    _rec("run/0badf00d", "warm", T - 10, 20.0),          # no parts at all
    _rec("ptpu_prefill_b4_s64", "warm", T + 1, 5000.0, load_ms=4900.0),
]


def test_records_before_the_window_are_counted_and_the_rest_left_out():
    kept, left_out = acquire_records.split(RECORDS, T)
    assert [r["name"] for r in kept] == [r["name"] for r in RECORDS[:5]]
    assert left_out == 1
    out = acquire_records.summary(kept)
    assert out["executables"] == 5
    assert out["acquire_s"] == pytest.approx(28.32)
    # a cold record's load_ms (a corrupt blob tried first) is no load
    assert out["load_s"] == pytest.approx(6.75)
    assert out["load_s"] <= out["acquire_s"]


def test_without_a_window_stamp_every_record_counts():
    kept, left_out = acquire_records.split(RECORDS)
    assert len(kept) == 6 and left_out == 0
    assert acquire_records.summary(kept)["acquire_s"] == pytest.approx(33.32)


def test_the_parents_timeline_reads_as_nothing():
    assert acquire_records.split([], T) is None
    # the parent's compile records: `cache`, no `path`, no `name`
    parent = [{"type": "compile", "kind": "prefill", "ts": T - 5,
               "cache": "aot-load"},
              {"type": "compile", "kind": "run", "ts": T - 4,
               "cache": "miss", "wall_ms": 3.0}]
    assert acquire_records.split(parent, T) is None
    # one record with a path beside older ones: those are left out
    kept, left_out = acquire_records.split(parent + RECORDS[:1], T)
    assert len(kept) == 1 and left_out == 2


def test_table_is_slowest_first_with_the_parts_each_record_has():
    kept, _ = acquire_records.split(RECORDS, T)
    lines = acquire_records.table(kept)
    assert [ln.split()[0] for ln in lines] == [
        "parallel/abcd1234", "ptpu_prefill_b2_s64", "ptpu_prefill_b1_s64",
        "ptpu_decode_b64_s2048", "run/0badf00d"]
    slow = lines[2]
    assert "warm" in slow and "build=400.0 load=6500.0" in slow
    assert "blob_bytes=4000000 phase=decode.loop.admit" in slow
    assert lines[-1].endswith("blob_bytes=- phase=-")


def test_of_run_reads_the_process_ring_once(capsys):
    """The readers' own path: the program's ring, this process. With the
    program of this tree a record written before the stamp is counted."""
    from paddle_tpu import observability as obs

    if not hasattr(obs, "observe_acquire"):
        pytest.skip("the program writes no acquisition records")
    obs.TIMELINE.reset()
    acquire_records._of_process.cache_clear()
    obs.observe_acquire("prefill", "warm", 40.0, program="abcd1234",
                        name="ptpu_prefill_b1_s64", ts=T - 1, load_ms=30.0)
    obs.observe_acquire("prefill", "warm", 50.0, program="abcd1234",
                        name="ptpu_prefill_b2_s64", ts=T + 1, load_ms=45.0)
    run = {"window_wall": (T, T + 51)}
    first = acquire_records.of_run(run)
    assert first == {"acquire_s": pytest.approx(0.04),
                     "load_s": pytest.approx(0.03), "executables": 1}
    assert acquire_records.of_run(run) is first     # memoised: one table
    printed = capsys.readouterr().out
    assert printed.count("ptpu_prefill_b1_s64") == 1
    assert "1 record(s) left out" in printed
    # the training runner gives no stamp
    assert acquire_records.of_run({})["executables"] == 2
    obs.TIMELINE.reset()
    acquire_records._of_process.cache_clear()
    assert acquire_records.of_run(run) is None


NEW = {"setup_acquire_s.serve": ("s", "serve"),
       "setup_load_s.serve": ("s", "serve"),
       "setup_executables.serve": ("count", "serve"),
       "setup_acquire_s.train": ("s", "train")}


def test_the_new_entries_name_files_and_cells_that_exist():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(NEW)
    for name, (unit, kind) in NEW.items():
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, "lower", "program_span", "model step",
                                "setup_s")
        mod = harness.load_layer_metric(name)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        assert mod.read({}) is None or isinstance(mod.read({}), (int, float))
        # every cell of the kind, and only those
        want = set()
        for c in cells:
            _, _, _, mix = harness.load_cell(harness.ROOT, c)
            if mix["kind"].startswith(kind):
                want.add(c)
        assert set(m["workloads"]) == want and want
