"""`lib/scope_time.py` on synthetic tuples: events joined to their
program's map by the module they lie in, containers left out, a weight
read by two dense events counted once."""
import pytest

from benchmark.lib import scope_time

MS = 1e6


def _op(scope=(), members=(), reads=(), bwd=False, users=()):
    return {"scope": list(scope), "pass": "bwd" if bwd else "fwd",
            "members": list(members), "users": list(users),
            "reads": list(reads)}


W1, W2 = "state['lm.l0.ffn.w1']", "state['lm.l0.ffn.w2']"
MAPS = {
    "ptpu_decode_b8_s64": {
        "module": "jit_ptpu_decode_b8_s64", "scoped": True,
        "params": {W1: 4_000_000, W2: 4_000_000, "feeds['kcache_0']": 999},
        "ops": {
            # the same instruction name in both programs, another op
            "fusion.1": _op(["fl.mul:lm.l0.ffn.w1"], reads=[W1]),
            "fusion.2": _op(["fl.layer_norm:layer_norm_0.w_0"],
                            ["fl.layer_norm:layer_norm_0.w_0",
                             "fl.mul:lm.l0.ffn.w2"], [W1, W2]),
            "ptpu.decode_attn.4": _op(
                ["fl.decode_attention:tmp_0", "ptpu.decode_attn"],
                reads=["feeds['kcache_0']"]),
            "copy.3": _op(), "while.5": _op(["fl.while:tmp_9"])}},
    "ptpu_prefill_b1_s64": {
        "module": "jit_ptpu_prefill_b1_s64", "scoped": True,
        "params": {W1: 4_000_000},
        "ops": {"fusion.1": _op(["fl.softmax:tmp_3"]),
                "fusion.7": _op([], ["fl.mul:lm.l0.ffn.w1"], [W1])}},
    "stale": {"module": "jit_ptpu_prefill_b1_s128", "scoped": False,
              "params": {}, "ops": {"fusion.1": _op()}},
}
MODULES = [("jit_ptpu_decode_b8_s64(1)", 0.0, 10 * MS),
           ("jit_ptpu_prefill_b1_s64(2)", 10 * MS, 10 * MS),
           ("jit_ptpu_decode_b8_s64(1)", 20 * MS, 10 * MS),
           ("jit_ptpu_prefill_b1_s128(3)", 30 * MS, 5 * MS),
           ("jit_ptpu_admit_scatter(4)", 35 * MS, 1 * MS)]


def _ev(name, start_ms, dur_ms):
    return (name, start_ms * MS, dur_ms * MS, "%" + name + " = ...")


OPS = [
    _ev("fusion.1", 0, 2), _ev("fusion.2", 2, 2),
    _ev("ptpu.decode_attn.4", 4, 3), _ev("copy.3", 7, 1),
    _ev("while.5", 8, 2), _ev("fusion.1", 8, 1),        # the loop's body
    _ev("fusion.1", 10, 4), _ev("fusion.7", 14, 6),     # the prefill
    _ev("fusion.1", 20, 2), _ev("fusion.2", 22, 2),
    _ev("fusion.1", 30, 5),                             # an unscoped blob
    _ev("fusion.9", 35, 1),                             # no map at all
]


@pytest.fixture
def joined():
    return scope_time.join(OPS, MODULES, MAPS)


def test_events_that_share_a_name_are_told_apart_by_their_module(joined):
    table = scope_time.reduce_events(OPS, joined)
    rows = {p["program"]: p for p in table["programs"]}
    decode = dict(rows["jit_ptpu_decode_b8_s64"]["classes"])
    prefill = dict(rows["jit_ptpu_prefill_b1_s64"]["classes"])
    # `fusion.1` is a product in the step and a softmax in the prefill
    assert decode["fl.mul:lm.l*.ffn.w*"] == pytest.approx(0.005)
    assert prefill["fl.softmax:tmp_*"] == pytest.approx(0.004)
    # an event without a scope of its own goes under its first member
    assert prefill["fl.mul:lm.l*.ffn.w*"] == pytest.approx(0.006)
    assert decode["fl.layer_norm:layer_norm_*.w_*"] == pytest.approx(0.004)
    assert decode["ptpu.decode_attn"] == pytest.approx(0.003)
    assert rows["jit_ptpu_decode_b8_s64"]["calls"] == 2
    assert rows["jit_ptpu_decode_b8_s64"]["s_per_call"] == pytest.approx(.01)
    big = table["largest"][0]
    assert (big["name"], big["program"]) == (
        "fusion.7", "jit_ptpu_prefill_b1_s64")
    assert big["reads"] == [W1] and big["members"] == ["fl.mul:lm.l0.ffn.w1"]
    assert any(ln.startswith("largest fusion.7 program=jit_ptpu_prefill")
               for ln in scope_time.lines(table))


def test_containers_are_left_out_and_unscoped_programs_are_not_unnamed(
        joined):
    table = scope_time.reduce_events(OPS, joined)
    rows = {p["program"]: p for p in table["programs"]}
    decode = rows["jit_ptpu_decode_b8_s64"]
    # `while.5` (2 ms) is in no sum; its body's `fusion.1` is
    assert decode["op_s"] == pytest.approx(0.013)
    assert "fl.while:tmp_*" not in dict(decode["classes"])
    assert decode["unnamed_s"] == pytest.approx(0.001)      # `copy.3`
    assert table["unnamed"] == [
        ["copy", "jit_ptpu_decode_b8_s64", pytest.approx(0.001), 1]]
    assert "unnamed copy program=jit_ptpu_decode_b8_s64 seconds=0.001000 " \
        "events=1" in scope_time.lines(table)
    stale = rows["jit_ptpu_prefill_b1_s128"]
    assert stale["mapped"] and not stale["scoped"]
    assert not rows["jit_ptpu_admit_scatter"]["mapped"]
    # named over mapped: the scoped programs alone (13 + 10 ms)
    assert table["mapped_s"] == pytest.approx(0.023)
    assert table["named_s"] == pytest.approx(0.022)
    assert table["busy_s"] == pytest.approx(0.030)


def test_decode_dense_roofline_counts_a_weight_read_by_two_events_once(
        joined):
    out = scope_time.dense_roofline(joined, "jit_ptpu_decode_", 1e9)
    # `fusion.1` streams W1, three times in two calls (once more in the
    # loop's body); `fusion.2` (a member is the product against W2)
    # reads W1 and W2 and streams W2, once a call
    assert out["bytes"] == 3 * 4_000_000 + 2 * 4_000_000
    assert out["seconds"] == pytest.approx(0.009)
    assert out["pct"] == pytest.approx(100 * 0.020 / 0.009)
    assert scope_time.dense_roofline(joined, "jit_ptpu_verify_", 1e9) is None
    # a weight that a prefetch or a `copy` moved first is read from HBM
    # by that operation, not by the product: left out on both sides
    MAPS["ptpu_decode_b8_s64"]["ops"]["fusion.2"]["copied"] = [W2]
    try:
        out = scope_time.dense_roofline(joined, "jit_ptpu_decode_", 1e9)
    finally:
        del MAPS["ptpu_decode_b8_s64"]["ops"]["fusion.2"]["copied"]
    assert out["bytes"] == 3 * 4_000_000
    assert out["seconds"] == pytest.approx(0.005)
    dense = scope_time.select_s(
        joined, "jit_ptpu_prefill_",
        lambda e, m: scope_time.is_dense(e, m["params"]))
    assert dense == pytest.approx(0.006)


def test_a_mosaic_call_is_named_by_its_own_name_without_a_map():
    assert scope_time.class_of("jvp_ptpu.flash_fwd_.3", None) == \
        "ptpu.flash_fwd"
    assert scope_time.class_of("fusion.3", None) == scope_time.UNNAMED
    assert scope_time.class_of(
        "fusion.3", _op(["fl.mul:w"], bwd=True)) == "fl.mul:w bwd"
    # the wait for a weight's prefetch goes under the op it is for, and
    # counts as dense time where that op is a product against the weight
    wait = _op(users=["fl.mul:lm.l0.ffn.w1"], reads=[W1])
    assert scope_time.class_of("slice-done.9", wait) == "fl.mul:lm.l*.ffn.w*"
    assert scope_time.is_dense_wait(wait)
    assert not scope_time.is_dense(wait, {W1: 1})
    assert not scope_time.is_dense_wait(_op(users=["fl.mul:lm.l0.ffn.w1"]))
