"""Tier-1 CPU smoke of tools/bench_decode.py: a tiny LM A/B runs in
seconds and every emitted JSON line matches the schema downstream sweep
tooling parses — the decode bench cannot silently rot between device
windows. This pins the CONTRACT, not the numbers (the speedup
is a chip measurement at the real config, not made here). The
in-window test covers the base phases over a two-rung DECODE_STEPS
ladder; the PR-14 arms (--speculative --prefix-share) run in a
slow-marked sibling (tier-1 budget triage — the arms compile extra
signatures and servers)."""
import io
import json
import sys
from contextlib import redirect_stdout

import pytest

_AB_KEYS = {
    "phase": str, "mode": str, "batch": int, "decode_steps": int,
    "prompt_len": int, "seq_bucket": int, "rounds": int, "tokens": int,
    "tokens_per_sec": float, "tokens_per_sec_rounds": list,
    "wall_s": float,
}

_AB_SPEEDUP_KEYS = {
    "phase": str, "batch": int, "decode_steps": int,
    "kv_tokens_per_sec": float, "full_tokens_per_sec": float,
    "speedup": float,
}

_SPEC_AB_KEYS = {
    "phase": str, "mode": str, "batch": int, "decode_steps": int,
    "spec_k": int, "draft_layers": int, "rounds": int, "favorable": bool,
    "tokens_per_sec": float, "tokens_per_sec_rounds": list,
    "wall_s": float,
}

_SPEC_SPEEDUP_KEYS = {
    "phase": str, "batch": int, "decode_steps": int, "spec_k": int,
    "draft_layers": int, "favorable": bool, "acceptance_rate": float,
    "spec_tokens_per_sec": float, "plain_tokens_per_sec": float,
    "speedup": float,
}

_BATCH_KEYS = {
    "phase": str, "mode": str, "slots": int, "requests": int,
    "max_new_mix": str, "rounds": int, "tokens": int,
    "tokens_per_sec": float, "tokens_per_sec_rounds": list,
    "mean_active": float, "decode_iters_per_round": float,
    "wall_s": float,
}

_BATCH_SPEEDUP_KEYS = {
    "phase": str, "slots": int, "requests": int,
    "continuous_tokens_per_sec": float, "static_tokens_per_sec": float,
    "speedup": float, "iters_ratio": float,
}

_PREFIX_AB_KEYS = {
    "phase": str, "mode": str, "slots": int, "requests": int,
    "groups": int, "max_new": int, "rounds": int,
    "prefill_executions": int, "tokens_per_sec": float,
    "tokens_per_sec_rounds": list, "wall_s": float,
}

_PREFIX_SPEEDUP_KEYS = {
    "phase": str, "slots": int, "requests": int, "groups": int,
    "shared_tokens_per_sec": float, "private_tokens_per_sec": float,
    "shared_prefills": int, "private_prefills": int, "speedup": float,
}


def _check_schema(rec, schema):
    assert set(rec) == set(schema), (
        "schema drift: %s vs %s" % (sorted(rec), sorted(schema)))
    for key, typ in schema.items():
        if typ is float:
            assert isinstance(rec[key], (int, float)), (key, rec[key])
        else:
            assert isinstance(rec[key], typ), (key, rec[key])


def _smoke_env(monkeypatch, layers="1"):
    monkeypatch.setenv("BENCH_DECODE_PLATFORM", "cpu")
    monkeypatch.setenv("DECODE_LAYERS", layers)
    monkeypatch.setenv("DECODE_HEADS", "2")
    monkeypatch.setenv("DECODE_DMODEL", "16")
    monkeypatch.setenv("DECODE_DINNER", "32")
    monkeypatch.setenv("DECODE_VOCAB", "64")
    monkeypatch.setenv("DECODE_PROMPT", "4")
    monkeypatch.setenv("DECODE_BATCH", "2")
    monkeypatch.setenv("DECODE_STEPS", "4,6")  # the ladder, two rungs
    monkeypatch.setenv("DECODE_ROUNDS", "1")
    monkeypatch.setenv("CONT_REQUESTS", "5")
    monkeypatch.setenv("CONT_SLOTS", "2")
    monkeypatch.setenv("CONT_ROUNDS", "1")
    monkeypatch.setenv("CONT_MAXNEW_MIX", "2,5")
    monkeypatch.setenv("DECODE_DRAFT_LAYERS", "1")
    monkeypatch.setenv("SPEC_K", "2")
    monkeypatch.setenv("PREFIX_GROUPS", "2")
    monkeypatch.syspath_prepend(
        __file__.rsplit("/tests/", 1)[0] + "/tools")
    # fresh import so the module-level env reads see the smoke config
    sys.modules.pop("bench_decode", None)


def _run(args):
    import bench_decode

    buf = io.StringIO()
    with redirect_stdout(buf):
        bench_decode.main(args)
    return [json.loads(ln) for ln in buf.getvalue().splitlines()
            if ln.strip()]


def test_bench_decode_smoke(monkeypatch):
    recs = (_smoke_env(monkeypatch), _run([]))[1]
    phases = [r["phase"] for r in recs]
    assert phases == ["decode_ab", "decode_ab", "decode_speedup",
                      "decode_ab", "decode_ab", "decode_speedup",
                      "batch_mode", "batch_mode", "batching_speedup"]

    ab = [r for r in recs if r["phase"] == "decode_ab"]
    assert {r["mode"] for r in ab} == {"kv_cache", "full_forward"}
    # the ladder: one A/B pair per rung, tagged with its own steps
    assert sorted({r["decode_steps"] for r in ab}) == [4, 6]
    for rec in ab:
        _check_schema(rec, _AB_KEYS)
        assert rec["tokens_per_sec"] > 0
        assert rec["batch"] == 2
        assert len(rec["tokens_per_sec_rounds"]) == rec["rounds"] == 1

    for sp in (r for r in recs if r["phase"] == "decode_speedup"):
        _check_schema(sp, _AB_SPEEDUP_KEYS)
        assert sp["speedup"] > 0

    bm = [r for r in recs if r["phase"] == "batch_mode"]
    assert {r["mode"] for r in bm} == {"continuous", "static"}
    for rec in bm:
        _check_schema(rec, _BATCH_KEYS)
        assert rec["tokens_per_sec"] > 0
        assert rec["slots"] == 2 and rec["requests"] == 5

    bs = [r for r in recs if r["phase"] == "batching_speedup"][0]
    _check_schema(bs, _BATCH_SPEEDUP_KEYS)
    assert bs["speedup"] > 0
    # the structural half is noise-free even in a smoke: mixed budgets
    # through continuous admission need no MORE sweeps than the gang
    # schedule
    assert bs["iters_ratio"] >= 1.0


@pytest.mark.slow
def test_bench_decode_lever_arms_smoke(monkeypatch):
    """The PR-14 opt-in arms (--speculative --prefix-share): schema +
    mechanism pins. Marked slow per the tier-1 budget triage — the two
    extra arms compile draft/verify signatures and two more servers
    (~20 s this box); the base smoke above stays in-window."""
    _smoke_env(monkeypatch, layers="2")  # draft (1) < target (2)
    recs = _run(["--speculative", "--prefix-share"])
    phases = [r["phase"] for r in recs]
    assert phases == ["decode_ab", "decode_ab", "decode_speedup",
                      "decode_ab", "decode_ab", "decode_speedup",
                      "spec_ab", "spec_ab", "spec_speedup",
                      "batch_mode", "batch_mode", "batching_speedup",
                      "prefix_ab", "prefix_ab", "prefix_speedup"]

    sab = [r for r in recs if r["phase"] == "spec_ab"]
    assert {r["mode"] for r in sab} == {"speculative", "plain"}
    for rec in sab:
        _check_schema(rec, _SPEC_AB_KEYS)
        assert rec["tokens_per_sec"] > 0
    ss = [r for r in recs if r["phase"] == "spec_speedup"][0]
    _check_schema(ss, _SPEC_SPEEDUP_KEYS)
    assert ss["speedup"] > 0
    # the favorable (tail-zeroed) export makes the draft agree with the
    # target exactly — acceptance is structural here, not luck
    assert ss["acceptance_rate"] == 1.0

    pab = [r for r in recs if r["phase"] == "prefix_ab"]
    assert {r["mode"] for r in pab} == {"shared", "private"}
    for rec in pab:
        _check_schema(rec, _PREFIX_AB_KEYS)
        assert rec["tokens_per_sec"] > 0
    shared = next(r for r in pab if r["mode"] == "shared")
    private = next(r for r in pab if r["mode"] == "private")
    # the mechanism, noise-free: after the warm round every shared-arm
    # prompt is a store hit (ZERO prefills), the private arm pays one
    # prefill batch per admission wave
    assert shared["prefill_executions"] == 0
    assert private["prefill_executions"] > 0
    ps = [r for r in recs if r["phase"] == "prefix_speedup"][0]
    _check_schema(ps, _PREFIX_SPEEDUP_KEYS)
    assert ps["speedup"] > 0
