"""Every device operation under the Fluid op that made it: the tracer's
scope around each op's kernel (`framework/trace.py: trace_op`), the map
an executable gives from its HLO instructions to those scopes and to
the weights they read (`observability/scopes.py`), the registry
`Engine.acquire` fills, and the benchmark's reader of a traced run
(`benchmark/lib/scope_time.py`) on synthetic tuples. CPU; the same
things compiled for a described v5e are in `test_tpu_compile*.py`.
"""
from __future__ import annotations

import contextlib
import hashlib
import os
import re
import sys

import numpy as np
import pytest

import jax

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers, optimizer  # noqa: E402
from paddle_tpu.executor import analyze_state, build_step_fn  # noqa: E402
from paddle_tpu.framework import trace  # noqa: E402
from paddle_tpu.observability import scopes  # noqa: E402
from paddle_tpu.runtime import aot_cache  # noqa: E402

from benchmark.lib import scope_time  # noqa: E402

FEED = {"x": np.linspace(0, 1, 4 * 16).reshape(4, 16).astype(np.float32),
        "y": np.ones((4, 1), np.float32)}


def _program(train=True):
    """x -> fc(lm.l3.ffn.w1) -> layer_norm -> rms_norm (a kernel with a
    `ptpu.` scope of its own) -> fc -> loss, and a `while` that doubles
    a counter's companion three times."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[16])
        y = layers.data(name="y", shape=[1])
        h = layers.fc(x, 32, act="relu",
                      param_attr=fluid.ParamAttr(name="lm.l3.ffn.w1"))
        h = layers.layer_norm(h)
        h = layers.rms_norm(h)
        pred = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="head.w"))
        loss = layers.mean(layers.square(pred - y))
        i = layers.fill_constant(shape=[1], dtype="int64", value=0)
        n = layers.fill_constant(shape=[1], dtype="int64", value=3)
        acc = layers.fill_constant(shape=[1], dtype="float32", value=1.0)
        cond = layers.less_than(i, n)
        loop = layers.While(cond)
        with loop.block():
            layers.assign(layers.scale(acc, scale=2.0), acc)
            layers.increment(i, in_place=True)
            layers.less_than(i, n, cond=cond)
        if train:
            optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss, acc


def _lowered(main, startup, fetch):
    """The step `Executor` would jit, lowered on abstract values."""
    sds = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    step = sds((), np.uint32)
    _, init_out = analyze_state(startup, set())
    _, init = jax.eval_shape(build_step_fn(startup, (), [], init_out),
                             {}, {}, key, step)
    state_in, state_out = analyze_state(main, set(FEED))
    stepfn = build_step_fn(main, tuple(v.name for v in fetch), state_in,
                           state_out)
    feeds = {n: sds(a.shape, a.dtype) for n, a in FEED.items()}
    return jax.jit(stepfn).lower(feeds, {n: init[n] for n in state_in},
                                 key, step), init


@pytest.fixture(scope="module")
def trained():
    """(compiled text, map, startup's state shapes) of the small
    Program's training step, compiled once for the CPU."""
    main, startup, loss, acc = _program()
    lowered, init = _lowered(main, startup, [loss, acc])
    text = lowered.compile().as_text()
    return text, scopes.scope_map(text), init


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


# -- (a) the scopes in a compiled text -----------------------------------------

def test_compiled_text_names_every_op_by_type_and_anchor(trained):
    names = _op_names(trained[0])
    paths = {tuple(scopes.scope_path(n)) for n in names}
    leaves = {p[-1] for p in paths if p}
    # a weight anchors its op; an op without one is anchored by its output
    assert "fl.mul:lm.l3.ffn.w1" in leaves
    assert any(s.startswith("fl.layer_norm:layer_norm_0.") for s in leaves)
    assert any(s.startswith("fl.relu:") for s in leaves)
    assert not any("/" in s for s in leaves)
    # the kernel's own scope nests inside the tracer's
    assert any(len(p) == 2 and p[0].startswith("fl.rms_norm:")
               and p[1] == "ptpu.rms_norm" for p in paths), sorted(paths)
    # an op of the `while`'s sub-block nests under the loop's scope
    assert any(len(p) == 2 and p[0].startswith("fl.while:")
               and p[1].startswith("fl.scale:") for p in paths), sorted(paths)


def test_transposed_ops_of_a_minimised_loss_read_as_backward(trained):
    text, m, _ = trained
    names = _op_names(text)
    assert any("transpose(jvp(fl.mul:head.w))" in n for n in names)
    assert any("/jvp(fl.mul:lm.l3.ffn.w1)/" in n for n in names)
    by_pass = {}
    for o in m["ops"].values():
        for leaf in o["scope"][-1:] or o["members"][:1]:
            by_pass.setdefault(o["pass"], set()).add(leaf.split(":")[0])
    assert "fl.mul" in by_pass["fwd"] and "fl.mul" in by_pass["bwd"]
    # the optimizer's updates are outside the vjp: forward by this rule
    assert "fl.sgd" in by_pass["fwd"] and "fl.sgd" not in by_pass["bwd"]


def test_a_differentiated_op_that_names_its_device_calls_gets_no_scope():
    """`fused_attention`'s Mosaic calls are told forward from backward
    by the transform jax wraps around the OUTERMOST scope under it
    (`jvp(ptpu.flash_fwd)` -> `%jvp_ptpu.flash_fwd_.N` on the chip): no
    scope of the tracer's may come between, where the op is replayed
    under `autodiff`; outside it the scope is there."""
    from paddle_tpu.ops.registry import NAMES_DEVICE_CALLS

    assert "fused_attention" in NAMES_DEVICE_CALLS

    def text_of(train):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            q = layers.data(name="q", shape=[2, 8, 16])
            w = layers.create_parameter([16, 16], "float32", name="att.w")
            qw = layers.matmul(q, w)
            out = layers.fused_attention(qw, qw, qw, causal=True)
            loss = layers.mean(out)
            if train:
                optimizer.SGD(0.1).minimize(loss)
        sds = jax.ShapeDtypeStruct
        state_in, state_out = analyze_state(main, {"q"})
        stepfn = build_step_fn(main, (loss.name,), state_in, state_out)
        state = {n: sds((16, 16) if n == "att.w" else (1,), np.float32)
                 for n in state_in}
        return jax.jit(stepfn).lower(
            {"q": sds((2, 2, 8, 16), np.float32)}, state,
            jax.eval_shape(lambda: jax.random.PRNGKey(0)),
            sds((), np.uint32)).compile().as_text()

    served, train = _op_names(text_of(False)), _op_names(text_of(True))
    assert any("fl.fused_attention:" in n for n in served)
    assert not any("fl.fused_attention:" in n for n in train)
    assert any("jvp(fl.matmul:att.w)" in n for n in train)


# -- (b) the map of a CPU-compiled step ----------------------------------------

def test_scope_map_names_the_weights_an_instruction_holds(trained):
    _, m, init = trained
    assert m["module"] == "jit_stepfn" and m["scoped"] is True
    w1, head = "state['lm.l3.ffn.w1']", "state['head.w']"
    # every state array is a parameter at its own size, the feeds too
    want = {"state['%s']" % n: int(np.prod(a.shape)) * a.dtype.itemsize
            for n, a in init.items()}
    got = {n: b for n, b in m["params"].items() if n.startswith("state[")}
    assert got == {n: want[n] for n in got} and {w1, head} <= set(got)
    assert m["params"]["feeds['x']"] == FEED["x"].nbytes
    # the forward product against each weight reads it, and is found
    # under that weight's scope (its own, or a member of its fusion)
    for weight, leaf in ((w1, "fl.mul:lm.l3.ffn.w1"),
                         (head, "fl.mul:head.w")):
        holders = [o for o in m["ops"].values() if o["pass"] == "fwd"
                   and leaf in o["scope"][-1:] + o["members"]
                   and weight in o["reads"]]
        assert holders, (leaf, m["ops"])
    # a fusion's members are leaves, distinct, at most eight
    for o in m["ops"].values():
        assert len(o["members"]) == len(set(o["members"])) <= 8
        assert all(s.startswith(("fl.", "ptpu.")) for s in o["members"])
    # nothing inside a fusion is an operation of its own
    assert not any(n.startswith("param_") for n in m["ops"])


def test_reads_follow_a_weight_into_a_loop_and_through_a_tuple():
    text = """HloModule jit_f, is_scheduled=true

%fused (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  ROOT %dot.1 = f32[8,8]{1,0} dot(%p0, %p1), metadata={op_name="jit(f)/fl.while:i/fl.mul:w/dot_general"}
}

%body (arg: (s32[], f32[8,8], f32[8,8])) -> (s32[], f32[8,8], f32[8,8]) {
  %arg = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  %gte.1 = f32[8,8]{1,0} get-tuple-element(%arg), index=1
  %gte.2 = f32[8,8]{1,0} get-tuple-element(%arg), index=2
  %fusion.9 = f32[8,8]{1,0} fusion(%gte.1, %gte.2), kind=kOutput, calls=%fused
  ROOT %tuple.2 = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%gte.0, %fusion.9, %gte.2)
}

%cond (arg.1: (s32[], f32[8,8], f32[8,8])) -> pred[] {
  %arg.1 = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (x: f32[8,8], w: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0), metadata={op_name="feeds[\\'x\\']"}
  %w = bf16[8,8]{1,0} parameter(1), metadata={op_name="state[\\'w\\']"}
  %zero = s32[] constant(0)
  %copy.4 = f32[8,8]{1,0} copy(%w)
  %copy-start.6 = (f32[8,8]{1,0}, f32[8,8]{1,0}, u32[]) copy-start(%x)
  %copy-done.6 = f32[8,8]{1,0} copy-done(%copy-start.6)
  %add.7 = f32[8,8]{1,0} add(%copy-done.6, %copy-done.6), metadata={op_name="jit(f)/fl.scale:y/mul"}
  %tuple.1 = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%zero, %x, %copy.4)
  %while.3 = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(f)/fl.while:i/while"}
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%while.3), index=1
}
"""
    m = scopes.scope_map(text)
    assert m["params"] == {"feeds['x']": 256, "state['w']": 128}
    fusion = m["ops"]["fusion.9"]
    assert fusion["members"] == ["fl.mul:w"] and fusion["scope"] == []
    assert fusion["reads"] == ["feeds['x']", "state['w']"]
    assert m["ops"]["copy.4"]["reads"] == ["state['w']"]
    # an instruction the compiler made with no scope is laid under the
    # scoped instruction its result goes to: the prefetch's two halves
    for name in ("copy-start.6", "copy-done.6"):
        assert m["ops"][name]["scope"] == m["ops"][name]["members"] == []
        assert m["ops"][name]["users"] == ["fl.scale:y"]
        assert m["ops"][name]["reads"] == ["feeds['x']"]
    # what an operation moved on the way (the prefetch; the compiler's
    # `copy`, which may leave it in on-chip memory at another width) is
    # `copied` for its reader; what an instruction takes where the
    # parameter lies is not: the copies themselves read it there
    assert m["ops"]["add.7"]["reads"] == m["ops"]["add.7"]["copied"] \
        == ["feeds['x']"]
    assert fusion["copied"] == ["state['w']"]   # `x` rides the loop in place
    assert m["ops"]["copy.4"]["copied"] == \
        m["ops"]["copy-start.6"]["copied"] == []
    assert m["ops"]["copy.4"]["users"] == ["fl.while:i"]
    assert m["ops"]["add.7"]["users"] == []     # it has a scope of its own
    assert m["ops"]["while.3"]["scope"] == ["fl.while:i"]
    assert "dot.1" not in m["ops"] and "gte.1" not in m["ops"]


# -- (c) through the disk tier; a text without scopes ----------------------------

def test_map_of_a_stored_and_loaded_executable_equals_the_cold_ones(
        tmp_path):
    main, startup, loss, acc = _program()
    lowered, _ = _lowered(main, startup, [loss, acc])
    cold = lowered.compile()
    disk = aot_cache.AotDiskCache(cache_dir=str(tmp_path), enabled=True)
    key = disk.key(("scopes-test",))
    assert disk.store(key, cold, meta={})
    warm = disk.load(key)
    assert warm is not None and warm is not cold
    assert scopes.scope_map(warm) == scopes.scope_map(cold)


def test_a_text_without_scopes_says_so(trained, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    main, startup, loss, acc = _program()
    bare = scopes.scope_map(
        _lowered(main, startup, [loss, acc])[0].compile().as_text())
    assert bare["scoped"] is False and trained[1]["scoped"] is True
    # the same instructions, read by nobody
    assert bare["ops"] and all(not o["scope"] for o in bare["ops"].values())
    assert bare["params"] == trained[1]["params"]


# -- (d) the registry ---------------------------------------------------------------

def test_acquisition_registers_a_memory_hit_does_not_nothing_renders_early(
        tmp_path, monkeypatch):
    rendered = []
    real = scopes.scope_map
    monkeypatch.setattr(scopes, "scope_map",
                        lambda c: rendered.append(c) or real(c))
    scopes.reset()
    main, startup, loss, _ = _program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe._disk = aot_cache.AotDiskCache(cache_dir=str(tmp_path),
                                           enabled=True)
        exe.run(startup)
        exe.run(main, feed=FEED, fetch_list=[loss])
        held = dict(scopes._EXECUTABLES)
        assert len(held) == 2 and all(n.startswith("run/") for n in held)
        exe.run(main, feed=FEED, fetch_list=[loss])    # a memory hit
    assert {n: id(e) for n, e in scopes._EXECUTABLES.items()} == {
        n: id(e) for n, e in held.items()}
    # a dict insert an acquisition: no text was rendered for it
    assert rendered == [] and all(e[1] is None for e in held.values())
    name = "run/" + fluid.observability.program_fp(main)
    got = scopes.maps([name])
    assert list(got) == [name] and len(rendered) == 1
    assert got[name]["module"] == "jit_stepfn" and got[name]["scoped"]
    # asked again it is the kept one; the others render at their asking
    assert scopes.maps([name])[name] is got[name] and len(rendered) == 1
    assert len(scopes.maps()) == 2 and len(rendered) == 2
    scopes.reset()
    assert scopes.maps() == {}


def test_the_registry_keeps_the_newest_names(monkeypatch):
    monkeypatch.setattr(scopes, "_KEEP", 3)
    scopes.reset()
    for i in range(5):
        scopes.register("exe_%d" % i, "HloModule m%d\n" % i)
    scopes.register("exe_2", "HloModule again\n")
    assert list(scopes._EXECUTABLES) == ["exe_3", "exe_4", "exe_2"]
    assert scopes.maps(["exe_2"])["exe_2"]["module"] == "again"
    scopes.reset()


# -- (e) metadata only ----------------------------------------------------------------

def test_lowered_text_without_locations_is_the_same_without_the_scope(
        monkeypatch):
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    from lowered_hashes import without_locations

    def digest():
        main, startup, loss, acc = _program()
        text = _lowered(main, startup, [loss, acc])[0].as_text()
        return hashlib.sha256(without_locations(text).encode()).hexdigest()

    scoped = digest()
    monkeypatch.setattr(trace, "op_scope", lambda op, block: "x")
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert digest() == scoped


# -- the benchmark's reader, on synthetic tuples ---------------------------------------

MS = 1e6


def _op(scope=(), members=(), reads=(), users=()):
    return {"scope": list(scope), "pass": "fwd", "members": list(members),
            "users": list(users), "reads": list(reads)}


def test_reader_tells_programs_apart_leaves_containers_out_counts_once():
    w1, w2 = "state['l0.w1']", "state['l0.w2']"
    maps = {
        "decode": {"module": "jit_ptpu_decode_b8_s64", "scoped": True,
                   "params": {w1: 1000, w2: 3000},
                   "ops": {"fusion.1": _op(["fl.mul:l0.w1"], reads=[w1]),
                           "fusion.5": _op(["fl.mul:l0.w1"], reads=[w1]),
                           "fusion.2": _op(["fl.relu:t_0"],
                                           ["fl.relu:t_0", "fl.mul:l0.w2"],
                                           [w1, w2]),
                           "while.4": _op(["fl.while:t_1"]),
                           "slice-done.3": _op(users=["fl.mul:l0.w2"],
                                               reads=[w2])}},
        "prefill": {"module": "jit_ptpu_prefill_b1_s64", "scoped": True,
                    "params": {w1: 1000},
                    "ops": {"fusion.1": _op(["fl.softmax:t_3"])}}}
    modules = [("jit_ptpu_decode_b8_s64(1)", 0.0, 10 * MS),
               ("jit_ptpu_prefill_b1_s64(2)", 10 * MS, 10 * MS)]
    ops = [("fusion.1", 0.0, 2 * MS, ""), ("fusion.2", 2 * MS, 2 * MS, ""),
           ("while.4", 4 * MS, 6 * MS, ""), ("fusion.5", 5 * MS, 1 * MS, ""),
           ("slice-done.3", 6 * MS, 1 * MS, ""),
           ("fusion.1", 10 * MS, 8 * MS, ""), ("copy.7", 18 * MS, MS, "")]
    joined = scope_time.join(ops, modules, maps)
    table = scope_time.reduce_events(ops, joined)
    rows = {p["program"]: p for p in table["programs"]}
    # one instruction name, two programs, two different ops (PERF.md 7 o)
    # the wait for w2's prefetch goes under the product it is for
    assert dict(rows["jit_ptpu_decode_b8_s64"]["classes"]) == {
        "fl.mul:l*.w*": pytest.approx(0.004),
        "fl.relu:t_*": pytest.approx(0.002)}
    assert dict(rows["jit_ptpu_prefill_b1_s64"]["classes"]) == {
        "fl.softmax:t_*": pytest.approx(0.008)}
    assert rows["jit_ptpu_prefill_b1_s64"]["unnamed_s"] == pytest.approx(.001)
    # the loop's own event is in no sum: its body's events are
    assert rows["jit_ptpu_decode_b8_s64"]["op_s"] == pytest.approx(0.006)
    assert table["named_s"] / table["mapped_s"] == pytest.approx(14 / 15)
    # two dense events of a step stream w1: its bytes count once; the
    # wait for a prefetch is on neither side of the share
    out = scope_time.dense_roofline(joined, "jit_ptpu_decode_", 1e6)
    assert out["bytes"] == 4000 and out["seconds"] == pytest.approx(0.005)
    assert out["pct"] == pytest.approx(100 * 4000 / 1e6 / 0.005)
    assert scope_time.is_dense_wait(maps["decode"]["ops"]["slice-done.3"])
    # a weight that reaches an event only through a copy is read from
    # HBM by the copy, not by the event: neither its bytes nor, where
    # the event streams nothing itself, its time
    maps["decode"]["ops"]["fusion.2"]["copied"] = [w2]
    out = scope_time.dense_roofline(joined, "jit_ptpu_decode_", 1e6)
    assert out["bytes"] == 1000 and out["seconds"] == pytest.approx(0.003)
    # and only the weights its products are AGAINST are an event's bytes
    # (w1 rides into `fusion.2` for another purpose: eight rows of a table)
    assert scope_time.dense_weights(
        maps["decode"]["ops"]["fusion.2"]) == {w2}
