"""Compile the main path's training steps for a DESCRIBED TPU v5e at the
widths the chip runs them at (chip_smoke.py's model: d_model 1024, 8
heads of 128, vocab 32768, seq 1024), on one chip and over a 2x2 mesh —
the chip's own compiler, no chip attached. What it refuses here (a block
Mosaic cannot tile, a kernel over its VMEM, a Mosaic call GSPMD cannot
partition, a step that does not fit 16 GB) costs no chip time. Nothing
runs, so nothing here says anything about results or speed.

The kernels alone are compiled beside this file, in
`test_tpu_compile_kernels.py`, and the serving steps in
`test_tpu_compile_serving.py` (OPT and the hybrid) and
`test_tpu_compile_cells.py` (Laguna and Phi-4-mini-flash);
`tpu_compile_lib.py` holds the described chip and says who may load the
TPU library. Dispatch code sees the CPU here, so the whole-step cases
steer it in the test (PADDLE_TPU_FORCE_PALLAS; in the serving files a
patch of `_use_pallas_decode` too) and every case asserts
`tpu_custom_call` in the compiled text: a case that fell to the XLA path
fails instead of passing empty.
"""
from __future__ import annotations

import re

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from tpu_compile_lib import (B, D_INNER, D_MODEL, T, VOCAB, _compile,
                             chip_smoke)
from tpu_compile_lib import one_chip, topo  # noqa: F401  (fixtures)


def _lm_programs(n_layer, tie=False):
    """chip_smoke.py's own training program, cut to `n_layer`."""
    return chip_smoke._build_lm(dict(chip_smoke.FULL, n_layer=n_layer),
                                tie_embeddings=tie)


def _train_step_avals(main_p, startup, loss, place_state, place_other):
    """(stepfn, avals) of the training step, state shapes taken from an
    abstract evaluation of the startup program — nothing is allocated.
    `place_state(name, aval)` / `place_other(aval)` attach shardings."""
    from paddle_tpu.executor import analyze_state, build_step_fn

    sds = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    step = sds((), np.uint32)
    _, init_out = analyze_state(startup, set())
    _, init = jax.eval_shape(build_step_fn(startup, (), [], init_out),
                             {}, {}, key, step)
    feed_names = ("ids", "labels")
    state_in, state_out = analyze_state(main_p, set(feed_names))
    stepfn = build_step_fn(main_p, (loss.name,), state_in, state_out)
    feeds = {n: place_other(sds((B, T), np.int32), batch=True)
             for n in feed_names}
    state = {n: place_state(n, init[n]) for n in state_in}
    return stepfn, (feeds, state, place_other(key), place_other(step))


_ONE_CHIP_TEXTS = {}  # tie -> the compiled step's text: one compile a case


def _one_chip_step_text(one_chip, monkeypatch, tie):
    """The compiled text of the LM training step on one described chip,
    2 layers at full width, AMP O2, fused head; the backward is whatever
    the shape gets with no option set (the fused one: the cell's
    program IS the default)."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    if tie not in _ONE_CHIP_TEXTS:
        main_p, startup, loss = _lm_programs(2, tie=tie)

        def on_chip(aval, batch=False):
            return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                        sharding=one_chip)

        stepfn, avals = _train_step_avals(
            main_p, startup, loss, lambda n, a: on_chip(a), on_chip)
        _ONE_CHIP_TEXTS[tie] = _compile(stepfn, *avals, donate_argnums=(1,))
    return _ONE_CHIP_TEXTS[tie]


@pytest.mark.parametrize("tie", [False, True], ids=["untied", "tied"])
def test_training_step_compiles(one_chip, monkeypatch, tie):
    """The LM training step, 2 layers at full width, AMP O2, fused
    backward, fused head — as chip_smoke.py trains it at 12 layers."""
    text = _one_chip_step_text(one_chip, monkeypatch, tie)
    # per layer: flash fwd + fused bwd
    assert text.count("tpu_custom_call") >= 2 * 2


def test_training_step_names_its_mosaic_calls_and_scopes_the_rest(
        one_chip, monkeypatch):
    """The tracer's scope around every Fluid op renames no Mosaic call:
    the forward kernel is `jvp_ptpu.flash_fwd_.N` and the fused backward
    `transpose_jvp_ptpu.flash_bwd_.N` as before it (the names
    `flash_attn_roofline.lm` anchors on: `fused_attention` is traced
    without a scope where it is differentiated), and the step's map
    lays the rest under `fl.<type>:<anchor>`, forward and backward, with
    the weights each fusion reads."""
    from paddle_tpu.observability import scopes

    text = _one_chip_step_text(one_chip, monkeypatch, False)
    calls = re.findall(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    fwd = [c for c in calls if re.match(r"jvp_ptpu\.flash_fwd_(\.\d+)?$", c)]
    bwd = [c for c in calls
           if re.match(r"transpose_jvp_ptpu\.flash_bwd\w*(\.\d+)?$", c)]
    assert len(fwd) == 2 and len(bwd) == 2 and len(calls) == 4, calls
    m = scopes.scope_map(text)
    assert m["scoped"] and not any("fl.fused_attention" in s
                                   for o in m["ops"].values()
                                   for s in o["scope"] + o["members"])
    named = [o for o in m["ops"].values() if o["scope"] or o["members"]]
    assert {o["pass"] for o in named} == {"fwd", "bwd"}
    # a layer's first FFN weight: read by a forward product under its
    # own scope, and written by the optimizer's update of it
    w = "state['lm.l0.ffn.fc1.w']"
    assert m["params"][w] == D_MODEL * D_INNER * 4
    leaf = "fl.mul:lm.l0.ffn.fc1.w"
    assert any(w in o["reads"] and leaf in o["scope"][-1:] + o["members"]
               for o in named if o["pass"] == "fwd")
    assert any(w in o["reads"] and any(
        s.startswith("fl.adam:lm.l0.ffn.fc1.w") for s in
        o["scope"][-1:] + o["members"]) for o in named), [
        o for o in named if w in o["reads"]]


@pytest.mark.parametrize("tie", [False, True], ids=["untied", "tied"])
def test_training_step_compiles_on_2x2_mesh(topo, monkeypatch, tie):
    """The same step as one program over four chips: batch over dp, heads
    over mp (megatron plan). Mosaic kernels cannot be partitioned by
    GSPMD; `fused_attention` shard_maps them under the trace mesh, and
    the fused head its chunk loops, a vocabulary slice an mp rank: no
    collective lies inside a loop body and nothing gathers the head's
    (tied: the token table's) whole weight."""
    from hlo_text import collectives, while_bodies
    from paddle_tpu.framework import trace as trace_mod
    from paddle_tpu.parallel import megatron_transformer_plan

    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    plan = megatron_transformer_plan(mesh, tied=tie)
    main_p, startup, loss = _lm_programs(2, tie=tie)
    sds = jax.ShapeDtypeStruct

    def place_state(name, aval):
        return sds(aval.shape, aval.dtype,
                   sharding=plan.sharding(name, shape=aval.shape))

    def place_other(aval, batch=False):
        sh = (plan.feed_sharding(len(aval.shape)) if batch
              else plan.replicated())
        return sds(aval.shape, aval.dtype, sharding=sh)

    with trace_mod.mesh_context(mesh, plan):
        stepfn, avals = _train_step_avals(main_p, startup, loss,
                                          place_state, place_other)
        text = _compile(stepfn, *avals, donate_argnums=(1,))
    # per layer a flash forward and, with no option set, the FUSED
    # backward: a shard's shape fits (`_fused_bwd_fits`)
    calls = re.findall(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 2 * 2, calls
    assert len([c for c in calls if "flash_bwd" in c
                and "_dq" not in c and "_dkv" not in c]) == 2, calls
    assert "all-reduce" in text
    assert len(while_bodies(text)) >= 2  # the head's two chunk loops
    found = collectives(text)
    assert not [c for c in found if c[3] is not None]
    whole = ("[%d,%d]" % (VOCAB, D_MODEL), "[%d,%d]" % (D_MODEL, VOCAB))
    assert not [c for c in found if c[0] == "all-gather"
                and any(w in c[1] for w in whole)]
    # one dp rank owns each matrix's update: fc1.w is (1024, 4096) f32, a
    # quarter of it a device (rows over dp, columns over mp)
    fc1 = next(n for n in avals[1] if n.endswith(".fc1.w"))
    assert avals[1][fc1].sharding.shard_shape(avals[1][fc1].shape) == (
        D_MODEL // 2, D_INNER // 2)
    # so no dp pair all-reduces a layer matrix's gradient (an mp rank's
    # slice of it, any type): each is reduce-scattered, by a fusion that
    # calls an `all-reduce-scatter` (whose own inner all-reduce does not
    # count), and the weights come back by all-gathers of which the
    # compiler runs some asynchronously, under the matmuls
    ids = np.array([[d.id for d in row] for row in mesh.devices])
    dp_pairs = frozenset(frozenset(int(i) for i in ids[:, j])
                         for j in range(2))
    grads = ["[%d,%d]" % s for s in (
        (D_MODEL, D_MODEL // 2), (D_MODEL // 2, D_MODEL),
        (D_MODEL, D_INNER // 2), (D_INNER // 2, D_MODEL))]
    assert not [c for c in found
                if c[0] == "all-reduce" and c[4] == dp_pairs
                and not c[2].startswith("all-reduce-scatter")
                and any(g in c[1] for g in grads)]
    assert re.search(r"calls=%all-reduce-scatter", text)
    assert [c for c in found if c[0] == "all-gather"
            and "async_collective_fusion" in c[2]]
