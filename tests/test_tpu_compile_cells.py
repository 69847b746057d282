"""Compile the serving steps of the Laguna, Phi-4-mini-flash,
Mistral-Small-4 and Ling-3.0-flash cells for a DESCRIBED TPU v5e
(`tpu_compile_lib.py`): the cells' own programs, from their own
configuration files (the dots3-note-prev pair is in
`test_tpu_compile_serving.py`: no file of the four over 240 s). See `test_tpu_compile.py` for what such a
compile can and cannot say.
"""
from __future__ import annotations

import importlib
import json
import os
import re
import sys

import numpy as np
import pytest

import jax

from paddle_tpu.ops import kv_cache as KV

from tpu_compile_lib import (HBM_BYTES, REPO, _serving_step,
                             _whole_slab_ops)
from tpu_compile_lib import one_chip, topo  # noqa: F401  (fixtures)


def _cell_predictor(model, config, monkeypatch):
    """A graph-builder-only DecodePredictor of `benchmark/models/<model>`
    under `benchmark/configs/<config>`, steered to the Pallas paths."""
    from paddle_tpu.serving.decode import DecodePredictor

    models = importlib.import_module("benchmark.models." + model)
    with open(os.path.join(REPO, "benchmark", "configs", config)) as f:
        cfg = json.load(f)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    monkeypatch.setattr(
        KV, "_use_pallas_decode",
        lambda s, d: d % 128 == 0 and s % 128 == 0 and s >= 128)
    pred = DecodePredictor.__new__(DecodePredictor)  # graph builder only
    pred.config = models.decode_config(cfg, "serve")
    pred.sample_k, pred.sample_p, pred.temperature = 40, 0.9, 1.0
    return pred


_CALL = re.compile(r"%((?:ptpu\.flash_fwd|ptpu\.attn_window)[\w.]*) = [^\n]*?"
                   r'custom_call_target="tpu_custom_call", '
                   r"operand_layout_constraints="
                   r"\{((?:[^{}]|\{[^{}]*\})*)\}")


def _prefill_attention_calls(text):
    """[(call's name, [its operands' types, in order])] of the flash
    forward calls of a compiled prefill, `ptpu.flash_fwd` and
    `ptpu.attn_window`."""
    return [(name, re.findall(r"(\w+\[[\d,]*\])", operands))
            for name, operands in _CALL.findall(text)]


def _assert_bfloat16_operands_and_lengths(text, batch, v_width=None):
    """Every flash forward of a serving prefill is handed the rows'
    lengths (scalar-prefetched: the first operand) and q, k and v in
    bfloat16 (`ops/attention.py: prefill_attention`); with `v_width`,
    v as (batch, T, v_width) where q and k are wider. A call's float32
    operands (the heads' sinks, prefetched beside the lengths; a band's
    bias, last) are left out of what is returned: [(name, [lengths, q,
    k, v])]."""
    calls = [(name, [o for o in operands if not o.startswith("f32[")])
             for name, operands in _prefill_attention_calls(text)]
    assert calls
    for name, operands in calls:
        assert operands[0] == "s32[%d]" % batch, (name, operands)
        assert len(operands) == 4 and all(
            o.startswith("bf16[%d," % batch) for o in operands[1:]), (
                name, operands)
        if v_width:
            assert operands[3].endswith(",%d]" % v_width), (name, operands)
            assert operands[1] == operands[2] != operands[3], (name,
                                                               operands)
    return calls


_LAGUNA_CASES = [
    # id, kind, batch, seq: the Laguna serving cell's own programs
    # (benchmark/configs/laguna-xs.2.json: 5 layers at published widths,
    # 64 of 256 experts held, 64 slots of 4096 positions)
    ("decode-64x4096", "decode", 64, 4096),
    ("prefill-4x4096", "prefill", 4, 4096),
]


@pytest.mark.slow  # two all-core compiles of a minute; `pytest <this file>`
@pytest.mark.parametrize("kind,batch,seq", [c[1:] for c in _LAGUNA_CASES],
                         ids=[c[0] for c in _LAGUNA_CASES])
def test_laguna_serving_step_compiles(one_chip, monkeypatch, kind, batch,
                                      seq):
    """The programs DecodePredictor builds for the Laguna cell (full and
    sliding layers of 48 / 64 query heads on 8 K/V heads of 128, rotary
    positions, a dense layer and four of 64 held experts of 256 with a
    shared one, an untied head over 100,352 ids): they compile for a v5e
    and fit it. The decode step donates every slab and ring and gets
    each back in place, with no whole-slab copy, and attends each slab
    through the in-place kernel (grouped queries); the largest admission
    (4 prompts of 4096: the token bound) holds one attention kernel a
    layer (three `ptpu.attn_window`, two flash forwards), in every
    sparse layer the grouped product as the TPU compiler's own ragged
    dots (three, and their metadata) inside the loop over blocks of
    sorted pairs, and temporaries that leave room for the weights and
    64 slots beside it."""
    pred = _cell_predictor("laguna_lm", "laguna-xs.2.json", monkeypatch)
    step_fn, feeds, state, n_cache = _serving_step(pred, kind, batch, seq,
                                                   one_chip)
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    weights = sum(int(np.prod(s.shape)) * 4 for s in state.values())
    assert 5.7e9 < weights < 5.9e9, weights  # 1.454 B parameters
    text = compiled.as_text()
    if kind == "prefill":
        calls = re.findall(r"%([\w.-]+?)(?:\.\d+)? = [^\n]*"
                           r'custom_call_target="tpu_custom_call"', text)
        assert sorted(set(calls)) == [
            "ptpu.attn_window", "ptpu.flash_fwd", "ragged-dot-metadata",
            "ragged-dot-none"], sorted(set(calls))
        assert calls.count("ptpu.attn_window") == 3
        assert calls.count("ptpu.flash_fwd") == 2
        assert len(_assert_bfloat16_operands_and_lengths(text, batch)) == 5
        assert calls.count("ragged-dot-none") == 3 * 4
        assert text.count(" while(") >= 4           # a loop a sparse layer
        slabs = sum(e.nbytes for e in pred.cache_spec(64, 4096))
        assert weights + slabs + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes) < 15.5 * 2**30, mem
        return
    # 8 K/V heads of float32: a full layer's slabs have the free view,
    # so its attention is one call of the in-place kernel on the slabs
    # themselves (the rings keep the lax path); the other Mosaic calls
    # are the compiler's own ragged dots
    calls = re.findall(r"%(ptpu\.[\w.]+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    assert calls == ["ptpu.decode_attn_grouped"] * 2, calls
    assert text.count("ragged-dot-none") >= 3 * 4
    spec = pred.cache_spec(batch, seq)
    assert n_cache == len(spec) == 10
    assert mem.alias_size_in_bytes >= sum(e.nbytes for e in spec)
    for shape in {e.shape for e in spec}:
        copies = [name for op, name, _ in _whole_slab_ops(text, shape)
                  if op == "copy"]
        assert not copies, (shape, copies)
    assert mem.temp_size_in_bytes < 1.5 * 2**30, mem.temp_size_in_bytes


_PHI4FLASH_CASES = [
    # id, kind, batch, seq: the Phi-4-mini-flash serving cell's own
    # programs (benchmark/configs/phi4-mini-flash.json: 16 layers at
    # published widths, 64 slots of 4096 positions; the largest
    # admission is 8 prompts of the 1024 bucket)
    ("decode-64x4096", "decode", 64, 4096),
    ("prefill-8x1024", "prefill", 8, 1024),
]


@pytest.mark.parametrize("kind,batch,seq",
                         [c[1:] for c in _PHI4FLASH_CASES],
                         ids=[c[0] for c in _PHI4FLASH_CASES])
def test_phi4flash_serving_step_compiles(one_chip, monkeypatch, kind, batch,
                                         seq):
    """The programs DecodePredictor builds for the Phi-4-mini-flash cell
    (Mamba and sliding layers, the memory's Mamba, ONE full layer, gated
    memory units and cross layers; differential attention over 40 query
    heads on 20 key/value heads of 64): they compile for a v5e and fit
    it beside each other. The decode step donates the one slab, the
    four rings and the five states and gets each back in place: flat
    rows, so NO whole-slab copy or relayout for the one-row append (a
    4-D slab of 10 pair-heads cost four 1.25 GiB copies a step), and a
    few tens of MB of temporaries; no Mosaic call (the lax paths). The
    largest admission holds one attention kernel a layer that owns keys
    (four `ptpu.attn_window`, one flash forward: the cross layers run
    one query row a prompt) and five scans."""
    pred = _cell_predictor("phi4flash_lm", "phi4-mini-flash.json", monkeypatch)
    step_fn, feeds, state, n_cache = _serving_step(pred, kind, batch, seq,
                                                   one_chip)
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    weights = sum(int(np.prod(s.shape)) * 4 for s in state.values())
    assert 8.7e9 < weights < 8.85e9, weights  # 2.193 B parameters
    text = compiled.as_text()
    slabs = sum(e.nbytes for e in pred.cache_spec(64, 4096))
    if kind == "prefill":
        calls = re.findall(r"%([\w.-]+?)(?:\.\d+)? = [^\n]*"
                           r'custom_call_target="tpu_custom_call"', text)
        assert calls.count("ptpu.attn_window") == 4, calls
        assert calls.count("ptpu.flash_fwd") == 1, calls
        assert len(_assert_bfloat16_operands_and_lengths(text, batch)) == 5
        # a prefill's cross layers: one query row a prompt on its rows
        assert calls.count("ptpu.diff_attn_rows") == 3, calls
        assert text.count(" while(") >= 5           # a scan a Mamba layer
        assert weights + slabs + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes) < 15.5 * 2**30, mem
        return
    # the full layer and the three cross layers attend the ONE slab
    # through the kernel over flat rows; the rings keep the lax path
    calls = re.findall(r"%(ptpu\.[\w.]+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    assert calls == ["ptpu.diff_attn_rows"] * 4, calls
    spec = pred.cache_spec(batch, seq)
    assert n_cache == len(spec) == 20
    assert mem.alias_size_in_bytes >= sum(e.nbytes for e in spec)
    big = {e.shape for e in spec if e.nbytes > 2**27}
    assert big == {(64, 4096, 1280), (64, 512, 1280)}  # the slab, the rings
    for shape in big:
        moved = [name for op, name, changed in _whole_slab_ops(text, shape)
                 if op == "copy" or changed]
        assert not moved, (shape, moved)
    assert mem.temp_size_in_bytes < 200 * 2**20, mem.temp_size_in_bytes


_MISTRAL4_CASES = [
    # id, kind, batch, seq: the Mistral-Small-4 serving cell's own
    # programs (benchmark/configs/mistral-small-4.json: 4 layers at
    # published widths, 16 of 128 experts held, 32 slots of 16,384
    # positions; the largest admission is one prompt of the 16,384 bucket)
    ("decode-32x16384", "decode", 32, 16384),
    ("prefill-1x16384", "prefill", 1, 16384),
]


@pytest.mark.parametrize("kind,batch,seq",
                         [c[1:] for c in _MISTRAL4_CASES],
                         ids=[c[0] for c in _MISTRAL4_CASES])
def test_mistral4_serving_step_compiles(one_chip, monkeypatch, kind, batch,
                                        seq):
    """The programs DecodePredictor builds for the Mistral-Small-4 cell
    (latent attention: 32 heads of 64 + 64 query/key and 128 value
    channels over a latent row of 320 floats; a softmax router over 128
    experts of width 2048, 16 held, and a shared one; an untied head
    over 16,384 ids): they compile for a v5e and fit it beside each
    other. The compiler lays the 320-wide row out with the SEQUENCE
    minor ({1,2,0}: 320 is no multiple of 128 lanes, so no padding to
    384), and the decode step donates the four latent slabs and gets
    each back in place in that one layout: no whole-slab copy or
    relayout for the one-row append (a scatter cost two a layer a
    step) nor for the absorbed attention, whose kernel
    (`ptpu.mla_latent_attn`, one call a layer) is handed the slab's
    TRANSPOSED view, a bitcast of it, and is found by the benchmark's
    reader of "an event that reads a latent slab"; no K or V of 32
    heads anywhere (no array of slots x 16,384 x 32 heads). The largest
    admission holds one flash forward a layer on bfloat16 operands and
    the row's length, whose resident K and V of 16,384 rows need the
    raised scoped VMEM."""
    pred = _cell_predictor("mistral4_lm", "mistral-small-4.json",
                           monkeypatch)
    step_fn, feeds, state, n_cache = _serving_step(pred, kind, batch, seq,
                                                   one_chip)
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    weights = sum(int(np.prod(s.shape)) * 4 for s in state.values())
    assert 7.8e9 < weights < 7.9e9, weights  # 1.960 B parameters
    text = compiled.as_text()
    slabs = sum(e.nbytes for e in pred.cache_spec(32, 16384))
    assert round(slabs / 1e9, 2) == 2.68
    calls = re.findall(r"%([\w.-]+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    if kind == "prefill":
        assert calls.count("ptpu.flash_fwd") == 4, calls
        # 32 heads of 128 / 128 / 128: nothing padded
        assert _assert_bfloat16_operands_and_lengths(text, batch)[0][1][
            1:] == ["bf16[1,16384,4096]"] * 3
        assert calls.count("ragged-dot-none") == 3 * 4
        assert weights + slabs + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes) < 15.5 * 2**30, mem
        return
    assert [c for c in calls if c.startswith("ptpu.")] == [
        "ptpu.mla_latent_attn"] * 4, calls
    mla_cost = importlib.import_module("benchmark.lib.mla_cost")
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mistral-small-4.json")) as f:
        pats = mla_cost.patterns(json.load(f))["slab"]
    kernel_lines = [ln for ln in text.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in ln
                    and "%ptpu." in ln.split(" = ")[0]]
    assert len(kernel_lines) == 4
    for ln in kernel_lines:
        assert any(p in ln for p in pats), ln[:300]
        # the slab's transposed view, row-major: the same bytes, and
        # since PR 55 the call's ONE streamed operand (a slot's summed
        # rows wait in VMEM between the passes: no operand for V)
        assert ln.count("f32[32,320,16384]{2,1,0") == 1, ln[:600]
    spec = pred.cache_spec(batch, seq)
    assert n_cache == len(spec) == 4
    assert mem.alias_size_in_bytes >= sum(e.nbytes for e in spec)
    (shape,) = {e.shape for e in spec}
    assert shape == (32, 16384, 320)
    ops = _whole_slab_ops(text, shape)
    # the kernel's operand is a BITCAST of the slab (other dimensions
    # and order, the same bytes: no instruction runs for it); anything
    # else that changes a slab's layout, and any copy, moves 671 MB
    assert [op for op, _, _ in ops].count("bitcast") == 4
    moved = [name for op, name, changed in ops
             if op == "copy" or (changed and op != "bitcast")]
    assert not moved, moved
    layouts = set(re.findall(r"f32\[32,16384,320\]\{([\d,]+)", text))
    assert layouts == {"1,2,0"}, layouts
    # no expanded K or V: nothing of slots x positions x heads
    assert "f32[32,16384,32," not in text
    assert mem.temp_size_in_bytes < 300 * 2**20, mem.temp_size_in_bytes


_LING3_CASES = [
    # id, kind, batch, seq: the Ling-3.0-flash serving cell's own
    # programs (benchmark/configs/ling-3.0-flash.json: 6 layers at
    # published widths, 64 of 512 experts held, 64 slots of 16,384
    # positions; the largest admission is one prompt of the 16,384 bucket)
    ("decode-64x16384", "decode", 64, 16384),
    ("prefill-1x16384", "prefill", 1, 16384),
]


@pytest.mark.parametrize("kind,batch,seq", [c[1:] for c in _LING3_CASES],
                         ids=[c[0] for c in _LING3_CASES])
def test_ling3_serving_step_compiles(one_chip, monkeypatch, kind, batch,
                                     seq):
    """The programs DecodePredictor builds for the Ling-3.0-flash cell
    (five KDA layers: 32 heads of a 128 x 128 state, three windows; one
    latent layer: 32 heads of 128 + 64 query/key and 128 value channels
    over a latent row of 576 floats, no query bottleneck; a sigmoid
    router with a bias over 512 experts of width 768 in 8 groups, 64
    held; an untied head over 19,648 ids): they compile for a v5e and
    fit it beside each other. What the compiler chooses for the 576-wide
    row is READ here: the sequence minor ({1,2,0}), as for Mistral's 320
    (576 is no multiple of 128 lanes either), so the absorbed kernel's
    transposed view is a bitcast and the step holds no copy of the
    slab; the five matrix states are donated and each comes back from
    ONE call of the step's kernel (`ptpu.kda_step`, since PR 48: operand
    and result `f32[64,32,128,128]`, the result aliased to the operand),
    in the layout it came in, with no copy of that shape and temporaries
    no larger than the lax form's; the counter reads five `kernel`
    traces. The largest admission holds
    one flash forward (the latent layer's: bfloat16 q and k at heads
    padded to 256, v at its own 128, the row's length) and the chunked
    scans' kernels."""
    from paddle_tpu import observability as obs

    def traces():
        got = {k["path"]: v for k, v in obs.KDA_STEP_TRACES.samples()}
        return got.get("kernel", 0), got.get("lax", 0)

    pred = _cell_predictor("ling3_lm", "ling-3.0-flash.json", monkeypatch)
    k0, l0 = traces()
    step_fn, feeds, state, n_cache = _serving_step(pred, kind, batch, seq,
                                                   one_chip)
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    weights = sum(int(np.prod(s.shape)) * 4 for s in state.values())
    assert 8.1e9 < weights < 8.13e9, weights  # 2.029 B parameters
    text = compiled.as_text()
    spec = pred.cache_spec(64, 16384)
    slabs = sum(e.nbytes for e in spec)
    assert round(slabs / 1e9, 2) == 3.13
    calls = re.findall(r"%([\w.-]+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    if kind == "prefill":
        assert calls.count("ptpu.flash_fwd") == 1, calls
        assert calls.count("ragged-dot-none") == 3 * 4
        # q and k padded to 32 heads of 256, v at its own 128
        assert _assert_bfloat16_operands_and_lengths(
            text, batch, v_width=32 * 128)[0][1][1:] == [
                "bf16[1,16384,8192]"] * 2 + ["bf16[1,16384,4096]"]
        # beside the weights, the slabs and states and the step
        assert weights + slabs + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes) < 15.5 * 2**30, mem
        assert mem.temp_size_in_bytes < 3.5 * 2**30, mem.temp_size_in_bytes
        return
    assert sorted(c for c in calls if c.startswith("ptpu.")) == [
        "ptpu.kda_step"] * 5 + ["ptpu.mla_latent_attn"], calls
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln
               and "%ptpu." in ln.split(" = ")[0]]
    (kernel,) = [ln for ln in kernels if "%ptpu.mla_latent_attn" in ln]
    # a step's kernel takes a layer's matrix states and gives them back
    # at the shape the benchmark's readers tell them by, over themselves
    steps = [ln for ln in kernels if "%ptpu.kda_step" in ln]
    for ln in steps:
        assert ln.count("f32[64,32,128,128]{3,2,1,0") >= 2, ln[:600]
        assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(0, \{\}\)\}",
                         ln), ln[:900]
    assert traces() == (k0 + 5, l0)
    # the slab's transposed view, row-major: the same bytes, the call's
    # ONE streamed operand (PR 55: read once)
    assert kernel.count("f32[64,576,16384]{2,1,0}") == 1, kernel[:600]
    assert n_cache == len(spec) == 21
    assert mem.alias_size_in_bytes >= slabs
    ops = _whole_slab_ops(text, (64, 16384, 576))
    assert [op for op, _, _ in ops].count("bitcast") == 1
    moved = [name for op, name, changed in ops
             if op == "copy" or (changed and op != "bitcast")]
    assert not moved, moved
    assert set(re.findall(r"f32\[64,16384,576\]\{([\d,]+)", text)) == {
        "1,2,0"}
    # a matrix state is written by one call a layer (an element of its
    # result tuple), never copied
    ops = _whole_slab_ops(text, (64, 32, 128, 128))
    assert sorted(op for op, _, _ in ops) == ["get-tuple-element"] * 5 + [
        "parameter"] * 5, ops
    assert not [name for _, name, changed in ops if changed]
    assert set(re.findall(r"f32\[64,32,128,128\]\{([\d,]+)", text)) == {
        "3,2,1,0"}
    # no expanded K or V: nothing of slots x positions x heads
    assert "f32[64,16384,32," not in text
    # no more than with the lax step (63,418,880 at PR 47; 43,335,680)
    assert mem.temp_size_in_bytes <= 63418880, mem.temp_size_in_bytes


# sha256 (16 digits) of the lowered text, Mosaic bodies written without
# their Python locations (`tools/lowered_hashes.py: without_locations`),
# of the gradient of `fused_attention`'s differentiable path at (2, 1024,
# 8 heads of 128), READ ON THE PARENT OF PR 45 (62c9651): what the
# training graphs hand the chip
_TRAINING_ATTENTION = [("bfloat16", "282865ad66cd6e75"),
                       ("float32", "9a0f4c0c6189ed47")]


@pytest.mark.parametrize("dtype,want", _TRAINING_ATTENTION,
                         ids=[c[0] for c in _TRAINING_ATTENTION])
def test_training_attention_lowers_to_the_parents_text(one_chip, monkeypatch,
                                                       dtype, want):
    """The serving prefills' entry (`prefill_attention`: bfloat16
    operands, lengths) shares `_mha_fwd_block` with the differentiable
    kernels and none of their dispatch: forward and backward of the
    training path lower, operand types included, to the very text they
    lowered to before there was such an entry."""
    import hashlib

    import jax.numpy as jnp

    from paddle_tpu.ops import attention as A

    sys.path.insert(0, os.path.join(REPO, "tools"))
    from lowered_hashes import without_locations

    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    # the text read on that parent is the split pair's (its default then):
    # a budget nothing fits reaches it
    monkeypatch.setattr(A, "_FUSED_BWD_VMEM_BUDGET", 1)
    a = jax.ShapeDtypeStruct((2, 1024, 8, 128), jnp.dtype(dtype),
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(A._attention_bthd(
            q, k, v, None, True, None, 0.0, 512, None).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(a, a, a).as_text()
    assert text.count("tpu_custom_call") == 3  # forward, dq, dk and dv
    assert hashlib.sha256(without_locations(text).encode()).hexdigest()[
        :16] == want
