"""Compile the main path's kernels and step programs for a DESCRIBED TPU
v5e at the widths the chip runs them at (chip_smoke.py's model: d_model
1024, 8 heads of 128, vocab 32768, seq 1024) — the chip's own compiler,
no chip attached. What it refuses here (a block Mosaic cannot tile, a
kernel over its VMEM, a Mosaic call GSPMD cannot partition, a step that
does not fit 16 GB) costs no chip time. Nothing runs, so nothing here
says anything about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every xdist
worker imports every test file. All such compiles live in THIS file so
one worker owns the library. Dispatch code sees the CPU here, so the
whole-step cases steer it in the test (PADDLE_TPU_FORCE_PALLAS, a patch
of `_use_pallas_decode`) and every case asserts `tpu_custom_call` in the
compiled text: a case that fell to the XLA path fails instead of passing
empty.
"""
from __future__ import annotations

import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

from paddle_tpu.ops import attention as A
from paddle_tpu.ops import kv_cache as KV

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402  (the model and sizes under test are its)

_FULL = chip_smoke.FULL
B, T, D_MODEL, D_INNER, VOCAB = (_FULL["batch"], _FULL["seq"],
                                 _FULL["d_model"], _FULL["d_inner"],
                                 _FULL["vocab"])
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described chip is written to jax's persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, *avals, mosaic=True, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*avals).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == mosaic, (
        "Mosaic kernel in the compiled text? wanted %s" % mosaic)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    return compiled


def _compile(fn, *avals, **jit_kw):
    return _compiled(fn, *avals, **jit_kw).as_text()


# -- the kernels, alone -----------------------------------------------------

def _attn_loss(kern):
    def loss(q, k, v):
        return jnp.sum(jnp.sin(kern(q, k, v, causal=True)
                               .astype(jnp.float32)))
    return loss


_BTHD = (B, T, 8, 128)
_ATTN_CASES = [
    # id, kernel, shape, grads?, fused backward?
    ("bthd-fwd", "pallas_flash_attention_bthd", _BTHD, False, False),
    ("bthd-bwd-split", "pallas_flash_attention_bthd", _BTHD, True, False),
    ("bthd-bwd-fused", "pallas_flash_attention_bthd", _BTHD, True, True),
    ("bhtd-h8d128-bwd-split", "pallas_flash_attention", (B, 8, T, 128),
     True, False),
    ("bhtd-h8d128-bwd-fused", "pallas_flash_attention", (B, 8, T, 128),
     True, True),
    ("bhtd-h16d64-bwd-split", "pallas_flash_attention", (B, 16, T, 64),
     True, False),
    ("bhtd-h16d64-bwd-fused", "pallas_flash_attention", (B, 16, T, 64),
     True, True),
]


@pytest.mark.parametrize("kernel,shape,grads,fused",
                         [c[1:] for c in _ATTN_CASES],
                         ids=[c[0] for c in _ATTN_CASES])
def test_flash_attention_kernel_compiles(one_chip, monkeypatch, kernel,
                                         shape, grads, fused):
    monkeypatch.setenv("PADDLE_TPU_FLASH_FUSED_BWD", "1" if fused else "0")
    fn = _attn_loss(getattr(A, kernel))
    if grads:
        fn = jax.value_and_grad(fn, argnums=(0, 1, 2))
    av = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    text = _compile(fn, av, av, av)
    # fwd alone is one kernel; split backward adds dq + dkv, fused adds one
    want = 1 if not grads else (2 if fused else 3)
    assert text.count("tpu_custom_call") >= want


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_attention_kernel_compiles(one_chip, dtype):
    sds = jax.ShapeDtypeStruct
    q = sds((8, 1, 8, 128), dtype, sharding=one_chip)
    kv = sds((8, 1024, 8, 128), dtype, sharding=one_chip)
    lens = sds((8,), jnp.int32, sharding=one_chip)
    _compile(KV.pallas_decode_attention, q, kv, kv, lens)


_GROUPED_CASES = [
    # id, query heads, slab or ring rows: the Laguna serving cell's own
    # shapes (64 slots, 8 K/V heads of 128, float32)
    ("full-48on8", 48, 4096),
    ("full-64on8", 64, 4096),
    ("ring-64on8", 64, 512),
]


@pytest.mark.parametrize("heads,rows", [c[1:] for c in _GROUPED_CASES],
                         ids=[c[0] for c in _GROUPED_CASES])
def test_grouped_decode_attention_kernel_compiles(one_chip, heads, rows):
    """g query heads on a slab of 8 key/value heads: the in-place kernel
    is handed the slab itself (its text keeps the slab's shape) and no
    copy, reshape or transpose of it is made around the call."""
    sds = jax.ShapeDtypeStruct
    slab = (64, rows, 8, 128)
    q = sds((64, 1, heads, 128), jnp.float32, sharding=one_chip)
    kv = sds(slab, jnp.float32, sharding=one_chip)
    lens = sds((64,), jnp.int32, sharding=one_chip)
    text = _compile(KV.pallas_decode_attention, q, kv, kv, lens)
    line, = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert "%ptpu.decode_attn_grouped" in line.split(" = ")[0], line
    assert line.count("f32[64,%d,8,128]" % rows) >= 2, line
    moved = [(op, n) for op, n, _ in _whole_slab_ops(text, slab)
             if op in ("copy", "reshape", "transpose")]
    assert not moved, moved


def test_lm_head_loss_gradient_compiles(one_chip):
    """(16384 x 1024) . (1024 x 32768): the chunked fused head; it holds
    no Pallas kernel, so only fit and compile are asserted."""
    from paddle_tpu.ops.fused_loss import lm_head_loss

    sds = jax.ShapeDtypeStruct
    x = sds((B * T, D_MODEL), jnp.bfloat16, sharding=one_chip)
    w = sds((D_MODEL, VOCAB), jnp.float32, sharding=one_chip)
    b = sds((VOCAB,), jnp.float32, sharding=one_chip)
    y = sds((B * T,), jnp.int32, sharding=one_chip)

    def loss(x, w, b, y):
        return jnp.mean(lm_head_loss(4096, x, w, b, y))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, w, b, y).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


# -- whole step programs ----------------------------------------------------

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


@pytest.mark.parametrize("tie", [False, True], ids=["untied", "tied"])
def test_training_step_compiles(one_chip, monkeypatch, tie):
    """The LM training step, 2 layers at full width, AMP O2, fused
    backward, fused head — as chip_smoke.py trains it at 12 layers."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    monkeypatch.setenv("PADDLE_TPU_FLASH_FUSED_BWD", "1")
    main_p, startup, loss = _lm_programs(2, tie=tie)

    def on_chip(aval, batch=False):
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                    sharding=one_chip)

    stepfn, avals = _train_step_avals(
        main_p, startup, loss, lambda n, a: on_chip(a), on_chip)
    text = _compile(stepfn, *avals, donate_argnums=(1,))
    # per layer: flash fwd + fused bwd
    assert text.count("tpu_custom_call") >= 2 * 2


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
    monkeypatch.setenv("PADDLE_TPU_FLASH_FUSED_BWD", "1")
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
    assert text.count("tpu_custom_call") >= 2 * 2
    assert "all-reduce" in text
    assert len(while_bodies(text)) >= 2  # the head's two chunk loops
    found = collectives(text)
    assert not [c for c in found if c[3] is not None]
    whole = ("[%d,%d]" % (VOCAB, D_MODEL), "[%d,%d]" % (D_MODEL, VOCAB))
    assert not [c for c in found if c[0] == "all-gather"
                and any(w in c[1] for w in whole)]
    # an mp-split weight is half per device: fc1.w is (1024, 4096) f32
    fc1 = next(n for n in avals[1] if n.endswith(".fc1.w"))
    assert avals[1][fc1].sharding.shard_shape(avals[1][fc1].shape) == (
        D_MODEL, D_INNER // 2)


# shape of a whole-slab instruction in compiled text, any view of it:
# `%name = f32[8,1024,8,128]{3,2,1,0:T(8,128)} opcode(%operands...)`
_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\](\{\S*)? ([\w-]+)\((.*)$")


def _whole_slab_ops(text, slab_shape):
    """[(opcode, name, changes layout?)] of the top-level instructions
    whose result holds a whole slab's elements, whatever its view."""
    n = int(np.prod(slab_shape))
    entry = text[text.index("ENTRY "):]
    shapes, out = {}, []
    for line in entry.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        name, dims, layout, opcode, operands = m.groups()
        # tiling and dimension order; `S(n)` is a memory space, not a layout
        shape = (dims, re.sub(r"S\(\d+\)", "", (layout or "").split("}")[0]))
        shapes[name] = shape
        if int(np.prod([int(d) for d in dims.split(",")])) != n:
            continue
        src = re.match(r"%(\S+?)[,)]", operands)
        changed = bool(src) and shapes.get(src.group(1), shape) != shape
        out.append((opcode, name, changed))
    return out


def _serving_step(pred, kind, batch, seq, one_chip, **kw):
    """(step function, feed shapes, state shapes, how many cache entries
    it is fed) of the program a graph-builder-only DecodePredictor
    builds for (kind, batch, seq), placed on the described chip. The
    function is `DecodePredictor._step`'s: the very one `_acquire` jits,
    its outputs in the order it traces them."""
    from paddle_tpu.executor import analyze_state

    pred.traces = 0
    step = pred._step(kind, batch, seq, "greedy", **kw)
    sds = jax.ShapeDtypeStruct
    feeds = {n: sds(a.shape, a.dtype, sharding=one_chip)
             for n, a in pred._feed_structs(step.program,
                                            step.feed_names).items()}
    gb = step.program.global_block()
    state = {}
    for n in analyze_state(step.program, set(step.feed_names))[0]:
        var = gb._find_var_recursive(n)
        state[n] = sds(tuple(var.shape), np.float32, sharding=one_chip)
    return step.fn, feeds, state, step.n_cache


_SERVING_CASES = [
    # id, kind, batch, seq, layers, heads, d_model, d_inner, vocab, tied,
    # what else `_step` takes
    ("decode-8x1024", "decode", 8, 1024, 2, 8, D_MODEL, D_INNER, VOCAB,
     False, {}),
    ("prefill-8x512", "prefill", 8, 512, 2, 8, D_MODEL, D_INNER, VOCAB,
     False, {}),
    # the serving cell's own decode step (OPT-6.7B widths: 32 heads of
    # 128, 2048 positions, tied table, 4 layers)
    ("decode-8x2048-h32", "decode", 8, 2048, 4, 32, 4096, 16384, 50272,
     True, {}),
    # the other donating steps, at small depth: a speculative round's
    # verify window, and the decode step over int8 slabs, whose
    # (slots, seq) scales are a class of donated feeds of their own
    ("verify-8x1024-w5", "verify", 8, 1024, 2, 8, D_MODEL, D_INNER, VOCAB,
     False, {"window": 5}),
    ("decode-8x1024-int8", "decode", 8, 1024, 2, 8, D_MODEL, D_INNER,
     VOCAB, False, {"kv_dtype": "int8"}),
]


@pytest.mark.parametrize(
    "kind,batch,seq,n_layer,n_head,d_model,d_inner,vocab,tied,step_kw",
    [c[1:] for c in _SERVING_CASES], ids=[c[0] for c in _SERVING_CASES])
def test_serving_step_compiles(one_chip, monkeypatch, kind, batch, seq,
                               n_layer, n_head, d_model, d_inner, vocab,
                               tied, step_kw):
    """The programs DecodePredictor builds for chip_smoke.py's serve
    phase, 2 layers at full width: the decode step at 8 slots x 1024 (the
    Pallas decode kernel, feeds donated) and the burst prefill; the
    decode step at the benchmark's serving widths; and the verify and
    int8 decode steps. What is compiled is the function `_acquire` jits.

    A step that is fed its cache moves no slab. The float32 kernel reads
    the (slots, seq, heads, d_head) feed where it lies, so the compiled
    step holds no `reshape`, `transpose` or layout-changing `copy` of a
    whole slab (each was a 268 MB relayout, two a layer a step on the
    chip: PERF.md, PR 25). And every entry comes back in its own feed's
    buffer: jax pairs a donated feed with the first output of its type,
    the feeds flatten sorted by name (kcache_0.., vcache_0..), and the
    step traces the updates in that order whatever `cache_spec`'s
    (`_pairing_order`), so no same-layout `copy` repairs a crossed
    pairing (there were 8 in the serving cell's step, 46% of its device
    time: PERF.md, PR 27), the aliased bytes cover the spec's, and the
    temporaries are a few MiB."""
    from paddle_tpu.serving.decode import DecodeConfig, DecodePredictor

    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    monkeypatch.setattr(
        KV, "_use_pallas_decode",
        lambda s, d: d % 128 == 0 and s % 128 == 0 and s >= 128)
    pred = DecodePredictor.__new__(DecodePredictor)  # graph builder only
    pred.config = DecodeConfig(vocab_size=vocab, n_layer=n_layer,
                               n_head=n_head, d_model=d_model,
                               d_inner=d_inner, max_len=max(T, seq),
                               tie_embeddings=tied)
    pred.sample_k, pred.sample_p, pred.temperature = 40, 0.9, 1.0
    pred.draft_n_layer = 1
    step_fn, feeds, state, n_cache = _serving_step(pred, kind, batch, seq,
                                                   one_chip, **step_kw)
    # a verify window attends through the lax path: no Mosaic call
    mosaic = kind != "verify"
    compiled = _compiled(step_fn, feeds, state, mosaic=mosaic,
                         donate_argnums=(0,))
    text = compiled.as_text()
    if mosaic:
        assert text.count("tpu_custom_call") >= n_layer  # one per layer
    if kind == "prefill":
        assert n_cache == 0
        return
    from paddle_tpu.serving.decode import _aliased_outputs

    spec = pred.cache_spec(batch, seq, step_kw.get("kv_dtype", "float32"))
    assert n_cache == len(spec)
    n_out = len(jax.tree_util.tree_leaves(compiled.out_info))
    assert set(range(n_out - n_cache, n_out)) <= _aliased_outputs(compiled)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(e.nbytes for e in spec)
    slab = spec[0].shape
    ops = _whole_slab_ops(text, slab)
    copies = [name for op, name, _ in ops if op == "copy"]
    assert not copies, "whole-slab copies in the step: %r" % copies
    if spec[0].dtype == "float32":
        moved = [(op, name) for op, name, _ in ops
                 if op in ("reshape", "transpose")]
        assert not moved, "whole-slab relayouts in the step: %r" % moved
    if kind == "decode" and spec[0].dtype == "float32":
        # the kernel's views of K and V are free
        assert sum(op == "bitcast" for op, _, _ in ops) >= 2 * n_layer
    assert mem.temp_size_in_bytes < 16 * 2**20, mem.temp_size_in_bytes


_HYBRID_CASES = [
    # id, kind, batch, seq: one period of 14 layers at the published
    # widths of the hybrid serving cell (benchmark/configs/jamba2-3b.json)
    ("decode-64x2048", "decode", 64, 2048),
    ("prefill-8x512", "prefill", 8, 512),
]


@pytest.mark.parametrize("kind,batch,seq", [c[1:] for c in _HYBRID_CASES],
                         ids=[c[0] for c in _HYBRID_CASES])
def test_hybrid_serving_step_compiles(one_chip, monkeypatch, kind, batch,
                                      seq):
    """The programs DecodePredictor builds for a hybrid of 13 state-space
    layers and one attention layer (20 query heads on 1 K/V head of 128,
    d_inner 5120, state 16): they compile for a v5e and fit it beside
    nothing else. The decode step donates every cache entry and gets each
    back in place: its fetches come in the feeds' own (sorted) order, so
    no recurrent state (64 x 5120 x 16) and no slab is copied to repair a
    pairing, and the step's temporaries stay small. The prefill holds one
    `while` a state-space layer (the plain `lax.scan`s) and one Mosaic
    call (the flash forward of the attention layer)."""
    from paddle_tpu.serving.decode import DecodeConfig, DecodePredictor

    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    pred = DecodePredictor.__new__(DecodePredictor)  # graph builder only
    pred.config = DecodeConfig(
        vocab_size=65536, n_layer=14, n_head=20, d_model=2560, d_inner=8192,
        max_len=2048, tie_embeddings=True, n_kv_head=1,
        attn_layer_period=14, attn_layer_offset=7, mamba_d_state=16,
        mamba_d_conv=4, mamba_dt_rank=160, mamba_expand=2, norm="rms_norm",
        norm_eps=1e-6, ffn="gated_silu", positions=False, biases=False)
    pred.sample_k, pred.sample_p, pred.temperature = 40, 0.9, 1.0
    step_fn, feeds, state, _ = _serving_step(pred, kind, batch, seq,
                                             one_chip)
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    text = compiled.as_text()
    if kind == "prefill":
        assert text.count("tpu_custom_call") == 1   # the flash forward
        assert text.count(" while(") == 13          # one scan a layer
        return
    # the lax path of one shared K/V head: no Mosaic call in the step
    assert "tpu_custom_call" not in text
    spec = pred.cache_spec(batch, seq)
    cache_bytes = sum(e.nbytes for e in spec)
    assert mem.alias_size_in_bytes >= cache_bytes   # every entry in place
    for shape in {e.shape for e in spec if e.nbytes > 2**24}:
        copies = [name for op, name, _ in _whole_slab_ops(text, shape)
                  if op == "copy"]
        assert not copies, (shape, copies)
    assert mem.temp_size_in_bytes < 200 * 2**20, mem.temp_size_in_bytes


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
    import json

    from paddle_tpu.serving.decode import DecodePredictor

    sys_path = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, sys_path)
    if os.path.join(sys_path, "benchmark") not in list(getattr(
            sys.modules.get("benchmark"), "__path__", [])):
        import types

        sys.modules["benchmark"] = types.ModuleType("benchmark")
        sys.modules["benchmark"].__path__ = [
            os.path.join(sys_path, "benchmark")]
    from benchmark.models import laguna_lm

    with open(os.path.join(sys_path, "benchmark", "configs",
                           "laguna-xs.2.json")) as f:
        cfg = json.load(f)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    monkeypatch.setattr(
        KV, "_use_pallas_decode",
        lambda s, d: d % 128 == 0 and s % 128 == 0 and s >= 128)
    pred = DecodePredictor.__new__(DecodePredictor)  # graph builder only
    pred.config = laguna_lm.decode_config(cfg, "serve")
    pred.sample_k, pred.sample_p, pred.temperature = 40, 0.9, 1.0
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
    import json

    from paddle_tpu.serving.decode import DecodePredictor

    sys_path = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, sys_path)
    if os.path.join(sys_path, "benchmark") not in list(getattr(
            sys.modules.get("benchmark"), "__path__", [])):
        import types

        sys.modules["benchmark"] = types.ModuleType("benchmark")
        sys.modules["benchmark"].__path__ = [
            os.path.join(sys_path, "benchmark")]
    from benchmark.models import phi4flash_lm

    with open(os.path.join(sys_path, "benchmark", "configs",
                           "phi4-mini-flash.json")) as f:
        cfg = json.load(f)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    monkeypatch.setattr(
        KV, "_use_pallas_decode",
        lambda s, d: d % 128 == 0 and s % 128 == 0 and s >= 128)
    pred = DecodePredictor.__new__(DecodePredictor)  # graph builder only
    pred.config = phi4flash_lm.decode_config(cfg, "serve")
    pred.sample_k, pred.sample_p, pred.temperature = 40, 0.9, 1.0
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


@pytest.mark.parametrize("b,s,h,row", [(64, 4096, 40, 1280),
                                       (8, 1024, 40, 1280),
                                       (8, 2048, 16, 512)])
def test_diff_attn_rows_kernel_compiles(one_chip, b, s, h, row):
    """The kernel over a slab of flat rows (`ops/diff_attn.py`) at the
    Phi-4-mini-flash cell's slab, at its largest prefill's rows (a cross
    layer's one query row a prompt) and at chip_smoke's block: Mosaic
    takes the lane slices of a (rows, P x 128) block and the (heads, S)
    score scratch, with no temporaries outside the call."""
    from paddle_tpu.ops import diff_attn as D

    compiled = _compiled(
        lambda qp, k, v, n: D.pallas_attend_rows(qp, k, v, n, 0.125),
        jax.ShapeDtypeStruct((b, 1, h, 128), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((b, s, row), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((b, s, row), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip))
    assert "ptpu.diff_attn_rows" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
