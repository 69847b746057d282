"""Compile the main path's kernels, alone, for a DESCRIBED TPU v5e
(`tpu_compile_lib.py`) at the shapes the chip runs them at: the flash
attention kernels (both layouts, split and fused backward), the decode
kernels (one K/V head a query head, and grouped queries on a slab of 8),
the fused LM-head loss gradient, the kernel over a slab of flat rows,
the absorbed latent attention's kernel over its slab's transposed
view, the selective scan's kernel and the chunked delta rule's. See `test_tpu_compile.py` for what such a compile can and cannot
say. The MiMo-V2-Flash cell's two programs are here too, around the one
new kernel `ptpu.decode_attn_uneven`: `test_tpu_compile_serving.py` is
at the gate's 240 s a file (ROADMAP D1) and this file is the lightest.
"""
from __future__ import annotations

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import attention as A
from paddle_tpu.ops import kv_cache as KV

from tpu_compile_lib import (B, D_MODEL, HBM_BYTES, T, VOCAB, _compile,
                             _compiled, _serving_step, _whole_slab_ops)
from tpu_compile_lib import one_chip, topo  # noqa: F401  (fixtures)


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
    if not fused:  # a budget nothing fits: the split pair
        monkeypatch.setattr(A, "_FUSED_BWD_VMEM_BUDGET", 1)
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


@pytest.mark.parametrize("b,s,h,row,rank", [(32, 16384, 32, 320, 256),
                                            (8, 2048, 8, 320, 256),
                                            (64, 16384, 32, 576, 512)],
                         ids=["cell-32x16384", "smoke-8x2048",
                              "ling-cell-64x16384"])
def test_latent_attention_kernel_compiles(one_chip, b, s, h, row, rank):
    """The absorbed attention's kernel (`ops/mla.py`) at the
    Mistral-Small-4 cell's slab (the transposed view is (32, 320,
    16384)), at chip_smoke's and at the Ling-3.0-flash cell's (64, 576,
    16384): Mosaic takes a (row, lanes) block whose contraction is no
    multiple of 128, the copy of its first `rank` sublane rows into the
    (rank, S) scratch that keeps a slot's V part between the passes
    (since PR 55 the call streams the slab ONCE: one operand, no second
    half of the grid), the weighted sum over that scratch's lane blocks
    and the (heads, S) score scratch, inside the scoped VMEM the call
    asks for (21.5 MB and 40.4 MB held at the cells' shapes, 16 MiB
    beside them; a v5e core has 128 MiB); the compiler keeps the slab's
    parameter in its sequence-minor layout and hands the kernel a
    BITCAST of it: no copy, no transpose, no temporaries outside the
    call."""
    from paddle_tpu.ops import mla
    from paddle_tpu.ops.decode_stream import block_positions, kept_vmem_bytes

    slab = (b, s, row)
    view = mla.latent_view(s, h, row, rank, jnp.float32)
    assert block_positions(view) == min(s, mla._LATENT_BLOCK_LANES)
    held = kept_vmem_bytes(view)
    print("ptpu.mla_latent_attn %s: %.1f MB of VMEM held (kept rows, scores, "
          "two blocks)" % (slab, held / 1e6))
    assert held is not None and held >= 4 * rank * s
    compiled = _compiled(
        lambda q, c, n: mla.pallas_latent_attend(q, c, n, rank),
        jax.ShapeDtypeStruct((b, h, row), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct(slab, jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip))
    text = compiled.as_text()
    line, = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert "%ptpu.mla_latent_attn" in line.split(" = ")[0], line
    # the slab's transposed view is the call's ONE streamed operand
    assert line.split("operand_layout_constraints={")[1].split(
        "}}")[0].count("f32[%d,%d,%d]{2,1,0" % (b, row, s)) == 1, line
    assert '"size":"%d"' % (held + 16 * 2**20) in line, line
    assert "f32[%d,%d,%d]{1,2,0" % slab in text
    ops = [op for op, _, _ in _whole_slab_ops(text, slab)]
    assert ops[0] == "parameter" and set(ops[1:]) == {"bitcast"}, ops
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("b,t", [(1, 2048), (8, 256)],
                         ids=["cell-1x2048", "cell-8x256"])
def test_ssm_scan_kernel_compiles(one_chip, b, t):
    """The selective scan's kernel (`ops/ssm.py`) at the hybrid cells'
    widths (`d_inner` 5,120, `d_state` 16), at their longest bucket and
    at their widest admission: Mosaic takes the (8, 16) transposes of a
    group's B and C rows, the lane broadcast of a position's column and
    the 16-sublane sum on a (16, lanes) state tile, inside the default
    scoped VMEM; x, delta and y are the (B, T, Di) arrays where they
    lie (no transposed copy of them outside the call, which the lax
    form's `while` needs five of), and the only temporaries are B and C
    padded to whole lanes."""
    from paddle_tpu.ops import ssm as S

    di, n = 5120, 16
    block_t, block_d = S._kernel_blocks(t, di, n)
    # double-buffered blocks of x, delta, y, A^T, B, C (N lanes padded
    # to 128), D (8 sublanes), the state's block and the state's scratch
    vmem = 4 * (2 * (3 * block_t * block_d + n * block_d
                     + 2 * block_t * 128 + 8 * block_d + n * block_d)
                + n * block_d)
    print("ptpu.ssm_scan (%d, %d, %d): blocks of (%d positions, %d lanes), "
          "%.2f MiB of VMEM in blocks and scratch"
          % (b, t, di, block_t, block_d, vmem / 2**20))
    assert vmem < 12 * 2**20

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compiled(
        S.pallas_ssm_scan, sd((b, t, di)), sd((b, t, di)), sd((di, n)),
        sd((b, t, n)), sd((b, t, n)), sd((di,)), sd((b,), jnp.int32))
    text = compiled.as_text()
    line, = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert "%ptpu.ssm_scan" in line.split(" = ")[0], line
    assert " while(" not in text
    wide = [(op, name) for op, name, _ in _whole_slab_ops(text, (b, t, di))
            if op not in ("parameter", "get-tuple-element")]
    assert not wide, "x, delta or y copied outside the call: %r" % wide
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        2 * b * t * 128 * 4 + 2**16)


@pytest.mark.parametrize(
    "b,t,h,guarded",
    [(8, 2048, 32, False), (2, 8192, 32, False), (1, 16384, 32, False),
     (8, 2048, 64, True), (4, 4096, 64, True)],
    ids=["cell-8x2048", "cell-2x8192", "cell-1x16384",
         "guarded-64-heads-8x2048", "guarded-64-heads-4x4096"])
def test_kda_scan_kernel_compiles(one_chip, b, t, h, guarded):
    """The chunked delta rule's kernel (`ops/kda.py`) at Ling-3.0-flash's
    head sizes (32 heads of a 128 x 128 state), at the cell's widest,
    its middle and its longest admission, and in its GUARDED form (a
    gate with no bound: the rolls down the sublanes and the masked
    exponentials of a sub-chunk's own block) at Solar-Open2-250B's 64
    heads and widest admissions: Mosaic takes the strided
    reads and writes of the forward substitution, the lane slices of the
    diagonal blocks, the stacked bfloat16 parts of the three-pass
    products and the dynamic loops over live chunks, inside the default
    scoped VMEM. ONE call, whose result tuple holds the final state at
    its interface shape (what the benchmark's reader of matrix states
    looks for, `,32,128,128]`, in the call's own line); q, k, v, g are
    the projections' (B, T, H * d) arrays where they lie; no `while`,
    and no temporary of a chunk's or a sub-chunk's shape outside the
    call: the only ones are o before its reshape and beta's copy."""
    from paddle_tpu.ops import kda as K

    d = 128
    block_t = K._kernel_block(t, d, d)
    assert block_t == K._KERNEL_BLOCK_T
    # double-buffered blocks of q, k, v, g, o, beta (H lanes padded to
    # 128), the state's block; the scratches: the state, four (Tb, d),
    # two (Tb, 64) and two (Tb, 16) padded to 128 lanes, e^(G_C)
    vmem = 4 * (2 * (5 * block_t * d + block_t * 128 + d * d)
                + d * d + 4 * block_t * d + 4 * block_t * 128
                + block_t // 64 * d * d)
    print("ptpu.kda_scan (%d, %d, %d, %d): blocks of %d positions, "
          "%.2f MiB of VMEM in blocks and scratch"
          % (b, t, h, d, block_t, vmem / 2**20))
    assert vmem < 12 * 2**20

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def scan(q, k, v, g, beta, lens):
        q, k, v, g = (a.reshape(b, t, h, d) for a in (q, k, v, g))
        o, state = K.pallas_kda_scan(q, k, v, g, beta, lens,
                                     guarded=guarded)
        return o.reshape(b, t, h * d), state

    flat = sd((b, t, h * d))
    compiled = _compiled(scan, flat, flat, flat, flat, sd((b, t, h)),
                         sd((b,), jnp.int32))
    text = compiled.as_text()
    line, = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert "%ptpu.kda_scan" in line.split(" = ")[0], line
    assert ",%d,128,128]" % h in line.split(" custom-call(")[0], line[:400]
    assert " while(" not in text
    wide = [(op, name) for op, name, _ in _whole_slab_ops(text, (b, t, h * d))
            if op not in ("parameter", "get-tuple-element", "bitcast")]
    assert not wide, "q, k, v, g or o copied outside the call: %r" % wide
    for shape in (",64,64]", ",16,16]", ",4,4,16,", ",64,%d,128]" % h):
        assert shape not in text, shape
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        b * t * 128 * 4 + 2**16)


_MIMO_CASES = [
    # id, kind, batch, seq: the MiMo-V2-Flash serving cell's own programs
    # (benchmark/configs/mimo-v2-flash.json: 7 layers at published widths,
    # 8 of 256 experts held, 16 slots of 16,384 positions; the largest
    # admission is one prompt of the 16,384 bucket)
    ("decode-16x16384", "decode", 16, 16384),
    ("prefill-1x16384", "prefill", 1, 16384),
]


@pytest.mark.parametrize("kind,batch,seq", [c[1:] for c in _MIMO_CASES],
                         ids=[c[0] for c in _MIMO_CASES])
def test_mimo_v2_serving_step_compiles(one_chip, monkeypatch, kind, batch,
                                       seq):
    """The programs DecodePredictor builds for the MiMo-V2-Flash cell
    (64 query heads of 192 over value heads of 128; a full layer on 4
    key/value heads, a sliding one on 8 with a window of 128 and a
    learned sink a head; a sigmoid router with a selection bias over 256
    experts of width 2,048, 8 held, no shared one; an untied head over
    19,072 ids): they compile for a v5e and fit it beside each other. A
    prefill holds one flash forward a layer on bfloat16 operands, q and
    k padded 192 -> 256 and v at 128 (`ptpu.flash_fwd` twice,
    `ptpu.attn_window` five times), K and V at their own 4 or 8 heads,
    a sliding layer's sink and band inside its call. The step donates its fourteen
    entries; each full layer's two slabs of FLAT rows (768 beside 512
    floats a position) are read where they lie by one call of
    `ptpu.decode_attn_uneven` and appended to in place: nothing of a
    slab's size is copied, reshaped or transposed."""
    from test_tpu_compile_cells import (
        _assert_bfloat16_operands_and_lengths, _cell_predictor,
        _prefill_attention_calls)

    pred = _cell_predictor("mimo_v2_lm", "mimo-v2-flash.json", monkeypatch)
    step_fn, feeds, state, n_cache = _serving_step(pred, kind, batch, seq,
                                                   one_chip)
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    weights = sum(int(np.prod(s.shape)) * 4 for s in state.values())
    assert 8.88e9 < weights < 8.90e9, weights  # 2.222 B parameters
    text = compiled.as_text()
    spec = pred.cache_spec(16, 16384)
    slabs = sum(e.nbytes for e in spec)
    assert round(slabs / 1e9, 2) == 2.79
    calls = re.findall(r"%([\w.-]+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    if kind == "prefill":
        assert calls.count("ptpu.flash_fwd") == 2, calls
        assert calls.count("ptpu.attn_window") == 5, calls
        # K and V at their own head count (PR 57): 8 x 256 / 8 x 128
        # channels a token on a sliding layer, 4 x 256 / 4 x 128 on a
        # full one, nothing repeated to the 64 query heads
        q, own = "bf16[1,16384,16384]", {
            "ptpu.attn_window": ["bf16[1,16384,2048]", "bf16[1,16384,1024]"],
            "ptpu.flash_fwd": ["bf16[1,16384,1024]", "bf16[1,16384,512]"]}
        for name, o in _assert_bfloat16_operands_and_lengths(text, batch):
            assert o[1:] == [q] + own[name.rstrip(".0123456789")], (name, o)
        # a sliding layer's call alone is handed the 64 sinks (beside the
        # lengths) and its band's bias: a q-block of 256 rows against
        # the one block of 384 keys it sees
        for name, o in _prefill_attention_calls(text):
            extra = [x for x in o if x.startswith("f32[")]
            assert extra == (["f32[64]", "f32[2,256,384]"]
                             if name.startswith("ptpu.attn_window")
                             else []), (name, o)
        # and under an attention kernel's scope there is nothing but the
        # Mosaic call and its results: no pass of XLA's for the sink
        # (the parent's was a multiply over the (T, 64, 128) output by
        # sigmoid(lse - sink) from a transposed lse, five times)
        stray = [ln.strip()[:200] for ln in text.splitlines()
                 if re.search(r'op_name="[^"]*ptpu\.(attn_window|flash_fwd)',
                              ln)
                 and "tpu_custom_call" not in ln
                 and not re.search(r" (get-tuple-element|bitcast)\(", ln)]
        assert not stray, stray
        # beside the weights, the slots' entries and the step
        assert weights + slabs + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes) < 15.5 * 2**30, mem
        # 2.53 GiB (2.89 with K and V repeated, before PR 57) where
        # ISSUE 54 allowed 3-4.5: 16 slots fit
        assert mem.temp_size_in_bytes < 2.7 * 2**30, mem.temp_size_in_bytes
        return
    assert [c for c in calls if c.startswith("ptpu.")] == [
        "ptpu.decode_attn_uneven"] * 2, calls
    assert n_cache == len(spec) == 14
    assert mem.alias_size_in_bytes >= slabs
    for shape in ((16, 16384, 768), (16, 16384, 512)):
        ops = _whole_slab_ops(text, shape)
        moved = [name for op, name, changed in ops
                 if op in ("copy", "transpose", "reshape") or changed]
        assert ops and not moved, (shape, ops)
    assert mem.temp_size_in_bytes < 200 * 2**20, mem.temp_size_in_bytes
