"""Compile the serving steps of the OPT-family model and of the hybrid
for a DESCRIBED TPU v5e (`tpu_compile_lib.py`): the programs
`DecodePredictor` builds, at the widths the chip and the benchmark's
cells run them at; and two cells' own programs that
`test_tpu_compile_cells.py` has no room for (Ling's widest admission,
the dots3-note-prev pair). See `test_tpu_compile.py` for what such a compile can
and cannot say.
"""
from __future__ import annotations

import re

import jax
import numpy as np
import pytest

from paddle_tpu.ops import kv_cache as KV

from tpu_compile_lib import (D_INNER, D_MODEL, HBM_BYTES, T, VOCAB,
                             _compiled, _serving_step, _whole_slab_ops)
from tpu_compile_lib import one_chip, topo  # noqa: F401  (fixtures)


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


_COMPILED = {}  # a case's values -> what `_compiled_case` gives: one compile


def _compiled_case(one_chip, monkeypatch, kind, batch, seq, n_layer, n_head,
                   d_model, d_inner, vocab, tied, step_kw):
    """The function `_acquire` jits for one of `_SERVING_CASES`,
    compiled for the described chip, feeds donated: (the graph builder,
    the executable, the cache entries it is fed, its state's shapes)."""
    from paddle_tpu.serving.decode import DecodeConfig, DecodePredictor

    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    monkeypatch.setattr(
        KV, "_use_pallas_decode",
        lambda s, d: d % 128 == 0 and s % 128 == 0 and s >= 128)
    case = (kind, batch, seq, n_layer, n_head, d_model, d_inner, vocab, tied,
            tuple(sorted(step_kw.items())))
    if case not in _COMPILED:
        pred = DecodePredictor.__new__(DecodePredictor)  # graph builder only
        pred.config = DecodeConfig(vocab_size=vocab, n_layer=n_layer,
                                   n_head=n_head, d_model=d_model,
                                   d_inner=d_inner, max_len=max(T, seq),
                                   tie_embeddings=tied)
        pred.sample_k, pred.sample_p, pred.temperature = 40, 0.9, 1.0
        pred.draft_n_layer = 1
        step_fn, feeds, state, n_cache = _serving_step(
            pred, kind, batch, seq, one_chip, **step_kw)
        compiled = _compiled(step_fn, feeds, state, mosaic=kind != "verify",
                             donate_argnums=(0,))
        _COMPILED[case] = (pred, compiled, n_cache, state)
    return _COMPILED[case]


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
    pred, compiled, n_cache, _ = _compiled_case(
        one_chip, monkeypatch, kind, batch, seq, n_layer, n_head, d_model,
        d_inner, vocab, tied, step_kw)
    # a verify window attends through the lax path: no Mosaic call
    mosaic = kind != "verify"
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


def test_serving_decode_step_maps_its_fusions_to_ops_and_weights(
        one_chip, monkeypatch):
    """`observability.scopes.scope_map` of the serving cell's own decode
    step (OPT-6.7B widths, 4 layers) as the chip's compiler leaves it:
    the Mosaic call keeps its name (`ptpu.decode_attn.N`) with the
    tracer's scope around it, every parameter is a state array or a feed
    at its own size, and ONE fusion holds both FFN products of a layer,
    reading both matrices (537 MB a layer in float32: what the four
    largest fusions of the cell's trace are)."""
    from paddle_tpu.observability import scopes

    case = next(c for c in _SERVING_CASES if c[0] == "decode-8x2048-h32")
    pred, compiled, _, state = _compiled_case(one_chip, monkeypatch,
                                              *case[1:])
    m = scopes.scope_map(compiled)
    assert m["scoped"] and m["module"].startswith("jit_")
    kernels = {n: o for n, o in m["ops"].items() if "ptpu." in n}
    assert len(kernels) == 4 and all(
        re.match(r"ptpu\.decode_attn\.\d+$", n)
        and o["scope"][0].startswith("fl.decode_attention:")
        and o["scope"][1] == "ptpu.decode_attn"
        for n, o in kernels.items()), kernels
    # a slab is read where it is fed by the append that writes its row
    # (the kernel then reads that fusion's result, not a parameter)
    appends = [o for o in m["ops"].values() if o["scope"]
               and o["scope"][-1].startswith("fl.cache_append:")
               and any("cache_" in r for r in o["reads"])]
    assert sorted(r for o in appends for r in o["reads"] if "cache_" in r) \
        == sorted("feeds['%scache_%d']" % (kv, i)
                  for kv in "kv" for i in range(4))
    d, di = 4096, 16384
    assert m["params"]["state['lm.l2.ffn.fc1.w']"] == d * di * 4
    assert m["params"]["feeds['kcache_0']"] == 8 * 2048 * d * 4
    # the parameters are the state arrays, each at its own size
    assert {n: b for n, b in m["params"].items()
            if n.startswith("state[")} == {
        "state['%s']" % n: int(np.prod(a.shape)) * 4
        for n, a in state.items()}
    for layer in range(4):
        w1, w2 = ("state['lm.l%d.ffn.fc%d.w']" % (layer, i) for i in (1, 2))
        both = [o for o in m["ops"].values()
                if w1 in o["reads"] and w2 in o["reads"]]
        assert len(both) == 1, (layer, both)
        assert {"fl.mul:lm.l%d.ffn.fc1.w" % layer,
                "fl.mul:lm.l%d.ffn.fc2.w" % layer} <= set(
            both[0]["members"]), both
        # and streams them from HBM itself: no copy or prefetch between
        assert not {w1, w2} & set(both[0]["copied"]), both
    # whatever reaches its reader only through an operation that moves
    # it (`copied`) is read in place by such an operation, an event of
    # its own: a share of the HBM peak counts its bytes there or nowhere
    movers = {r for n, o in m["ops"].items() for r in o["reads"]
              if r not in o["copied"] and re.match(
                  r"(copy|copy-start|slice-start|convert)[.\d]*$", n)}
    assert {r for o in m["ops"].values() for r in o["copied"]} <= movers
    named = sum(1 for o in m["ops"].values() if o["scope"] or o["members"])
    unnamed = {re.sub(r"[.\d]+$", "", n) for n, o in m["ops"].items()
               if not (o["scope"] or o["members"])}
    # what carries no scope is the compiler's own moving of weights
    assert unnamed <= {"copy-start", "copy-done", "slice-start",
                       "slice-done", "custom-call", "copy"}, unnamed
    assert named >= 100


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
    pairing, and the step's temporaries stay small. The prefill, bound
    for a TPU, holds one Mosaic call a state-space layer
    (`ptpu.ssm_scan`, the selective scan's kernel: 13) beside the flash
    forward of the attention layer, and no `while`: no scan takes the
    lax form in a bucket of whole blocks of positions."""
    import types

    from paddle_tpu import observability as obs
    from paddle_tpu.ops import kv_cache as KV
    from paddle_tpu.serving.decode import DecodeConfig, DecodePredictor

    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    if kind == "prefill":
        # the gate asks what device a step is bound for, and here that
        # is the CPU: the test steers it, as FORCE_PALLAS steers the
        # flash kernel's
        monkeypatch.setattr(KV, "current_device",
                            lambda: types.SimpleNamespace(platform="tpu"))
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

    def scans_traced():
        return {k["path"]: v for k, v in obs.SSM_SCAN_TRACES.samples()}

    before = scans_traced()
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    text = compiled.as_text()
    if kind == "prefill":
        after = scans_traced()
        assert after["kernel"] - before.get("kernel", 0) == 13
        assert after.get("lax", 0) == before.get("lax", 0)
        calls = [ln.split(" = ")[0] for ln in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        assert len(calls) == 14, calls              # 13 scans, one flash
        assert sum("ptpu.ssm_scan" in c for c in calls) == 13, calls
        assert " while(" not in text
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


def _kda_scans_traced(form):
    """(kernel, lax) traces of the chunked delta rule in one form
    ("factored" | "guarded")."""
    from paddle_tpu import observability as obs

    got = {"kernel": 0, "lax": 0}
    for k, v in obs.KDA_SCAN_TRACES.samples():
        if k["form"] == form:
            got[k["path"]] += v
    return got["kernel"], got["lax"]


_SOLAR_CASES = [
    # id, kind, batch, seq: the Solar-Open2-250B serving cell's own
    # programs (benchmark/configs/solar-open2-250b.json: 4 layers at
    # published widths, 20 of 320 experts held, 32 slots of 8,192
    # positions; the largest admissions hold 16,384 bucketed tokens)
    ("decode-32x8192", "decode", 32, 8192),
    ("prefill-4x4096", "prefill", 4, 4096),
]


@pytest.mark.parametrize("kind,batch,seq", [c[1:] for c in _SOLAR_CASES],
                         ids=[c[0] for c in _SOLAR_CASES])
def test_solar_open2_serving_step_compiles(one_chip, monkeypatch, kind,
                                           batch, seq):
    """The programs DecodePredictor builds for the Solar-Open2-250B cell
    (three KDA layers: 64 heads of a 128 x 128 state under the UNBOUNDED
    softplus gate, beta in (0, 2), the decay and the output gate through
    bottlenecks of 128; one softmax layer: 64 query heads on 8 key/value
    heads of 128, no positions, an elementwise gate; a softmax router
    over 320 experts of width 1,280, 20 held; an untied head over 24,576
    ids): they compile for a v5e and fit it beside each other. An
    admission holds ONE `ptpu.kda_scan` call a KDA layer, the GUARDED
    form's, with the final state at its interface shape in its own line
    (the counter says `kernel`, `form=guarded`, three times, and `lax`
    never: a program traced onto the composed guarded path on a TPU
    fails here), beside the softmax layer's one flash forward on
    bfloat16 operands (k and v repeated for the 8 query heads that share
    each, as Laguna's prefill hands them). The step donates its
    fourteen entries: each matrix state comes back from one call of the
    step's kernel over itself (`ptpu.kda_step` takes 64 heads: 16 a grid
    cell), the slab is read where it lies by one call of the grouped
    kernel (64 query heads on 8, no rotation), and nothing of a state's
    or the slab's size is copied."""
    from paddle_tpu import observability as obs
    from test_tpu_compile_cells import (
        _assert_bfloat16_operands_and_lengths, _cell_predictor)

    def steps_traced():
        got = {"kernel": 0, "lax": 0}
        for k, v in obs.KDA_STEP_TRACES.samples():
            got[k["path"]] += v
        return got["kernel"], got["lax"]

    pred = _cell_predictor("solar_open2_lm", "solar-open2-250b.json",
                           monkeypatch)
    scans, steps = _kda_scans_traced("guarded"), steps_traced()
    factored = _kda_scans_traced("factored")
    step_fn, feeds, state, n_cache = _serving_step(pred, kind, batch, seq,
                                                   one_chip)
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    weights = sum(int(np.prod(s.shape)) * 4 for s in state.values())
    assert 8.19e9 < weights < 8.21e9, weights  # 2.050 B parameters
    text = compiled.as_text()
    spec = pred.cache_spec(32, 8192)
    slabs = sum(e.nbytes for e in spec)
    assert round(slabs / 1e9, 2) == 2.58
    calls = re.findall(r"%([\w.-]+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    if kind == "prefill":
        assert _kda_scans_traced("guarded") == (scans[0] + 3, scans[1])
        assert _kda_scans_traced("factored") == factored
        assert calls.count("ptpu.kda_scan") == 3, calls
        assert calls.count("ptpu.flash_fwd") == 1, calls
        assert calls.count("ragged-dot-none") == 3 * 4
        for ln in text.splitlines():
            if ('custom_call_target="tpu_custom_call"' in ln
                    and "%ptpu.kda_scan" in ln.split(" = ")[0]):
                assert "f32[4,64,128,128]" in ln.split(" custom-call(")[
                    0], ln[:400]
        # 64 query heads on 8 key/value heads of 128, K and V at their own
        assert _assert_bfloat16_operands_and_lengths(text, batch)[0][1][
            1:] == ["bf16[4,4096,8192]"] + ["bf16[4,4096,1024]"] * 2
        # beside the weights, the slots' entries and the step
        assert weights + slabs + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes) < 15.5 * 2**30, mem
        assert mem.temp_size_in_bytes < 4.4 * 2**30, mem.temp_size_in_bytes
        return
    assert steps_traced() == (steps[0] + 3, steps[1])
    assert sorted(c for c in calls if c.startswith("ptpu.")) == [
        "ptpu.decode_attn_grouped"] + ["ptpu.kda_step"] * 3, calls
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln
               and "%ptpu.kda_step" in ln.split(" = ")[0]]
    for ln in kernels:
        assert ln.count("f32[32,64,128,128]{3,2,1,0") >= 2, ln[:600]
        assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(0, \{\}\)\}",
                         ln), ln[:900]
    assert n_cache == len(spec) == 14
    assert mem.alias_size_in_bytes >= slabs
    # a matrix state is written by one call a layer, never copied
    # (the 4096 x 8192 projections hold as many elements, and the
    # compiler rounds one to bfloat16 by a `copy`: told apart by type)
    ops = [op for op, _, _ in _whole_slab_ops(text, (32, 64, 128, 128))]
    assert ops.count("get-tuple-element") == 3, ops
    assert not re.search(r"= f32\[32,64,128,128\]\S* copy\(", text)
    # the slab: read and appended to where it lies
    moved = [name for op, name, changed in _whole_slab_ops(
        text, (32, 8192, 8, 128)) if op == "copy" or changed]
    assert not moved, moved
    assert mem.temp_size_in_bytes < 100 * 2**20, mem.temp_size_in_bytes


def test_ling3_prefill_holds_one_scan_kernel_a_kda_layer(one_chip,
                                                         monkeypatch):
    """The Ling-3.0-flash cell's widest admission (8 prompts of the
    2,048 bucket; `benchmark/configs/ling-3.0-flash.json`), bound for a
    TPU: one `ptpu.kda_scan` call a KDA layer (five), each with the
    final state at its interface shape in its own line, beside the
    latent layer's one flash forward; the counter says `kernel` five
    times and `lax` never. (The program's temporaries are what they were,
    3.14 GiB: the experts' sorted pairs and the padded heads hold them,
    not the scans' factors, which lived a block of chunks at a time.)"""
    from paddle_tpu import observability as obs
    from test_tpu_compile_cells import _cell_predictor

    pred = _cell_predictor("ling3_lm", "ling-3.0-flash.json", monkeypatch)
    step_fn, feeds, state, _ = _serving_step(pred, "prefill", 8, 2048,
                                             one_chip)

    k0, l0 = _kda_scans_traced("factored")
    guarded = _kda_scans_traced("guarded")
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    assert _kda_scans_traced("factored") == (k0 + 5, l0)
    assert _kda_scans_traced("guarded") == guarded
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and "%ptpu." in ln.split(" = ")[0]]
    scans = [ln for ln in calls if "%ptpu.kda_scan" in ln.split(" = ")[0]]
    assert len(scans) == 5, [ln.split(" = ")[0] for ln in calls]
    assert len(calls) == 6, [ln.split(" = ")[0] for ln in calls]  # + flash
    for ln in scans:
        assert "f32[8,32,128,128]" in ln.split(" custom-call(")[0], ln[:400]
    assert mem.temp_size_in_bytes < 3.3 * 2**30, mem.temp_size_in_bytes


_DOTS3_CASES = [
    # id, kind, batch, seq: the dots3-note-prev serving cell's own
    # programs (benchmark/configs/dots3-note-prev.json: 5 layers at
    # published widths, 8 of 256 experts held, 32 slots of 16,384
    # positions; the largest admission is one prompt of the 16,384 bucket)
    ("decode-32x16384", "decode", 32, 16384),
    ("prefill-1x16384", "prefill", 1, 16384),
]


@pytest.mark.parametrize("kind,batch,seq", [c[1:] for c in _DOTS3_CASES],
                         ids=[c[0] for c in _DOTS3_CASES])
def test_dots3_serving_step_compiles(one_chip, monkeypatch, kind, batch,
                                     seq):
    """The programs DecodePredictor builds for the dots3-note-prev cell
    (two full layers: 128 heads of 128 + 64 query/key and 128 value
    channels over a latent row of 576 floats, under an indexer of 64
    heads of 128 that keeps 2,048 rows; three sliding layers: 64 heads
    of 192 + 64 and 128 over a ring of 513 rows of 1,088; a sigmoid
    router with a bias over 256 experts of width 1,536, 8 held; an
    untied head over 19,008 ids): they compile for a v5e and fit it
    beside each other. The largest admission holds the flash calls of
    16 heads at a time (two under the indexer's int8 mask, three over
    the window) and NOTHING of all 128 heads' q, k or v, nor a (64, T,
    T) array of per-head index products, nor a sort; the step donates
    its seven cache entries and holds no copy of a slab of 16,384
    positions."""
    from test_tpu_compile_cells import _cell_predictor

    pred = _cell_predictor("dots3_lm", "dots3-note-prev.json", monkeypatch)
    step_fn, feeds, state, n_cache = _serving_step(pred, kind, batch, seq,
                                                   one_chip)
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    weights = sum(int(np.prod(s.shape)) * 4 for s in state.values())
    assert 7.28e9 < weights < 7.30e9, weights  # 1.822 B parameters
    text = compiled.as_text()
    spec = pred.cache_spec(32, 16384)
    slabs = sum(e.nbytes for e in spec)
    assert round(slabs / 1e9, 2) == 3.17
    calls = re.findall(r"%([\w.-]+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    # the choice of 2,048 is no sort (the router's top-8 and the routed
    # product's pairs are)
    sorts = [ln for ln in text.splitlines() if " sort(" in ln]
    assert sorts and all("ptpu.moe_" in ln for ln in sorts)
    if kind == "prefill":
        # one call a layer, inside the loop over groups of 16 heads
        assert calls.count("ptpu.dsa_attend") == 2, calls
        assert calls.count("ptpu.latent_ring_attend") == 3, calls
        assert calls.count("ragged-dot-none") == 3 * 4
        # 16 heads padded to 256 channels, never all 128 (or 64); v at
        # its own 128
        assert "bf16[1,16384,4096]" in text  # the kernel's operands
        assert "bf16[1,16384,2048]" in text
        assert "bf16[1,16384,32768]" not in text
        assert "f32[1,16384,128,192]" not in text
        assert "s8[1,32,16384,512]" in text  # the mask, a key block major
        assert "f32[1,64,16384,16384]" not in text
        # beside the weights, the slabs and rings and the step
        assert weights + slabs + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes) < 14.5 * 2**30, mem
        assert mem.temp_size_in_bytes < 3.0 * 2**30, mem.temp_size_in_bytes
        return
    # the attention under the choice: one kernel a full layer, over the
    # transposed view of the latent slab where it lies
    assert [c for c in calls if c.startswith("ptpu.")] == [
        "ptpu.dsa_index_step", "ptpu.dsa_attend_step"] * 2, calls
    assert "f32[32,128,16384]" not in text  # no scores of every row
    assert "bf16[32,16384,128]" not in text  # no rounded copy of the keys
    assert n_cache == len(spec) == 7
    assert mem.alias_size_in_bytes >= slabs
    # (a ring of 71 MB is another matter: the compiler moves each into
    # its faster memory space for the step's fusions, a copy a layer)
    for shape in ((32, 16384, 576), (32, 16384, 128)):
        moved = [name for op, name, changed in _whole_slab_ops(text, shape)
                 if op == "copy"]
        assert not moved, (shape, moved)
    # no expanded K or V: nothing of slots x positions x heads
    assert "f32[32,16384,128," not in text
    assert mem.temp_size_in_bytes < 400 * 2**20, mem.temp_size_in_bytes


_EVABYTE_CASES = [
    # id, kind, batch, seq: the EvaByte serving cell's own programs
    # (benchmark/configs/evabyte.json: 4 layers at published widths, 16
    # slots of 16,384 positions; the widest admissions are one prompt of
    # the 16,384 bucket and four of the 4,096 one)
    ("decode-16x16384", "decode", 16, 16384),
    ("prefill-1x16384", "prefill", 1, 16384),
]


@pytest.mark.parametrize("kind,batch,seq", [c[1:] for c in _EVABYTE_CASES],
                         ids=[c[0] for c in _EVABYTE_CASES])
def test_evabyte_serving_step_compiles(one_chip, monkeypatch, kind, batch,
                                       seq):
    """The programs DecodePredictor builds for the EvaByte cell (32
    heads of 128 on as many key/value heads, a window of 2,048 and
    chunks of 16, an MLP of 11,008, a head of 320 ids): they compile for
    a v5e and fit it beside each other. A prefill holds one causal flash
    call over every window's own rows (the windows folded into the
    batch) and one a window over the summaries it sees, seven at 16,384
    rows, all `ptpu.eva_prefill` on bfloat16 operands, and no (T, T)
    mask; the step donates its eight entries, attends each by ONE call
    of `ptpu.eva_attn` (the two-pass body with a start) and holds no
    copy of an entry: the rows a chunk is pooled from are sliced a slot
    at a time (under `vmap` the gather made the compiler lay every entry
    out anew: eight copies of 805 MB a step)."""
    from test_tpu_compile_cells import _cell_predictor

    pred = _cell_predictor("evabyte_lm", "evabyte.json", monkeypatch)
    step_fn, feeds, state, n_cache = _serving_step(pred, kind, batch, seq,
                                                   one_chip)
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    weights = sum(int(np.prod(s.shape)) * 4 for s in state.values())
    assert 3.24e9 < weights < 3.26e9, weights  # 812.2 M parameters
    text = compiled.as_text()
    spec = pred.cache_spec(16, 16384)
    slabs = sum(e.nbytes for e in spec)
    assert round(slabs / 1e9, 2) == 6.44
    calls = re.findall(r"%([\w.-]+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    if kind == "prefill":
        # a layer: the folded call and windows 1..7 over their summaries
        assert calls.count("ptpu.eva_prefill") == 4 * 8, calls
        assert "bf16[8,2048,4096]" in text      # the folded operands
        assert "bf16[1,896,4096]" in text       # window 7's 896 summaries
        assert "f32[1,32,16384,16384]" not in text and "s8[" not in text
        assert "f32[1,3072,32,128]" in text     # an entry as a step finds it
        # beside the weights, the entries and the step
        assert weights + slabs + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes) < 14.5 * 2**30, mem
        assert mem.temp_size_in_bytes < 2.5 * 2**30, mem.temp_size_in_bytes
        return
    assert [c for c in calls if c.startswith("ptpu.")] == [
        "ptpu.eva_attn"] * 4, calls
    assert n_cache == len(spec) == 8
    assert mem.alias_size_in_bytes >= slabs
    ops = _whole_slab_ops(text, (16, 3072, 32, 128))
    assert ops and not [name for op, name, changed in ops
                        if op in ("copy", "transpose") or changed], ops
    assert mem.temp_size_in_bytes < 100 * 2**20, mem.temp_size_in_bytes
