"""The streamed two-pass decode kernel (`ops/decode_stream.py`): ONE
body behind `ptpu.decode_attn_grouped`, `ptpu.diff_attn_rows` and
`ptpu.mla_latent_attn`, so what is a property of that body is tested
here once: its index maps as plain functions, and every view in
interpret mode against that view's own exact lax path (the parity cases
the three op files used to hold, case for case, and each view at the
lengths around a block's edges and past the slab). Where K and V are
ONE array that fits in VMEM the body fetches each live block once (PR
55): which calls do, by shape and identity alone, and that their
output is the two-read body's bit for bit.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.ops import decode_stream as DS
from paddle_tpu.ops import diff_attn as D
from paddle_tpu.ops import eva
from paddle_tpu.ops import kv_cache as KV
from paddle_tpu.ops import mla


# -- the index maps -------------------------------------------------------------

ROWS, N_BLK = 32, 4   # a slot of 128 positions in blocks of 32
_LENGTHS = [0, 1, ROWS - 1, ROWS, ROWS + 1, 2 * ROWS, N_BLK * ROWS - 1,
            N_BLK * ROWS]


def _live_blocks(length):
    return -(-length // ROWS)


@pytest.mark.parametrize("length", _LENGTHS)
def test_k_block_never_passes_the_slots_last_live_block(length):
    """Pass one reads blocks 0 .. last live, each once, then that block
    again (an index that did not change is not copied again)."""
    lens = np.array([7, length], np.int32)
    got = [int(DS.live_block(j, lens, 1, ROWS)) for j in range(2 * N_BLK)]
    last = max(_live_blocks(length), 1) - 1
    assert got == [min(j, last) for j in range(2 * N_BLK)]
    assert int(DS.last_block(lens, 1, ROWS)) == last
    assert max(got) * ROWS < max(length, 1)  # no block wholly dead is read


@pytest.mark.parametrize("length", _LENGTHS)
def test_v_block_waits_at_block_0_until_the_second_pass(length):
    lens = np.array([length, 99], np.int32)
    got = [int(DS.second_pass_block(j, lens, 0, ROWS, N_BLK))
           for j in range(2 * N_BLK)]
    last = max(_live_blocks(length), 1) - 1
    assert got[:N_BLK] == [0] * N_BLK
    assert got[N_BLK:] == [min(j, last) for j in range(N_BLK)]


def test_an_empty_slot_fetches_block_0_once_a_pass():
    """Length 0: both maps stay at block 0 through all 2 n_blk steps, so
    each operand's one copy is its first; nothing is computed on it."""
    lens = np.array([0], np.int32)
    steps = range(2 * N_BLK)
    assert {int(DS.live_block(j, lens, 0, ROWS)) for j in steps} == {0}
    assert {int(DS.second_pass_block(j, lens, 0, ROWS, N_BLK))
            for j in steps} == {0}


def test_the_rule_is_the_views_numbers_and_the_type():
    """`block_positions`: the largest power of two of positions within
    `most` that divides the slot's, at least `least`; None for a layout
    that fills no whole tiles, a type not 32 bits wide, scores past the
    budget."""
    def view(**kw):
        return DS.StreamView("v", **{"seq": 4096, "dtype": "float32",
                                     "most": 512, **kw})

    assert DS.block_positions(view()) == 512
    assert DS.block_positions(view(most=DS.rows_within(32 * 128 * 4))) == 128
    assert DS.block_positions(view(seq=96)) == 32
    assert DS.block_positions(view(seq=192, least=128)) is None
    assert DS.block_positions(view(seq=100)) is None
    assert DS.block_positions(view(dtype="bfloat16")) is None
    assert DS.block_positions(view(whole_tiles=False)) is None
    assert DS.block_positions(view(score_rows=256)) == 512
    assert DS.block_positions(view(score_rows=257)) is None


def test_the_kept_rows_rule_is_the_views_numbers():
    """`kept_vmem_bytes`: V's part of every position of a slot, the
    slot's scores and K's two blocks, under 64 MiB; None past it, and
    where no kernel attends the view at all."""
    f32 = jnp.float32
    mistral = mla.latent_view(16384, 32, 320, 256, f32)
    assert DS.kept_vmem_bytes(mistral) == 4 * (
        256 * 16384 + 32 * 16384 + 2 * 320 * 1024)       # 21.5 MB
    ling = mla.latent_view(16384, 32, 576, 512, f32)
    assert DS.kept_vmem_bytes(ling) == 4 * (
        512 * 16384 + 32 * 16384 + 2 * 576 * 1024)       # 40.4 MB
    assert DS.kept_vmem_bytes(ling) < DS._KEPT_VMEM_CAP == 64 * 2**20
    # the same rows on slots of 32,768: 71.3 MB
    assert DS.kept_vmem_bytes(mla.latent_view(32768, 32, 576, 512,
                                              f32)) is None
    assert DS.kept_vmem_bytes(mla.latent_view(16384, 32, 320, 256,
                                              jnp.bfloat16)) is None
    assert DS.kept_vmem_bytes(mistral, rows=2048) == (
        DS.kept_vmem_bytes(mistral) + 4 * 2 * 320 * 1024)


# -- which calls read once -----------------------------------------------------------

def _sd(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


def _latent_call(b, s, h, row, rank, two_arrays=False):
    if two_arrays:
        view = mla.latent_view(s, h, row, rank, jnp.float32)
        return (lambda q, k, v, n: DS.stream_attend(view, n, q, k, v),
                (_sd(b, h, row), _sd(b, s, row), _sd(b, s, row),
                 _sd(b, dtype=jnp.int32)))
    return (lambda q, c, n: mla.pallas_latent_attend(q, c, n, rank),
            (_sd(b, h, row), _sd(b, s, row), _sd(b, dtype=jnp.int32)))


# id -> (fn, its arguments' shapes, positions a slot, positions a block,
#        floats of a slot kept in VMEM: 0 where each block is read a pass)
_BODY_CASES = {
    "latent-mistral-cell": _latent_call(32, 16384, 32, 320, 256) + (
        16384, 1024, 256 * 16384),
    "latent-ling-cell": _latent_call(64, 16384, 32, 576, 512) + (
        16384, 1024, 512 * 16384),
    "latent-smoke": _latent_call(8, 2048, 8, 320, 256) + (
        2048, 1024, 256 * 2048),
    # kept rows, scores and blocks of 71.3 MB: over the cap
    "latent-slots-of-32768": _latent_call(4, 32768, 32, 576, 512) + (
        32768, 1024, 0),
    # the same numbers in two arrays: nothing says V lies inside K
    "latent-two-arrays": _latent_call(2, 2048, 8, 320, 256, True) + (
        2048, 1024, 0),
    "grouped": (
        lambda q, k, v, n: KV.pallas_decode_attention(q, k, v, n),
        (_sd(2, 1, 48, 128), _sd(2, 1024, 8, 128), _sd(2, 1024, 8, 128),
         _sd(2, dtype=jnp.int32)), 1024, 512, 0),
    "uneven": (
        lambda q, k, v, n: KV.pallas_decode_attention_uneven(q, k, v, n, 4),
        (_sd(2, 1, 64, 192), _sd(2, 1024, 4 * 192), _sd(2, 1024, 4 * 128),
         _sd(2, dtype=jnp.int32)), 1024, 512, 0),
    "rows": (
        lambda q, k, v, n: D.pallas_attend_rows(q, k, v, n, 0.125),
        (_sd(2, 1, 16, 128), _sd(2, 1024, 512), _sd(2, 1024, 512),
         _sd(2, dtype=jnp.int32)), 1024, 512, 0),
    "eva": (
        lambda q, k, v, n: DS.stream_attend(
            eva.eva_view(1024, 8, 128, jnp.float32), n, q, k, v, starts=n),
        (_sd(2, 1, 8, 128), _sd(2, 1024, 8, 128), _sd(2, 1024, 8, 128),
         _sd(2, dtype=jnp.int32)), 1024, 512, 0),
}


@pytest.mark.parametrize("fn,avals,s,rows,kept", list(_BODY_CASES.values()),
                         ids=list(_BODY_CASES))
def test_one_array_that_fits_is_streamed_once(fn, avals, s, rows, kept):
    """`v is k` and the shapes choose the body, nothing else: ONE array
    whose V part of a slot fits in VMEM is the call's one streamed
    operand, on a grid of `n_blk` steps a slot, with a scratch of the
    slot's V part and the scoped VMEM raised to hold it; every view
    that hands two arrays, and a slot too large to keep, keeps the grid
    of `2 n_blk` steps and both operands, and asks for no VMEM."""
    call, = _pallas_calls(jax.make_jaxpr(fn)(*avals).jaxpr)
    grid = call.params["grid_mapping"]
    b, n_blk = avals[0].shape[0], s // rows
    streamed = [m for m in grid.block_mappings[:grid.num_inputs]
                if any(getattr(d, "block_size", d) == rows
                       for d in m.block_shape)]
    scratch = [a.shape for a in grid.scratch_avals]
    limit = call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    if kept:
        assert grid.grid == (b, n_blk)
        assert len(streamed) == 1 and grid.num_inputs == 2
        assert int(np.prod(scratch[-1])) == kept and len(scratch) == 5
        assert 4 * kept < limit < 4 * kept + 32 * 2**20
    else:
        assert grid.grid == (b, 2 * n_blk)
        assert len(streamed) == 2 and grid.num_inputs == 3
        assert len(scratch) == 4 and limit is None


# -- every view against its lax path ------------------------------------------------

def _rand(shape, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


def _grouped(lengths, heads, s=128, block=32, ring=False):
    """`heads` query heads on slabs (or, with `ring`, one-block rings)
    of 8 key/value heads of 128: the kernel against
    `decode_attention_reference` (`decode_attn_ring` for a ring, which
    sees `min(lengths, W)` rows)."""
    b = len(lengths)
    q = jnp.asarray(_rand((b, 1, heads, 128), 3 if ring else 0))
    k = jnp.asarray(_rand((b, s, 8, 128), 4 if ring else 1))
    v = jnp.asarray(_rand((b, s, 8, 128), 5 if ring else 2))
    lens = jnp.asarray(lengths, jnp.int32)
    assert DS.block_positions(KV.decode_view(
        s, heads, 8, 128, np.float32, block)) == min(block, s)
    if ring:
        want = KV.decode_attn_ring(q, k, v, lens)
        got = KV.pallas_decode_attention(q, k, v, jnp.minimum(lens, s),
                                         interpret=True)
    else:
        want = KV.decode_attention_reference(q, k, v, lens)
        got = KV.pallas_decode_attention(q, k, v, lens, interpret=True,
                                         block_s=block)
    return got, want, dict(rtol=1e-5, atol=2e-6)


def _rows(lengths, block, s=256, h=16, pairs=4, w=128):
    """`h` paired query heads of `w` on slabs of `s` flat rows of
    `pairs` pair-heads: the kernel against `_attend_rows_lax`."""
    r = np.random.default_rng(7)
    b = len(lengths)
    qp = D.pair_queries(jnp.asarray(
        r.normal(size=(b, 1, h, w // 2)).astype(np.float32)))
    k = jnp.asarray(r.normal(size=(b, s, pairs * w)).astype(np.float32))
    v = jnp.asarray(r.normal(size=(b, s, pairs * w)).astype(np.float32))
    lens = jnp.asarray(lengths, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = D._attend_rows_lax(qp, k, v, lens, 0.125)
        got = D.pallas_attend_rows(qp, k, v, lens, 0.125, block_s=block,
                                   interpret=True)
    return got, want, dict(rtol=1e-5, atol=1e-6)


def _latent(lengths, block, s=512, h=8, row=40, rank=32, seed=5):
    """`h` heads on a latent slab of `row`-float rows, `rank` of them
    summed: the kernel against `_latent_attend_lax`. Rows past a slot's
    length hold garbage (1e6) and the `k_r` columns are large: neither
    may reach the output."""
    r = np.random.default_rng(seed)
    lens = np.asarray(lengths, np.int32)
    b = len(lens)
    slab = r.normal(size=(b, s, row)).astype(np.float32)
    slab[..., rank:] *= 10.0
    for i, n in enumerate(lens):
        slab[i, n:] = 1e6
    q_row = jnp.asarray(r.normal(size=(b, h, row)) * 0.05, jnp.float32)
    slab, lens = jnp.asarray(slab), jnp.asarray(lens)
    want = mla._latent_attend_lax(q_row, slab, lens, rank)
    got = mla.pallas_latent_attend(q_row, slab, lens, rank, block_s=block,
                                   interpret=True)
    assert got.shape == (b, h, rank)
    _same_bits_as_two_reads(
        got, mla.latent_view(s, h, row, rank, slab.dtype, block), lens,
        q_row, slab)
    return got, want, None


def _same_bits_as_two_reads(got, view, lens, q_row, slab, starts=None):
    """`got`, the body's output under ONE array (read once, the V part
    kept in VMEM), is bit for bit what it gives handed K and V as two
    arrays of the same numbers (each live block read a pass)."""
    assert DS.kept_vmem_bytes(view) is not None
    twice = DS.stream_attend(view, lens, q_row, slab,
                             jnp.array(slab, copy=True), True, starts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(twice))


def _latent_ranged(lengths, starts, block, s=512, h=8, row=40, rank=32):
    """The latent view over rows `[starts, lengths)` of a slot, read
    once: against the lax form under that choice of rows, and against
    the two-read body bit for bit."""
    r = np.random.default_rng(9)
    b = len(lengths)
    slab = jnp.asarray(r.normal(size=(b, s, row)).astype(np.float32))
    q_row = jnp.asarray(r.normal(size=(b, h, row)) * 0.05, jnp.float32)
    lens, first = (jnp.asarray(x, jnp.int32) for x in (lengths, starts))
    view = mla.latent_view(s, h, row, rank, slab.dtype, block)
    got = DS.stream_attend(view, lens, q_row, slab, slab, True, first)
    _same_bits_as_two_reads(got, view, lens, q_row, slab, first)
    want = mla._latent_attend_lax(
        q_row, slab, lens, rank,
        chosen=jnp.arange(s)[None, :] >= first[:, None])
    return got, want, None


KS = 512
_UNEQUAL = [int(n) for n in np.random.default_rng(11).integers(0, KS + 1, 32)]


def _mla_decode(lengths, block, monkeypatch):
    """`mla_decode` through the kernel (interpret mode, steered past the
    device rule) against `mla_decode` by the lax path, which the CPU
    takes; the counter names each path."""
    kh, krank, krope, dn, dv = 8, 32, 8, 4, 8
    lens = np.asarray(lengths, np.int32)
    b = len(lens)
    r = np.random.default_rng(5)
    slab = r.normal(size=(b, KS, krank + krope)).astype(np.float32)
    slab[..., krank:] *= 10.0
    for i, n in enumerate(lens):
        slab[i, n:] = 1e6
    q = jnp.asarray(r.normal(size=(b, 1, kh, dn + krope)) * 0.3, jnp.float32)
    w_kvb = jnp.asarray(r.normal(size=(krank, kh * (dn + dv))) * 0.3,
                        jnp.float32)
    slab, lens = jnp.asarray(slab), jnp.asarray(lens)

    def counts():
        return {k["path"]: v for k, v in obs.MLA_TRACES.samples()}

    c0 = counts()
    want = mla.mla_decode(q, slab, lens, w_kvb, 0.37)
    c1 = counts()
    assert c1["absorbed"] - c0.get("absorbed", 0) == 1
    assert c1.get("absorbed_kernel_once", 0) == c0.get(
        "absorbed_kernel_once", 0)
    monkeypatch.setattr(KV, "decode_stream_rows", lambda view: block)
    kernel = mla.pallas_latent_attend
    monkeypatch.setattr(
        mla, "pallas_latent_attend",
        lambda *a: kernel(*a, block_s=block, interpret=True))
    got = mla.mla_decode(q, slab, lens, w_kvb, 0.37)
    c2 = counts()
    # a slab of 512 rows of 40 is kept whole: the one-read body
    assert c2["absorbed_kernel_once"] - c1.get("absorbed_kernel_once",
                                               0) == 1
    assert c2["absorbed"] == c1["absorbed"]
    assert c2.get("absorbed_kernel", 0) == c0.get("absorbed_kernel", 0)
    assert got.shape == want.shape == (b, 1, kh, dv)
    np.testing.assert_array_equal(np.asarray(want)[np.asarray(lens) == 0],
                                  0.0)
    return got, want, None


_IP_S, _IP_BLOCK, _RING_W = 128, 32, 32
_IP_LENGTHS = {
    # 0, 1, one less than / exactly / one more than a block boundary, full
    "edges": [0, 1, _IP_BLOCK - 1, _IP_BLOCK, _IP_BLOCK + 1, _IP_S],
    "second-boundary": [2 * _IP_BLOCK - 1, 2 * _IP_BLOCK, 2 * _IP_BLOCK + 1,
                        _IP_S - 1, 7, 0],
    "full": [_IP_S] * 6,
    "empty": [0] * 6,
}
_RING_LENGTHS = {
    "below": [0, 1, _RING_W - 1, 5],
    "at": [_RING_W] * 4,
    "past": [_RING_W + 1, 2 * _RING_W, 1000, _RING_W + 7],
    "mixed": [7, _RING_W, _RING_W + 1, 0],
}
_MLA_LENGTHS = {
    # live rows a slot (`b` stands for the block)
    "empty": lambda b: [0, 0],
    "one-row": lambda b: [1, 0],
    "edge-1": lambda b: [b - 1, 1],
    "edge": lambda b: [b, 2 * b],
    "edge+1": lambda b: [b + 1, KS - 1],
    "full-slab": lambda b: [KS, KS],
    "past-the-slab": lambda b: [KS + 5, KS],  # read as every row
    "unequal-32": lambda b: _UNEQUAL,
}


def _edges(block, s):
    return [0, 1, block - 1, block, block + 1, s]


# id -> (the slots' lengths, run(lengths, monkeypatch) -> (got, want, tol))
_CASES = {}
for _h in (48, 64, 16):  # 6, 8 and 2 query rows share a key/value head
    for _n, _l in _IP_LENGTHS.items():
        _CASES["grouped-%don8-%s" % (_h, _n)] = (
            _l, lambda l, mp, h=_h: _grouped(l, h))
for _h in (16, 64):  # a ring is a one-block slab of min(lengths, W) rows
    for _n, _l in _RING_LENGTHS.items():
        _CASES["grouped-ring-%don8-%s" % (_h, _n)] = (
            _l, lambda l, mp, h=_h: _grouped(l, h, s=_RING_W, block=512,
                                             ring=True))
for _b in (64, 128, 256):
    # a free slot, one that ends inside a block, at its edge, one past it
    for _l in ((0, 100, 256), (1, 64, 65)):
        _CASES["rows-block%d-%s" % (_b, "-".join(map(str, _l)))] = (
            list(_l), lambda l, mp, b=_b: _rows(l, b))
for _b in (128, 256):
    for _n, _f in _MLA_LENGTHS.items():
        _CASES["latent-mla_decode-block%d-%s" % (_b, _n)] = (
            _f(_b), lambda l, mp, b=_b: _mla_decode(l, b, mp))
# the Ling cell's row (512 + 64), 32 heads
_CASES["latent-row576-rank512"] = (
    [0, 1, 129, 256],
    lambda l, mp: _latent(l, 128, s=256, h=32, row=576, rank=512, seed=6))
# each view around a block's edges, and at a length past the slab
_CASES["grouped-edges"] = (
    _edges(32, 128), lambda l, mp: _grouped(l, 48))
_CASES["rows-edges"] = (_edges(64, 256), lambda l, mp: _rows(l, 64))
_CASES["latent-edges"] = (_edges(128, 512), lambda l, mp: _latent(l, 128))
_CASES["grouped-past-the-slab"] = (
    [128 + 5, 128, 7], lambda l, mp: _grouped(l, 16))
_CASES["rows-past-the-slab"] = (
    [256 + 5, 256, 7], lambda l, mp: _rows(l, 64))
_CASES["latent-past-the-slab"] = (
    [512 + 5, 512, 7], lambda l, mp: _latent(l, 128))
# the one-read body against the two-read one (`_latent` asks for the
# same bits), a length a case and a batch that mixes them
for _n in _edges(128, 512):
    _CASES["latent-once-%d" % _n] = (
        [_n, _n], lambda l, mp: _latent(l, 128, seed=8))
_CASES["latent-once-mixed"] = (
    _edges(256, 512) + _edges(128, 512)[2:5],
    lambda l, mp: _latent(l, 256, seed=8))
_CASES["latent-once-ranged"] = (
    [0, 300, 512, 129, 128, 40],
    lambda l, mp: _latent_ranged(l, [0, 7, 128, 128, 130, 40], 128))


@pytest.mark.parametrize("lengths,run", list(_CASES.values()),
                         ids=list(_CASES))
def test_view_kernel_matches_its_lax_path(monkeypatch, lengths, run):
    """A view's kernel in interpret mode against that view's exact lax
    path: elementwise where the op's own test asked that, and to 1e-5
    of the output's norm (what two passes give; PERF.md, PR 32); zeros
    and finite at length 0; a length past the slab reads as "every
    row"."""
    got, want, tol = run(lengths, monkeypatch)
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_array_equal(got[np.asarray(lengths) == 0], 0.0)
    if tol:
        np.testing.assert_allclose(got, want, **tol)
    err = float(np.linalg.norm(got - want))
    assert err <= 1e-5 * max(float(np.linalg.norm(want)), 1e-30), err

