"""PR-14 decode levers, deep coverage (standalone tier: this file sorts
after the tier-1 870s cutoff — run it directly): PrefixStore semantics
(block-aligned partial hits, byte-bounded LRU eviction, refcount
pinning), speculative server fault tolerance, spec+prefix composition,
ring-attention prefill (single-device structural parity and the true
sequence-parallel chunked path), and decode crash-requeue through the Router
with both levers live (mid-speculation / prefix-shared sequences
re-prefill on a survivor, zero misversioned)."""
from __future__ import annotations

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu import observability as obs
from paddle_tpu.models import transformer as T
from paddle_tpu.serving.decode import (
    DecodeConfig, DecodePredictor, DecodeServer, save_decode_model)
from paddle_tpu.serving.prefix import PrefixStore

V, L, NH, D, DI, ML = 37, 2, 2, 16, 32, 64


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("spec_model"))
    B, S = 2, 16
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = startup.random_seed = 7
    with fluid.program_guard(prog, startup):
        with fluid.unique_name.guard():
            ids = layers.data(name="ids", shape=[B, S], dtype="int64",
                              append_batch_size=False)
            lbl = layers.data(name="lbl", shape=[B, S], dtype="int64",
                              append_batch_size=False)
            loss, _ = T.transformer_lm(
                ids, lbl, V, n_layer=L, n_head=NH, d_model=D, d_inner=DI,
                dropout_rate=0.0, max_len=ML, fused_head=False)
            optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    r = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            x = r.randint(0, V, (B, S)).astype(np.int64)
            exe.run(prog, feed={"ids": x, "lbl": x})
        save_decode_model(d, DecodeConfig(
            vocab_size=V, n_layer=L, n_head=NH, d_model=D, d_inner=DI,
            max_len=ML), exe, scope=scope)
    return d


@pytest.fixture(scope="module")
def pred(model_dir):
    return DecodePredictor(model_dir, draft_n_layer=1)


def _prompts(n, seed=1, lo=3, hi=9):
    r = np.random.RandomState(seed)
    return [r.randint(1, V, r.randint(lo, hi + 1)).astype(np.int64)
            for _ in range(n)]


def _rows(p, scale=1.0):
    """Fake per-layer K/V rows for a length-p prompt."""
    return [np.full((p, NH, D // NH), scale, np.float32)
            for _ in range(2 * L)]


# -- PrefixStore unit semantics -------------------------------------------

def test_store_block_aligned_partial_hits():
    store = PrefixStore(max_bytes=1 << 20, block=4)
    prompt = np.arange(1, 11, dtype=np.int64)  # length 10
    assert store.lookup(prompt) == (None, 0, None, None)
    eid = store.insert(prompt, _rows(10), np.zeros((V,), np.float32))
    assert eid is not None
    # full hit: rows + logits
    got_eid, length, rows, logits = store.lookup(prompt)
    assert (got_eid, length) == (eid, 10) and logits is not None
    assert len(rows) == 2 * L and rows[0].shape[0] == 10
    # a longer prompt sharing the 8-aligned header: partial hit at 8
    longer = np.concatenate([prompt[:8], np.array([30, 31, 32],
                                                  np.int64)])
    got_eid, length, rows, logits = store.lookup(longer)
    assert (got_eid, length) == (eid, 8) and logits is None
    assert rows[0].shape[0] == 8
    # sharing 6 tokens (non-aligned): the hit falls back to the LAST
    # aligned boundary inside the shared span (4)
    odd = np.concatenate([prompt[:6], np.array([33, 34], np.int64)])
    got_eid, length, rows, logits = store.lookup(odd)
    assert (got_eid, length) == (eid, 4) and logits is None
    # nothing shared before the first aligned boundary: a clean miss
    alien = np.array([90, 91, 92, 93, 94, 95], np.int64)
    assert store.lookup(alien)[0] is None


def test_store_aligned_prefix_of_longer_entry_is_not_a_full_hit():
    """Review regression: a prompt that EQUALS a block-aligned prefix
    of a longer cached entry must not surface as a full hit — the
    entry's stored logits belong to the longer prompt's last position.
    It demotes to a partial at the previous boundary (or a miss when
    none exists), and inserting the short prompt's own entry restores
    the true full hit with ITS logits."""
    store = PrefixStore(max_bytes=1 << 20, block=4)
    long_prompt = np.arange(1, 13, dtype=np.int64)  # length 12
    long_logits = np.full((V,), 7.0, np.float32)
    store.insert(long_prompt, _rows(12), long_logits)
    short = long_prompt[:8].copy()  # exactly a block-aligned prefix
    eid, length, rows, logits = store.lookup(short)
    assert logits is None, "longer entry's logits leaked to a short hit"
    assert length == 4 and rows[0].shape[0] == 4  # previous boundary
    # a length-<=block prefix of the longer entry: clean miss, never a
    # zero-length 'partial'
    tiny = long_prompt[:4].copy()
    assert store.lookup(tiny) == (None, 0, None, None)
    # the short prompt's OWN insert is not shadowed by the longer entry
    short_logits = np.full((V,), 3.0, np.float32)
    own = store.insert(short, _rows(8), short_logits)
    eid2, length2, _rows2, logits2 = store.lookup(short)
    assert eid2 == own and length2 == 8
    np.testing.assert_array_equal(logits2, short_logits)


def test_store_insert_copies_rows_not_views():
    """Review regression: entries must COPY the row views sliced from
    batched prefill outputs — storing views pins the whole parent
    array while nbytes accounts only the slice."""
    store = PrefixStore(max_bytes=1 << 20, block=4)
    parent = np.ones((4, 64, NH, D // NH), np.float32)  # big batch buf
    prompt = np.arange(1, 9, dtype=np.int64)
    store.insert(prompt, [parent[0, :8] for _ in range(2 * L)],
                 np.zeros((V,), np.float32))
    parent[:] = -1.0  # mutate the source; stored rows must not follow
    _eid, _l, rows, _lg = store.lookup(prompt)
    assert float(rows[0][0, 0, 0]) == 1.0
    assert not any(r.base is parent for r in rows)


def test_store_eviction_is_lru_and_byte_bounded():
    one = sum(r.nbytes for r in _rows(8)) + V * 4
    store = PrefixStore(max_bytes=int(one * 2.5), block=4)
    prompts = [np.arange(1, 9, dtype=np.int64) + 100 * i
               for i in range(3)]
    for p in prompts:
        store.insert(p, _rows(8), np.zeros((V,), np.float32))
    # byte bound holds: the OLDEST entry evicted
    assert store.bytes <= store.max_bytes
    assert len(store) == 2
    assert store.lookup(prompts[0])[0] is None
    assert store.lookup(prompts[1])[0] is not None
    assert store.lookup(prompts[2])[0] is not None


def test_store_shared_header_survives_one_owners_eviction():
    """Review regression: two entries sharing a block-aligned header
    both own the header's index key — evicting one must not drop the
    key while the survivor's rows can still serve it."""
    header = np.arange(1, 9, dtype=np.int64)      # 8 tokens, block 4
    a = np.concatenate([header, np.array([50, 51, 52, 53], np.int64)])
    b = np.concatenate([header, np.array([60, 61, 62, 63], np.int64)])
    one = sum(r.nbytes for r in _rows(12)) + V * 4
    store = PrefixStore(max_bytes=int(one * 2.5), block=4)
    ea = store.insert(a, _rows(12), np.zeros((V,), np.float32))
    eb = store.insert(b, _rows(12), np.zeros((V,), np.float32))
    # evict A (LRU) under pressure; B stays — A's own full-length key
    # is gone, but its lookup now partial-hits the shared header via B
    store.insert(np.arange(100, 112, dtype=np.int64), _rows(12),
                 np.zeros((V,), np.float32))
    eid_a, len_a = store.lookup(a)[:2]
    assert eid_a == eb and len_a == 8
    # the shared header still partial-hits via B's rows
    probe = np.concatenate([header, np.array([70, 71], np.int64)])
    eid, length, rows, _lg = store.lookup(probe)
    assert eid == eb and length == 8
    assert rows[0].shape[0] == 8


def test_store_refcounted_entries_survive_eviction_pressure():
    one = sum(r.nbytes for r in _rows(8)) + V * 4
    store = PrefixStore(max_bytes=int(one * 1.5), block=4)
    hot = np.arange(1, 9, dtype=np.int64)
    eid = store.insert(hot, _rows(8), np.zeros((V,), np.float32))
    store.acquire(eid)  # a live sequence decodes from this prefix
    # pressure: two more inserts would evict it were it unreferenced
    for i in (1, 2):
        store.insert(hot + 100 * i, _rows(8),
                     np.zeros((V,), np.float32))
    assert store.lookup(hot)[0] == eid, \
        "a referenced entry must not be evicted"
    store.release(eid)
    # released -> the next pressure round may reclaim it
    store.insert(hot + 300, _rows(8), np.zeros((V,), np.float32))
    assert store.bytes <= store.max_bytes


def test_store_oversized_entry_is_refused():
    store = PrefixStore(max_bytes=64, block=4)
    assert store.insert(np.arange(1, 9, dtype=np.int64), _rows(8),
                        np.zeros((V,), np.float32)) is None
    assert store.bytes == 0


# -- speculative serving: composition + fault tolerance -------------------

def test_spec_and_prefix_compose_lossless(pred):
    shared = _prompts(1, seed=31, lo=8, hi=8)[0]
    singles = _prompts(3, seed=32)
    want_shared = pred.generate([shared], max_new_tokens=6)[0]
    want_single = pred.generate(singles, max_new_tokens=6)
    srv = DecodeServer(pred, slots=2, max_seq=32, max_new_tokens=6,
                       speculative=True, spec_k=2, prefix_cache=True)
    srv.start()
    futs = [srv.submit((shared,)) for _ in range(4)]
    futs += [srv.submit((p,)) for p in singles]
    got = [f.result(timeout=300)[0] for f in futs]
    srv.stop()
    assert srv.prefill_executions <= 1 + len(singles)
    for g in got[:4]:
        np.testing.assert_array_equal(g, want_shared)
    for g, w in zip(got[4:], want_single):
        np.testing.assert_array_equal(g, w)


def test_spec_server_survives_verify_failure(model_dir):
    """An injected verify-step failure fails the affected futures,
    releases the slots, and the loop keeps serving — the PR-9 step-
    failure contract extended to speculative rounds."""
    p = DecodePredictor(model_dir, draft_n_layer=1)
    boom = {"armed": True}
    real_acquire = p.acquire

    def flaky_acquire(kind, batch, seq, strategy=None, **kw):
        exe, fetch = real_acquire(kind, batch, seq, strategy, **kw)
        if kind != "verify":
            return exe, fetch

        def wrapped(feeds, state):
            if boom.pop("armed", False):
                raise RuntimeError("injected verify failure")
            return exe(feeds, state)

        return wrapped, fetch

    p.acquire = flaky_acquire
    srv = DecodeServer(p, slots=2, max_seq=32, max_new_tokens=4,
                       speculative=True, spec_k=2, prewarm=False)
    srv.start()
    prompts = _prompts(2, seed=33)
    futs = [srv.submit((pr,)) for pr in prompts]
    with pytest.raises(RuntimeError, match="injected verify failure"):
        futs[0].result(timeout=120)
    # the loop survived: fresh requests still serve end to end
    out, = srv.submit((prompts[0],)).result(timeout=120)
    srv.stop()
    want = DecodePredictor(model_dir).generate(
        [prompts[0]], max_new_tokens=4)[0]
    np.testing.assert_array_equal(out, want)


def test_predictor_speculative_matches_greedy_with_eos(pred, model_dir):
    """Predictor-level lossless pin, including early-eos truncation and
    a full-depth draft (which must accept everything the target
    emits)."""
    prompts = _prompts(3, seed=24)
    plain = pred.generate(prompts, max_new_tokens=8)
    spec = pred.generate(prompts, max_new_tokens=8, speculative=True,
                         spec_k=3)
    for g, w in zip(spec, plain):
        np.testing.assert_array_equal(g, w)
    eos = int(plain[0][3])
    pe = pred.generate(prompts, max_new_tokens=8, eos_id=eos)
    se = pred.generate(prompts, max_new_tokens=8, speculative=True,
                       spec_k=3, eos_id=eos)
    for g, w in zip(se, pe):
        np.testing.assert_array_equal(g, w)
    full = DecodePredictor(model_dir, draft_n_layer=L)
    sf = full.generate(prompts, max_new_tokens=8, speculative=True,
                       spec_k=2)
    for g, w in zip(sf, plain):
        np.testing.assert_array_equal(g, w)


def test_prefix_extension_failure_fails_batch_and_keeps_serving(
        model_dir):
    """Review regression: a verify call that dies during suffix
    EXTENSION follows the step-failure contract (the donated slabs are
    not reusable on device backends) — the extension job's future
    fails, the loop hands back fresh slabs and keeps serving."""
    p = DecodePredictor(model_dir, draft_n_layer=1)
    boom = {"armed": False}
    real_acquire = p.acquire

    def flaky_acquire(kind, batch, seq, strategy=None, **kw):
        exe, fetch = real_acquire(kind, batch, seq, strategy, **kw)
        if kind != "verify":
            return exe, fetch

        def wrapped(feeds, state):
            if boom.pop("armed", False):
                raise RuntimeError("injected extension failure")
            return exe(feeds, state)

        return wrapped, fetch

    p.acquire = flaky_acquire
    srv = DecodeServer(p, slots=2, max_seq=48, max_new_tokens=4,
                       prefix_cache=True, prewarm=False)
    srv.start()
    header = np.arange(1, 17, dtype=np.int64)
    srv.submit((header,)).result(timeout=120)  # seed the store
    boom["armed"] = True
    suffixed = np.concatenate([header, np.array([5, 9], np.int64)])
    with pytest.raises(RuntimeError, match="injected extension failure"):
        srv.submit((suffixed,)).result(timeout=120)
    # the loop survived with fresh slabs: the same prompt serves now
    out, = srv.submit((suffixed,)).result(timeout=120)
    srv.stop()
    want = DecodePredictor(model_dir).generate(
        [suffixed], max_new_tokens=4)[0]
    np.testing.assert_array_equal(out, want)


def test_draft_n_layer_zero_is_rejected_not_defaulted(model_dir):
    """Review regression: draft_n_layer=0 must hit the range check, not
    silently fall back to the half-depth default."""
    with pytest.raises(ValueError, match="draft_n_layer"):
        DecodePredictor(model_dir, draft_n_layer=0)


def test_prefix_only_server_validates_spec_k(pred):
    """Review regression: a prefix_cache-only server sizes its
    suffix-extension window off spec_k — spec_k=0 must fail fast at
    the constructor, not as a cryptic graph-build error mid-admission."""
    with pytest.raises(ValueError, match="spec_k"):
        DecodeServer(pred, slots=2, max_seq=32, prefix_cache=True,
                     speculative=False, spec_k=0)


def test_spec_acceptance_counters_track_rounds(pred):
    p0 = obs.DECODE_SPEC_PROPOSED.value()
    a0 = obs.DECODE_SPEC_ACCEPTED.value()
    pred.generate(_prompts(2, seed=34), max_new_tokens=8,
                  speculative=True, spec_k=3)
    proposed = obs.DECODE_SPEC_PROPOSED.value() - p0
    accepted = obs.DECODE_SPEC_ACCEPTED.value() - a0
    assert proposed > 0
    assert 0 <= accepted <= proposed


# -- ring-attention long-context prefill ----------------------------------

def test_ring_prefill_structural_parity(model_dir):
    """transformer_lm_prefill(use_ring_attention=True) on one device
    (exact-attention fallback) must match the dense prefill: same
    logits (rtol — different attention kernels), same greedy tokens,
    and decode continues correctly from the ring-prefilled slabs."""
    dense = DecodePredictor(model_dir)
    ring = DecodePredictor(model_dir, ring_prefill_min_seq=16)
    prompts = _prompts(3, seed=35, lo=12, hi=12)
    want = dense.generate(prompts, max_new_tokens=8)
    got = ring.generate(prompts, max_new_tokens=8)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    # the ring predictor really built a different prefill program: its
    # executables landed under their own signatures
    ring_kinds = {k for k in ring._compiled if k[0] == "prefill"}
    assert any(k[-1] for k in ring_kinds), \
        "no ring-built prefill signature was compiled"
    # logits parity, direct: one prefill call each way
    toks = np.zeros((1, 16), np.int64)
    toks[0, :12] = prompts[0][:12]
    lens = np.array([12], np.int32)
    dexe, _ = dense.acquire("prefill", 1, 16)
    rexe, _ = ring.acquire("prefill", 1, 16)
    dl = np.asarray(dexe({"tokens": toks, "lengths": lens},
                         dense._state)[0])
    rl = np.asarray(rexe({"tokens": toks, "lengths": lens},
                         ring._state)[0])
    np.testing.assert_allclose(rl, dl, rtol=2e-5, atol=1e-5)


def test_ring_prefill_sequence_parallel_mesh():
    """The true long-context path: the ring prefill under an sp mesh
    matches the single-device prefill."""
    from paddle_tpu.parallel import (ParallelExecutor, make_mesh,
                                     seq_parallel_plan)

    B, S, vocab = 2, 32, 64
    feed = {"tokens": np.random.RandomState(5).randint(
                0, vocab, (B, S)).astype(np.int64),
            "lengths": np.full((B,), S, np.int32)}

    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 13
        scope = fluid.Scope()
        with fluid.scope_guard(scope), fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                tokens = layers.data(name="tokens", shape=[B, S],
                                     dtype="int64",
                                     append_batch_size=False)
                lengths = layers.data(name="lengths", shape=[B],
                                      dtype="int32",
                                      append_batch_size=False)
                logits, _caches = T.transformer_lm_prefill(
                    tokens, lengths, vocab, n_layer=2, n_head=2,
                    d_model=16, d_inner=32, max_len=S,
                    use_ring_attention=True)
        return main, startup, scope, logits

    main, startup, scope, logits = build()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ref = np.asarray(exe.run(main, feed=feed,
                                 fetch_list=[logits])[0])
    mesh = make_mesh([4], ("sp",), devices=jax.devices()[:4])
    main, startup, scope, logits = build()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pexe = ParallelExecutor(
            loss_name=logits.name, main_program=main, scope=scope,
            mesh=mesh, plan=seq_parallel_plan(mesh, sp_axis="sp",
                                              batch_axes=()))
        got = np.asarray(pexe.run(feed=feed, fetch_list=[logits])[0])
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


# The fleet crash-requeue variant with both levers live rides in
# tests/test_traffic_fleet.py (the chaos-harness home), per ISSUE 14.
