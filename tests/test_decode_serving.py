"""KV-cache decode serving (serving/decode.py): incremental-vs-full
parity, slab bucketing + AOT warm start, sampling strategies, the
continuous-batching DecodeServer, decode observability, the Router
fleet path (zero-drop drain_restart over in-flight decode sequences),
and the ops-layer beam-search strategy — including parity against
contrib's BeamSearchDecoder on a small seq2seq."""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.models import transformer as T
from paddle_tpu.serving.decode import (
    DecodeConfig, DecodePredictor, DecodeServer, save_decode_model,
    _pow2_bucket)

V, L, NH, D, DI, ML = 37, 2, 2, 16, 32, 64


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    """A tiny trained LM exported for decode serving, shared module-wide
    (every test reads; none mutates the export)."""
    d = str(tmp_path_factory.mktemp("decode_model"))
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
    return DecodePredictor(model_dir)


def _prompts(n, seed=1, lo=3, hi=9):
    r = np.random.RandomState(seed)
    return [r.randint(1, V, r.randint(lo, hi + 1)).astype(np.int64)
            for _ in range(n)]


def _full_forward_greedy(pred, prompts, steps):
    """Reference rollout: one full prefill forward per generated token
    (greedy) — the O(T^2) path the KV cache replaces."""
    b = len(prompts)
    bb = _pow2_bucket(b)
    s = _pow2_bucket(max(len(p) for p in prompts) + steps, floor=16)
    tokens = np.zeros((bb, s), np.int64)
    lens = np.ones((bb,), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
        lens[i] = len(p)
    pexe, _ = pred.acquire("prefill", bb, s)
    out = [[] for _ in range(b)]
    rows = np.arange(bb)
    for _ in range(steps):
        outs = pexe({"tokens": tokens, "lengths": lens}, pred._state)
        nxt = np.asarray(outs[0]).argmax(axis=1)
        for i in range(b):
            out[i].append(int(nxt[i]))
        tokens[rows, np.minimum(lens, s - 1)] = nxt
        lens = np.minimum(lens + 1, s - 1)
    return [np.asarray(o, np.int64) for o in out]


# -- DecodePredictor ------------------------------------------------------

def test_export_dir_serves_plain_predictor(model_dir):
    """The exported dir stays a normal inference model: the plain
    Predictor loads and serves the prefill graph."""
    from paddle_tpu.inference import Predictor

    p = Predictor(model_dir)
    assert p.feed_names == ["tokens", "lengths"]
    # the canonical export shape: batch 1 x min(max_len, 128) tokens
    toks = np.zeros((1, ML), np.int64)
    toks[0, :4] = [5, 3, 9, 2]
    (logits,) = p.run({"tokens": toks,
                       "lengths": np.array([4], np.int32)})
    assert logits.shape == (1, V)
    assert os.path.exists(os.path.join(model_dir, "__decode__.json"))


def test_incremental_decode_matches_full_forward(pred):
    """THE contract: N decode steps against the cache produce exactly
    the tokens N full-prefix forwards produce (greedy both sides)."""
    prompts = _prompts(3)
    steps = 10
    got = pred.generate(prompts, max_new_tokens=steps)
    want = _full_forward_greedy(pred, prompts, steps)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_generate_eos_stops_row_early(pred):
    prompts = _prompts(2, seed=2)
    base = pred.generate(prompts, max_new_tokens=8)
    eos = int(base[0][3])  # stop row 0 at its 4th generated token
    got = pred.generate(prompts, max_new_tokens=8, eos_id=eos)
    assert len(got[0]) <= 4 and got[0][-1] == eos
    # the other row is untouched unless it also emits eos
    stop1 = np.where(base[1] == eos)[0]
    want1 = base[1][:stop1[0] + 1] if len(stop1) else base[1]
    np.testing.assert_array_equal(got[1], want1)


def test_sampling_strategies_determinism(pred):
    prompts = _prompts(2, seed=3)
    a = pred.generate(prompts, max_new_tokens=6, strategy="topk", seed=5)
    b = pred.generate(prompts, max_new_tokens=6, strategy="topk", seed=5)
    c = pred.generate(prompts, max_new_tokens=6, strategy="topp", seed=5)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)  # same seed -> same tokens
    for row in a + c:
        assert row.min() >= 0 and row.max() < V


def test_warm_start_compiles_nothing(model_dir, pred):
    """A fresh process-equivalent (new DecodePredictor over the same
    dir) must AOT-load every executable the first predictor compiled:
    zero traces on the warm path (the PR-5 story extended to decode)."""
    prompts = _prompts(3)
    pred.generate(prompts, max_new_tokens=10)  # ensure sigs on disk
    p2 = DecodePredictor(model_dir)
    p2.generate(prompts, max_new_tokens=10)
    assert p2.traces == 0


@pytest.fixture(scope="module")
def hybrid_dir(tmp_path_factory):
    """The tiny state-space / attention hybrid of test_hybrid_decode.py."""
    import test_hybrid_decode as H

    return H.export_hybrid(str(tmp_path_factory.mktemp("hybrid_model")),
                           H.seeded_weights())


@pytest.mark.parametrize("case,kv_dtype,reordered", [
    ("opt", "float32", True), ("opt", "int8", True),
    ("hybrid", "float32", False)],
    ids=["opt-float32", "opt-int8", "hybrid"])
def test_decode_step_hands_each_cache_entry_back_in_fetch_order(
        case, kv_dtype, reordered, request, monkeypatch):
    """The order `acquire`'s callers see is a contract: `fetch_names` is
    what the graph builder gave, and `outs[2:]` zipped with
    `cache_spec`'s names is each entry's OWN update, whatever order the
    step was traced in (serving/decode.py `_pairing_order`: OPT's
    updates are traced sorted by their feeds' names, so that on a chip
    each donated slab comes back in its own buffer; the hybrid's spec
    sorts already and its executable is handed out bare). Here on the
    CPU nothing is donated: this pins the re-indexing. The AOT key
    follows the traced order: a predictor that traces in the fetch
    order must not load the executable of one that does not."""
    from paddle_tpu.framework.trace import RngStream, trace_block
    from paddle_tpu.serving import decode as decode_mod

    mdir = request.getfixturevalue(
        "model_dir" if case == "opt" else "hybrid_dir")
    slots, seq = 4, 32
    p = DecodePredictor(mdir)
    spec = p.cache_spec(slots, seq, kv_dtype)
    step = p._step("decode", slots, seq, "greedy", kv_dtype=kv_dtype)
    exe, fetch_names = p.acquire("decode", slots, seq, kv_dtype=kv_dtype)
    assert fetch_names == p._build("decode", slots, seq, "greedy",
                                   kv_dtype=kv_dtype)[2]
    assert (step.traced != fetch_names) == reordered
    assert isinstance(exe, decode_mod._InFetchOrder) == reordered
    assert sorted(step.traced) == sorted(fetch_names)
    assert "HloModule" in exe.as_text()   # the executable's own surface

    # every entry a constant of its own; one step appends one row
    lens = np.array([3, 5, 1, 7], np.int32)
    feeds = {"tokens": np.array([[4], [9], [2], [7]], np.int64),
             "lengths": lens, "seed": np.zeros((1,), np.int64)}
    if p.config.positions:
        feeds["positions"] = lens.reshape(slots, 1).astype(np.int64)
    for j, e in enumerate(spec):
        feeds[e.name] = jnp.full(e.shape, j + 2, e.dtype)
    outs = exe(feeds, p._state)
    assert len(outs) == 2 + len(spec) == len(fetch_names)
    # what each fetch IS, read from the traced program by its name
    env = dict(p._state)
    env.update(feeds)
    trace_block(step.program.global_block(), env,
                RngStream(jax.random.PRNGKey(0)))
    for name, got in zip(fetch_names, outs):
        np.testing.assert_allclose(np.asarray(got), np.asarray(env[name]),
                                   rtol=1e-5, atol=1e-6, err_msg=name)
    old_rows = np.ones((slots, seq), bool)
    old_rows[np.arange(slots), lens] = False
    for j, (e, got) in enumerate(zip(spec, outs[2:])):
        got = np.asarray(got)
        assert got.shape == e.shape and str(got.dtype) == e.dtype, e.name
        if e.per_position:  # its own constant outside the appended row
            assert (got[old_rows] == j + 2).all(), e.name
            assert (got[~old_rows] != j + 2).any(), e.name

    # a predictor that traces in the callers' order (the programs of
    # before PR 27) is another key: it compiles, where a twin loads
    twin = DecodePredictor(mdir)
    twin.acquire("decode", slots, seq, kv_dtype=kv_dtype)
    assert twin.traces == 0
    real = decode_mod._pairing_order
    monkeypatch.setattr(
        decode_mod, "_pairing_order",
        lambda feeds, fetches, names: (
            list(fetches), None, real(feeds, fetches, names)[2]))
    other = DecodePredictor(mdir)
    oexe, _ = other.acquire("decode", slots, seq, kv_dtype=kv_dtype)
    assert other.traces == (1 if reordered else 0)
    for want, got in zip(outs, oexe(feeds, other._state)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_signature_count_stays_bucketed(pred):
    """1..4 prompts of assorted lengths share ONE (batch-bucket, slab-
    bucket) signature set — the pow2 discipline that bounds compiles."""
    before = dict(pred._compiled)
    outs = pred.generate(_prompts(3, seed=4, lo=3, hi=5),
                         max_new_tokens=10)
    assert len(outs) == 3
    pred.generate(_prompts(4, seed=5, lo=3, hi=5), max_new_tokens=9)
    new_keys = set(pred._compiled) - set(before)
    # both calls: batch bucket 4, slab bucket 16 -> at most one prefill
    # + one decode signature added beyond what the fixture already has
    assert all(k[1] == 4 and k[2] == 16 for k in new_keys), new_keys


# -- DecodeServer ---------------------------------------------------------

def test_server_continuous_matches_generate(pred):
    prompts = _prompts(6, seed=6)
    want = pred.generate(prompts, max_new_tokens=6)
    srv = DecodeServer(pred, slots=2, max_seq=32, max_new_tokens=6)
    srv.start()
    futs = [srv.submit((p,)) for p in prompts]
    got = [f.result(timeout=300)[0] for f in futs]
    srv.stop()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    # continuous admission actually happened: more sequences than slots
    assert max(srv.step_active_counts, default=0) <= 2


def test_server_static_mode_matches(pred):
    prompts = _prompts(5, seed=7)
    want = pred.generate(prompts, max_new_tokens=5)
    srv = DecodeServer(pred, slots=2, max_seq=32, max_new_tokens=5,
                       continuous=False)
    srv.start()
    futs = [srv.submit((p,)) for p in prompts]
    got = [f.result(timeout=300)[0] for f in futs]
    srv.stop()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_server_per_request_budget_and_mixed_lengths(pred):
    prompts = _prompts(4, seed=8)
    budgets = [2, 7, 3, 5]
    srv = DecodeServer(pred, slots=4, max_seq=32, max_new_tokens=8)
    srv.start()
    futs = [srv.submit((p, np.array([mn], np.int64)))
            for p, mn in zip(prompts, budgets)]
    got = [f.result(timeout=300)[0] for f in futs]
    srv.stop()
    want = pred.generate(prompts, max_new_tokens=8)
    for g, w, mn in zip(got, want, budgets):
        assert len(g) == mn
        np.testing.assert_array_equal(g, w[:mn])


# -- an admission takes one length bucket (PR 50) --------------------------

def _mixed_queue(n, seed):
    """Prompts of the 16, 32 and 64 buckets in one queue, each with room
    for 4 tokens in a 64-row slab."""
    r = np.random.RandomState(seed)
    lens = r.choice([5, 9, 14, 20, 27, 31, 40, 52, 60], size=n)
    return [r.randint(1, V, k).astype(np.int64) for k in lens]


class _WatchedPredictor:
    """`pred`, noting the prefill shapes asked of it."""

    def __init__(self, pred, shapes):
        self._pred, self._shapes = pred, shapes

    def acquire(self, kind, *args, **kw):
        if kind == "prefill":
            self._shapes.add(tuple(args[:2]))
        return self._pred.acquire(kind, *args, **kw)

    def __getattr__(self, name):
        return getattr(self._pred, name)


def _watched_server(pred, calls, shapes, **kw):
    """A server whose floor is 16 rows (the tiny model's slab ends at
    64, under the real floor of 512, where every bucket shares and a
    batch is padded freely), that logs each admission's view of the
    queue and each prefill shape it asks the predictor for."""
    srv = DecodeServer(_WatchedPredictor(pred, shapes), slots=8, max_seq=64,
                       max_new_tokens=4, **kw)
    srv._ADMIT_FLOOR = 16
    pick = srv._admit_group

    def admit_group(free, pending):
        take = pick(free, pending)
        calls.append((free, [p[0] for p in pending],
                      [len(p[1]) for p in pending], take))
        return take

    srv._admit_group = admit_group
    return srv


@pytest.fixture(scope="module")
def warm_shapes(pred):
    """The prefill shapes a warm-up of same-length bursts of 1, 2, 4 and
    8 requests in every prompt bucket asks for: what the benchmark's
    runner compiles before its window."""
    shapes = set()
    for plen in (16, 32, 60):
        for n in (1, 2, 4, 8):
            srv = _watched_server(pred, [], shapes)
            futs = [srv.submit((np.ones((plen,), np.int64),
                                np.array([2], np.int64)))
                    for _ in range(n)]
            srv.start()
            for f in futs:
                f.result(timeout=300)
            srv.stop()
    assert shapes == {(b, s) for b in (1, 2, 4, 8) for s in (16, 32, 64)}
    return shapes


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_mixed_queue_is_admitted_a_bucket_at_a_time(pred, warm_shapes, seed):
    """A server fed prompts of three buckets: every admission holds the
    oldest request and prompts of ITS bucket only; what it passes over
    keeps its place and leads, or joins, the next iteration's; the
    queue never holds more than the free slots take; no prefill shape
    appears that a same-length warm-up did not make; and every reply is
    the direct rollout's."""
    from paddle_tpu import observability as obs

    prompts = _mixed_queue(24, seed)
    want = pred.generate(prompts, max_new_tokens=4)
    calls, shapes = [], set()
    srv = _watched_server(pred, calls, shapes)
    before = obs.DECODE_ADMIT_DEFERRED.value()
    futs = [srv.submit((p,)) for p in prompts]
    srv.start()
    got = [f.result(timeout=300)[0] for f in futs]
    srv.stop()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert shapes <= warm_shapes
    assert len(calls) > 8  # 24 requests, three buckets, 8 slots
    passed_over = 0
    for k, (free, rids, lens, take) in enumerate(calls):
        assert 0 < len(rids) <= free          # rule 4: pending <= free
        assert take[0] == 0                   # the oldest always goes
        buckets = {_pow2_bucket(lens[i], 16) for i in take}
        assert len(buckets) == 1, (lens, take)
        # nobody of the head's bucket is left behind within the room,
        # but for a whole power-of-two batch (3 run as 2, 5-7 as 4)
        same = [i for i in range(min(free, 8, len(lens)))
                if _pow2_bucket(lens[i], 16) in buckets]
        assert take == same[:_pow2_bucket(len(same) + 1) // 2]
        left = [r for i, r in enumerate(rids) if i not in take]
        passed_over += min(free, 8, len(rids)) - len(take)
        if k + 1 < len(calls):
            # in its place: the next queue opens with what was left
            assert calls[k + 1][1][:len(left)] == left
    assert passed_over > 0
    assert obs.DECODE_ADMIT_DEFERRED.value() - before == passed_over
    # one program an admission, and its rows: the padding share is one
    # division on any server
    assert srv.prefill_executions == len(calls)
    assert srv.prefill_prompt_rows == sum(len(p) for p in prompts)
    assert srv.prefill_bucket_rows == sum(
        _pow2_bucket(len(t)) * _pow2_bucket(max(l[i] for i in t), 16)
        for _f, _r, l, t in calls)


def test_gang_scheduling_admits_the_queue_as_it_stands(pred, warm_shapes):
    """`continuous=False` fills its slots with the head of the queue,
    whatever the buckets, as it always has."""
    prompts = _mixed_queue(12, seed=34)
    want = pred.generate(prompts, max_new_tokens=4)
    calls, shapes = [], set()
    srv = _watched_server(pred, calls, shapes, continuous=False)
    futs = [srv.submit((p,)) for p in prompts]
    srv.start()
    got = [f.result(timeout=300)[0] for f in futs]
    srv.stop()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert all(take == list(range(len(take))) for *_x, take in calls)
    assert sum(len(take) for *_x, take in calls) == len(prompts)
    assert srv.prefill_executions == len(calls) < 4


def test_server_stop_is_zero_drop(pred):
    """stop() right after a submit burst: every request still completes
    (queued ones admitted as slots free, in-flight ones finished)."""
    prompts = _prompts(8, seed=9)
    srv = DecodeServer(pred, slots=2, max_seq=32, max_new_tokens=4)
    srv.start()
    futs = [srv.submit((p,)) for p in prompts]
    stopper = threading.Thread(target=srv.stop)
    stopper.start()
    got = [f.result(timeout=300)[0] for f in futs]
    stopper.join(timeout=300)
    assert len(got) == len(prompts)
    want = pred.generate(prompts, max_new_tokens=4)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def _record_steps(pred, events, fail_on=()):
    """Wrap the predictor's decode executable: each call is logged as
    ("call", k, the type of its `tokens` feed), and call ``k`` in
    ``fail_on`` raises at its dispatch."""
    real_acquire = pred.acquire

    def acquire(kind, batch, seq, strategy=None, **kw):
        exe, fetch = real_acquire(kind, batch, seq, strategy, **kw)
        if kind != "decode":
            return exe, fetch

        def wrapped(feeds, state):
            k = 1 + sum(1 for e in events if e[0] == "call")
            events.append(("call", k, type(feeds["tokens"])))
            if k in fail_on:
                raise RuntimeError("injected device failure")
            return exe(feeds, state)

        return wrapped, fetch

    pred.acquire = acquire


def test_server_survives_step_failure(model_dir):
    """A decode step that raises (device OOM, backend loss) must fail
    the affected futures and keep the loop alive — not strand every
    client on a dead daemon thread."""
    p = DecodePredictor(model_dir)
    _record_steps(p, [], fail_on=(1,))
    srv = DecodeServer(p, slots=2, max_seq=32, max_new_tokens=4,
                       prewarm=False)
    srv.start()
    prompts = _prompts(2, seed=14)
    futs = [srv.submit((pr,)) for pr in prompts]
    with pytest.raises(RuntimeError, match="injected device failure"):
        futs[0].result(timeout=120)
    # the loop survived: fresh requests still serve end to end
    fut = srv.submit((prompts[0],))
    out, = fut.result(timeout=120)
    srv.stop()
    want = DecodePredictor(model_dir).generate([prompts[0]],
                                               max_new_tokens=4)[0]
    np.testing.assert_array_equal(out, want)


# -- one step in flight (PR 30) ---------------------------------------------

def _host_in_the_loop(ref, prompt, max_new):
    """The reference of a server that keeps a step in flight: ONE
    sequence alone in slot 0 of the same executables, the host reading
    every token before it feeds the next (the loop's order before PR
    30). ``ref`` is a DecodeServer that is never started: its admission
    recipe (prefill at the prompt's bucket, scatter into fresh entries)
    serves here too."""
    pred, slots, seq = ref.predictor, ref.slots, ref.seq
    dexe, _ = pred.acquire("decode", slots, seq, ref.strategy,
                           kv_dtype=ref.kv_dtype)
    outs, sp, _ = ref._prefill_prompts([prompt])
    tok = int(pred._sample_host(outs[0], ref.strategy, 0)[0])
    caches = ref._scatter_prefill(ref._fresh_slabs(), list(outs[1:]), [0],
                                  sp)
    lens = np.zeros((slots,), np.int32)
    lens[0] = len(prompt)
    gen = [tok]
    while len(gen) < max_new and lens[0] + 1 < seq:
        cur = np.zeros((slots, 1), np.int64)
        cur[0] = tok
        feeds = {"tokens": cur, "lengths": lens.copy(),
                 "seed": np.zeros((1,), np.int64)}
        if pred.config.positions:
            feeds["positions"] = lens.reshape(slots, 1).astype(np.int64)
        feeds.update(zip(ref._cache_feed_names, caches))
        outs = dexe(feeds, pred._state)
        tok = int(np.asarray(outs[0])[0])
        caches = list(outs[2:])
        lens[0] += 1
        gen.append(tok)
    return gen


def _case_server(case, request, **kw):
    mdir = request.getfixturevalue(
        "hybrid_dir" if case == "hybrid" else "model_dir")
    kw.setdefault("kv_dtype", "int8" if case == "int8" else "float32")
    return DecodeServer(DecodePredictor(mdir), **kw)


@pytest.mark.parametrize("case", ["opt", "hybrid", "int8"])
def test_server_with_a_step_in_flight_answers_as_the_host_in_the_loop(
        case, request):
    """Token for token, over what the order of the loop could break:
    eleven requests on three slots, so that admissions land between two
    steps of live sequences and on slots freed a step before their last
    token was read; budgets of 1 (the prefill's token alone), 2 and 3
    (a sequence the host retires at its first or second dispatch);
    two that end on the slab's last row; four that arrive while the
    first seven decode."""
    kw = dict(slots=3, max_seq=32, max_new_tokens=8)
    srv = _case_server(case, request, **kw)
    ref = DecodeServer(srv.predictor, kv_dtype=srv.kv_dtype, **kw)
    r = np.random.RandomState(30)
    plens = [4, 9, 3, 24, 6, 12, 5, 7, 30, 10, 8]
    budgets = [1, 2, 3, 8, 5, 8, 1, 2, 2, 3, 8]
    vocab = srv.predictor.config.vocab_size
    prompts = [r.randint(1, vocab, n).astype(np.int64) for n in plens]
    reqs = [(p, np.array([b], np.int64)) for p, b in zip(prompts, budgets)]
    futs = [srv.submit(q) for q in reqs[:7]]
    srv.start()
    futs[2].result(timeout=300)
    futs += [srv.submit(q) for q in reqs[7:]]
    got = [np.asarray(f.result(timeout=300)[0]).tolist() for f in futs]
    srv.stop()
    want = [_host_in_the_loop(ref, p, b) for p, b in zip(prompts, budgets)]
    assert got == want
    assert [len(g) for g in got] == budgets
    # a step's count is the tokens it DELIVERED: every token but each
    # request's first (the prefill's) came out of one decode step
    assert sum(srv.step_active_counts) == sum(budgets) - len(budgets)
    assert max(srv.step_active_counts) <= 3


def test_step_is_dispatched_before_the_one_before_it_is_read(model_dir):
    """The order itself: step n+1's call happens before the host
    materialises step n's ids, its `tokens` feed is a device value (the
    select of `_chain_fn`), and `in_flight` is 1 on every dispatch but
    the first after a park."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import tracing

    p = DecodePredictor(model_dir)
    events = []
    _record_steps(p, events)
    srv = DecodeServer(p, slots=2, max_seq=32, max_new_tokens=6)
    real_dispatch, real_fetch = srv._dispatch, srv._fetch
    dispatched, read = [], []

    def dispatch(*args):
        dispatched.append(real_dispatch(*args))
        return dispatched[-1]

    def fetch(flight, t_token):
        read.append(flight)
        events.append(("read", len(dispatched)))
        return real_fetch(flight, t_token)

    srv._dispatch, srv._fetch = dispatch, fetch
    before = {k: obs.DECODE_STEPS.value(in_flight=k) for k in "01"}
    tracing.reset()
    tracing.set_sample_rate(1.0)
    try:
        # three requests queued before the start (two slots: the third
        # is admitted behind a step in flight), then, when all have
        # resolved and nothing is live or queued, one more
        futs = [srv.submit((q,)) for q in _prompts(3, seed=21)]
        srv.start()
        for f in futs:
            assert len(f.result(timeout=300)[0]) == 6
        late = srv.submit((_prompts(1, seed=22)[0],))
        assert len(late.result(timeout=300)[0]) == 6
        srv.stop()
    finally:
        tracing.set_sample_rate(0.0)
    calls = [e for e in events if e[0] == "call"]
    assert [e[0] for e in events][:4] == ["call", "call", "read", "call"]
    # every step is read once, in the order dispatched, and when step j
    # is read step j+1 has been dispatched already, unless step j was
    # the last before the server emptied
    assert len(read) == len(calls) == len(dispatched)
    assert all(a is b for a, b in zip(read, dispatched))
    behind = [e[1] - j for j, e in enumerate(
        e for e in events if e[0] == "read")]
    assert set(behind) == {1, 2} and behind.count(1) == 2
    steps = [s for s in tracing.get_recorder().spans()
             if s["name"] == "decode.loop.iter" and "active" in s]
    in_flight = [s["in_flight"] for s in steps]
    assert len(in_flight) == len(calls)
    assert in_flight.count(0) == 2 and in_flight[0] == 0
    second = in_flight.index(0, 1)
    assert in_flight == [0] + [1] * (second - 1) + [0] + \
        [1] * (len(calls) - second - 1)
    # a slot that continues from the step in flight takes its token on
    # the device: the `tokens` of such a step never were on the host
    # (where every slot turned over at once, they are the admission's)
    for j, (_c, _k, fed) in enumerate(calls):
        was = {(i, id(st)) for i, st, _l in dispatched[j - 1].rows}
        chained = in_flight[j] and any(
            (i, id(st)) in was for i, st, _l in dispatched[j].rows)
        assert issubclass(fed, jax.Array) == bool(chained), j
    assert sum(issubclass(c[2], jax.Array) for c in calls) >= 6
    assert obs.DECODE_STEPS.value(in_flight="0") - before["0"] == 2
    assert (obs.DECODE_STEPS.value(in_flight="1") - before["1"]
            == len(calls) - 2)
    assert sum(srv.step_active_counts) == 4 * 5


@pytest.mark.parametrize("case", ["opt", "int8"])
def test_eos_learnt_a_step_late_reaches_nobody(case, request):
    """An `eos_id` hit is read one step after the slot has been fed
    again: the token of that extra step reaches no request and no
    counter, and the slot's next occupant answers exactly as on a
    fresh server (an admission overwrites what it scatters). The tiny
    hybrid model repeats one token, so it has no `eos_id` to hit
    mid-way: its slots turn over under a step in flight in the
    token-for-token test above."""
    from paddle_tpu import observability as obs

    kw = dict(slots=1, max_seq=32, max_new_tokens=8)
    probe = _case_server(case, request, **kw)
    vocab = probe.predictor.config.vocab_size
    r = np.random.RandomState(5)
    first_p, next_p = (r.randint(1, vocab, n).astype(np.int64)
                       for n in (7, 5))
    base = _host_in_the_loop(probe, first_p, 8)
    # stop the first sequence at a token it emits mid-way for the first
    # time, after some decode steps and well before its budget
    cut = next(k + 1 for k in range(2, 6) if base[k] not in base[:k])
    eos = base[cut - 1]
    srv = _case_server(case, request, eos_id=eos, **kw)
    tokens0 = obs.DECODE_TOKENS.value(kind="decode")
    srv.start()
    got = np.asarray(srv.submit((first_p,)).result(timeout=300)[0])
    assert got.tolist() == base[:cut]
    reused = np.asarray(srv.submit((next_p,)).result(timeout=300)[0])
    srv.stop()
    fresh = _case_server(case, request, eos_id=eos, **kw)
    fresh.start()
    want = np.asarray(fresh.submit((next_p,)).result(timeout=300)[0])
    fresh.stop()
    np.testing.assert_array_equal(reused, want)
    # delivered tokens alone are counted: the discarded step shows as a
    # step that delivered nothing
    assert sum(srv.step_active_counts) == (len(got) - 1) + (len(reused) - 1)
    assert 0 in srv.step_active_counts
    assert (obs.DECODE_TOKENS.value(kind="decode") - tokens0
            == len(got) + len(reused) + len(want))


@pytest.mark.parametrize("where", ["dispatch", "fetch"])
def test_step_failure_with_a_step_in_flight(model_dir, where):
    """A step that raises at its dispatch, and one whose ids raise when
    they are read, with another step in flight either way: the steps in
    flight are lost together, so every live request fails with the
    error: the one that had left its slot and waited only for its last
    token, and the one admitted into that slot since; what was queued
    is served; `stop()` returns."""
    p = DecodePredictor(model_dir)
    events = []
    _record_steps(p, events, fail_on=(3,) if where == "dispatch" else ())
    srv = DecodeServer(p, slots=2, max_seq=32, max_new_tokens=6)
    if where == "fetch":
        real_fetch, reads = srv._fetch, []

        def fetch(flight, t_token):
            reads.append(flight)
            if len(reads) == 2:
                raise RuntimeError("injected device failure")
            return real_fetch(flight, t_token)

        srv._fetch = fetch
    prompts = _prompts(5, seed=23)
    # a budget of 3: its last token is step 2's, so at the failure (the
    # dispatch of step 3, or the read of step 2 behind it) request 0
    # has left its slot and waits for that token alone, and request 2
    # has been admitted into the slot for step 3
    budgets = [3, 6, 4, 4, 4]
    futs = [srv.submit((q, np.array([b], np.int64)))
            for q, b in zip(prompts, budgets)]
    srv.start()
    for f in futs[:3]:
        with pytest.raises(RuntimeError, match="injected device failure"):
            f.result(timeout=120)
    got = [f.result(timeout=120)[0] for f in futs[3:]]
    stopper = threading.Thread(target=srv.stop)
    stopper.start()
    stopper.join(timeout=120)
    assert not stopper.is_alive()
    want = DecodePredictor(model_dir).generate(prompts[3:],
                                               max_new_tokens=4)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_stop_with_work_queued_and_a_step_in_flight_drops_nothing(pred):
    """`stop()` arrives while a step is in flight and requests wait for
    a slot: the loop reads the step, admits what is queued and finishes
    it all before it returns."""
    srv = DecodeServer(pred, slots=2, max_seq=32, max_new_tokens=5)
    real_fetch = srv._fetch
    reads, stopper = [], []

    def fetch(flight, t_token):
        reads.append(flight)
        if len(reads) == 1:
            # from the loop's own thread, between a dispatch and the
            # read behind it: a step is in flight, six requests queued
            stopper.append(threading.Thread(target=srv.stop))
            stopper[0].start()
            while not srv._chan._py_closed:
                time.sleep(1e-3)
        return real_fetch(flight, t_token)

    srv._fetch = fetch
    prompts = _prompts(8, seed=24)
    futs = [srv.submit((q,)) for q in prompts]
    srv.start()
    got = [f.result(timeout=300)[0] for f in futs]
    stopper[0].join(timeout=300)
    assert not stopper[0].is_alive()
    with pytest.raises(RuntimeError, match="stopped"):
        srv.submit((prompts[0],))
    for g, w in zip(got, pred.generate(prompts, max_new_tokens=5)):
        np.testing.assert_array_equal(g, w)


def test_server_rejects_oversized_prompt(pred):
    srv = DecodeServer(pred, slots=1, max_seq=16, max_new_tokens=8)
    srv.start()
    fut = srv.submit((np.arange(1, 20, dtype=np.int64),))  # 19 + 8 > 16
    with pytest.raises(ValueError):
        fut.result(timeout=120)
    srv.stop()


def test_decode_metrics_exported_and_merged(pred, tmp_path):
    """Acceptance pin: the decode series reach /metrics, and
    tools/metrics_dump.py --merge aggregates snapshots containing
    them."""
    srv = DecodeServer(pred, slots=2, max_seq=32, max_new_tokens=4,
                       speculative=True, spec_k=4, prefix_cache=True,
                       prewarm=False)
    srv.start()
    base = _prompts(3, seed=10)
    futs = [srv.submit((p,)) for p in base + [base[0]]]
    for f in futs:
        f.result(timeout=300)
    port = srv.start_http(0)
    text = urllib.request.urlopen(
        "http://127.0.0.1:%d/metrics" % port, timeout=30
    ).read().decode("utf-8")
    srv.stop()
    for series in ("paddle_tpu_decode_tokens_total",
                   "paddle_tpu_decode_slots",
                   "paddle_tpu_decode_step_ms_bucket",
                   "paddle_tpu_decode_requests_total",
                   # PR-14 lever series: prefix-hit-rate and
                   # acceptance-rate ride the same scrape
                   "paddle_tpu_decode_prefix_queries_total",
                   "paddle_tpu_decode_prefix_hits_total",
                   "paddle_tpu_decode_prefix_bytes",
                   "paddle_tpu_decode_spec_proposed_total",
                   "paddle_tpu_decode_spec_accepted_total"):
        assert series in text, series

    from paddle_tpu.observability import export

    snap = tmp_path / "w0.json"
    snap.write_text(json.dumps(export.to_json(include_timeline=False)))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "metrics_dump.py"),
         "--merge", str(snap), str(snap)],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    merged = json.loads(res.stdout)
    names = json.dumps(merged)
    assert "paddle_tpu_decode_tokens_total" in names


# -- shared-prefix KV (PR 14) ---------------------------------------------

def test_prefix_sharing_one_prefill_with_parity_and_refcounts(pred):
    """Acceptance pin: N concurrent sequences sharing a prompt prefix
    execute exactly ONE prefill, their outputs match private-prefill
    sequences, and the store's refcounts release on retirement."""
    r = np.random.RandomState(21)
    shared = r.randint(1, V, 8).astype(np.int64)
    want = pred.generate([shared], max_new_tokens=6)[0]
    # slots=2 + prewarm=False: every signature this server needs is
    # already compiled by the earlier server tests (tier-1 budget)
    srv = DecodeServer(pred, slots=2, max_seq=32, max_new_tokens=6,
                       prefix_cache=True, prewarm=False)
    srv.start()
    futs = [srv.submit((shared,)) for _ in range(6)]
    got = [f.result(timeout=300)[0] for f in futs]
    assert srv.prefill_executions == 1, srv.prefill_executions
    for g in got:
        np.testing.assert_array_equal(g, want)
    # refcount release on retirement: nothing pins the lone entry
    store = srv._prefix
    assert len(store) == 1
    assert all(store.refs(eid) == 0 for eid in store._entries)
    srv.stop()


def test_prefix_partial_hit_extends_suffix_only(pred):
    """Prompts sharing a block-aligned header with a cached entry seed
    from its rows and extend ONLY their suffix through the verify
    window — no second full prefill — with token parity vs private
    prefill (padded-batch GEMMs are not bitwise; greedy argmax is the
    parity surface at this scale)."""
    r = np.random.RandomState(22)
    header = r.randint(1, V, 16).astype(np.int64)
    suffixed = [np.concatenate([header,
                                r.randint(1, V, 3).astype(np.int64)])
                for _ in range(3)]
    want = pred.generate(suffixed, max_new_tokens=5)
    srv = DecodeServer(pred, slots=2, max_seq=32, max_new_tokens=5,
                       prefix_cache=True, prewarm=False, spec_k=4)
    srv.start()
    # seed the store with the header's rows...
    srv.submit((header,)).result(timeout=300)
    assert srv.prefill_executions == 1
    # ...then every suffixed prompt is a partial hit: zero new prefills
    futs = [srv.submit((p,)) for p in suffixed]
    got = [f.result(timeout=300)[0] for f in futs]
    srv.stop()
    assert srv.prefill_executions == 1, srv.prefill_executions
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# -- speculative decoding (PR 14) -----------------------------------------

def test_server_speculative_is_lossless(pred):
    """Acceptance pin: greedy speculative serving output is token-for-
    token identical to non-speculative greedy — through the continuous-
    batching server, mixed prompt lengths and budgets."""
    prompts = _prompts(6, seed=23)
    budgets = [2, 6, 4, 6, 3, 5]
    want = pred.generate(prompts, max_new_tokens=6)
    srv = DecodeServer(pred, slots=2, max_seq=32, max_new_tokens=6,
                       speculative=True, spec_k=4, prewarm=False)
    srv.start()
    futs = [srv.submit((p, np.array([mn], np.int64)))
            for p, mn in zip(prompts, budgets)]
    got = [f.result(timeout=300)[0] for f in futs]
    srv.stop()
    for g, w, mn in zip(got, want, budgets):
        assert len(g) == mn
        np.testing.assert_array_equal(g, w[:mn])


# (the predictor-level speculative pins — eos truncation, draft-depth
# sweep — live in tests/test_speculative.py, the standalone tier)


# -- fleet path -----------------------------------------------------------

def test_fleet_decode_round_trip_with_drain_restart(model_dir, pred):
    """Acceptance pin: decode requests round-trip through the PR-8
    Router fleet, and a drain_restart mid-traffic drops NOTHING — the
    zero-drop contract extended to in-flight decode sequences. PR 14:
    the replicas run with BOTH new levers on (speculative rounds +
    prefix store) and the prompt list carries duplicates, so drained /
    requeued sequences are exactly the prefix-shared and
    mid-speculation kind the contract must survive."""
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import Router

    prompts = _prompts(8, seed=11)
    prompts += [prompts[0].copy(), prompts[3].copy()]  # prefix sharers
    want = pred.generate(prompts, max_new_tokens=5)
    before_mis = obs.FLEET_MISVERSIONED.value()
    router = Router(model_dir, replicas=2, decode=True, decode_slots=2,
                    decode_max_seq=32, max_new_tokens=8,
                    decode_speculative=True, decode_spec_k=2,
                    decode_prefix_cache=True,
                    jax_platform="cpu")
    router.start()
    opts = np.array([5], np.int64)
    futs = [router.submit((p, opts)) for p in prompts[:5]]
    drainer = threading.Thread(target=lambda: router.drain_restart(0))
    drainer.start()
    futs += [router.submit((p, opts)) for p in prompts[5:]]
    got = [f.result(timeout=300)[0] for f in futs]
    drainer.join(timeout=300)
    router.stop()
    assert len(got) == len(prompts)  # zero drops
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert obs.FLEET_MISVERSIONED.value() == before_mis


# -- beam-search strategy -------------------------------------------------

def test_beam_size_one_equals_greedy(pred):
    prompts = _prompts(2, seed=12)
    beam = pred.generate(prompts, max_new_tokens=6, strategy="beam",
                         beam_size=1)
    greedy = pred.generate(prompts, max_new_tokens=6, strategy="greedy")
    for b, g in zip(beam, greedy):
        np.testing.assert_array_equal(b, g)


def test_beam_scores_are_ordered(pred):
    prompts = _prompts(2, seed=13)
    sent, lens, scores = pred.generate_beam(
        prompts, max_new_tokens=5, beam_size=3, return_all=True)
    assert sent.shape[:2] == (2, 3)
    for b in range(2):
        assert all(scores[b, i] >= scores[b, i + 1] - 1e-6
                   for i in range(2))


def test_beam_strategy_parity_with_contrib_decoder():
    """Satellite pin: the ops-layer beam search driven HOST-SIDE between
    step executions (beam_search_step / cache_gather state reorder /
    beam_search_backtrack — exactly DecodePredictor.generate_beam's
    loop) reproduces contrib BeamSearchDecoder's program-level scan on a
    small seq2seq cell, id-for-id and score-for-score."""
    from paddle_tpu.contrib import BeamSearchDecoder, InitState, StateCell
    from paddle_tpu.ops.decode import (beam_search_backtrack,
                                       beam_search_step)
    from paddle_tpu.ops.kv_cache import cache_gather

    B, Dh, Vc, WD, K, MAXLEN, END = 2, 8, 11, 6, 3, 6, 1

    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = startup.random_seed = 3
    with fluid.program_guard(prog, startup):
        with fluid.unique_name.guard():
            enc = layers.data(name="enc", shape=[Dh])
            init_ids = layers.data(name="init_ids", shape=[1],
                                   dtype="int64")
            init_scores = layers.data(name="init_scores", shape=[1])
            cell = StateCell(inputs={"x": None},
                             states={"h": InitState(init=enc)},
                             out_state="h")

            @cell.state_updater
            def updater(c):
                x = c.get_input("x")
                h = c.get_state("h")
                c.set_state("h", layers.fc(input=[x, h], size=Dh,
                                           act="tanh", bias_attr=False))

            decoder = BeamSearchDecoder(
                cell, init_ids, init_scores, target_dict_dim=Vc,
                word_dim=WD, topk_size=Vc, sparse_emb=False,
                max_len=MAXLEN, beam_size=K, end_id=END)
            decoder.decode()
            ids_v, scores_v = decoder()
    r = np.random.RandomState(11)
    enc_v = r.randn(B, Dh).astype(np.float32)
    feed = {"enc": enc_v, "init_ids": np.zeros((B, 1), np.int64),
            "init_scores": np.zeros((B, 1), np.float32)}
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        ids_p, scores_p = exe.run(prog, feed=feed,
                                  fetch_list=[ids_v, scores_v])
        params = {n: np.asarray(scope.find_var(n))
                  for n in prog.global_block().vars
                  if scope.find_var(n) is not None
                  and getattr(prog.global_block().vars[n],
                              "persistable", False)}
    ids_p, scores_p = np.asarray(ids_p), np.asarray(scores_p)
    emb_w = next(v for v in params.values() if v.shape == (Vc, WD))
    x_w = next(v for v in params.values() if v.shape == (WD, Dh))
    h_w = next(v for v in params.values() if v.shape == (Dh, Dh))
    s_w = next(v for v in params.values() if v.shape == (Dh, Vc))
    s_b = next(v for v in params.values() if v.shape == (Vc,))

    # host-side replay: the generate_beam loop shape, with the RNN cell
    # in place of the compiled LM decode step
    h = np.repeat(enc_v, K, axis=0)                     # beam-tiled state
    pre_ids = jnp.zeros((B, K), jnp.int32)
    pre_scores = jnp.asarray(
        np.concatenate([np.zeros((B, 1), np.float32),
                        np.full((B, K - 1), -1e9, np.float32)], axis=1))
    step_ids, step_parents, scores_stack = [], [], []
    for _ in range(MAXLEN):
        x = emb_w[np.asarray(pre_ids).reshape(-1)]
        h = np.tanh(x @ x_w + h @ h_w)
        probs = jax.nn.softmax(jnp.asarray(h @ s_w + s_b), axis=-1)
        cand_probs, cand_ids = jax.lax.top_k(probs, Vc)
        cum = (jnp.log(cand_probs)
               + pre_scores.reshape(-1, 1)).reshape(B, K, Vc)
        sel_ids, sel_scores, parents = beam_search_step(
            pre_ids, pre_scores, cum, cand_ids.reshape(B, K, Vc), K, END)
        flat_parent = (np.arange(B, dtype=np.int32)[:, None] * K
                       + np.asarray(parents)).reshape(-1)
        # the slab-reorder primitive doubles as the RNN-state reorder
        h = np.asarray(cache_gather(jnp.asarray(h),
                                    jnp.asarray(flat_parent)))
        pre_ids, pre_scores = sel_ids.astype(jnp.int32), sel_scores
        step_ids.append(sel_ids)
        step_parents.append(parents)
        scores_stack.append(sel_scores)
    sent, lens = beam_search_backtrack(jnp.stack(step_ids),
                                      jnp.stack(step_parents), END)
    np.testing.assert_array_equal(np.asarray(sent), ids_p)
    np.testing.assert_allclose(np.asarray(scores_stack[-1]), scores_p,
                               rtol=1e-5, atol=1e-6)
