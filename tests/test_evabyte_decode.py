"""An EvaByte-family LM (every layer EVA attention: a query attends its
own window of `window_size` positions exactly and every EARLIER window
through one pooled key and value a chunk of `chunk_size` positions, all
under one softmax; RMS norms with a unit offset; rotary positions; a
gated-SiLU MLP; logits at `highest`) through the normal serving path
(`save_decode_model` -> `DecodePredictor` -> `DecodeServer`) at a tiny
size (hidden 64, 4 heads of 16, a window of 32, chunks of 4, 3 layers):
prefill then decode LOGITS against the plain reference's full forward
pass (`benchmark/reference/evabyte.py`: the mask from the definition of
what a query sees, which imports nothing of the program) at prompt
lengths inside window 0, exactly a window, exactly a chunk and several
windows deep; decode runs that close a chunk, a window and two windows;
prompts of two buckets admitted together; the kernel's view against the
lax path; the four ASSUMED fields; the `eva` entries of `cache_spec`;
the counts; the manifests that stand."""
import json
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.ops import decode_stream, eva  # noqa: E402
from paddle_tpu.serving.decode import (  # noqa: E402
    DecodeConfig, DecodePredictor, DecodeServer, _eva_pairs, cache_spec,
    kv_slab_slots, save_decode_model)

from benchmark.lib import weights  # noqa: E402
from benchmark.models import (dots3_lm, evabyte_lm, jamba_lm,  # noqa: E402
                              laguna_lm, ling3_lm, mistral4_lm, phi4flash_lm)
from benchmark.reference import evabyte as ref  # noqa: E402

with open(os.path.join(_ROOT, "benchmark", "tests", "tiny",
                       "evabyte-tiny.json")) as _f:
    CFG = json.load(_f)
SLOTS, SEQ, N_LAYER = 4, 256, 3
H, DH, W, C = 4, 16, 32, 4
N_SUM, ROWS = SEQ // C, SEQ // C + W  # 64 summary rows, 96 in all


def _seeded(cfg):
    specs = evabyte_lm.parameter_specs(cfg, "serve")
    return weights.seeded_weights(specs, 2 ** 31 + 13, evabyte_lm.init_rule)


def _pred(d, cfg, w):
    scope = fluid.Scope()
    for n in w:
        scope.set_var(n, w[n])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        save_decode_model(d, evabyte_lm.decode_config(cfg, "serve"), exe,
                          scope=scope)
    return DecodePredictor(d)


@pytest.fixture(scope="module")
def seeded():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def pred(tmp_path_factory, seeded):
    return _pred(str(tmp_path_factory.mktemp("evabyte_model")), CFG, seeded)


def _prompts(lens, seed=3):
    r = np.random.default_rng(seed)
    return [r.integers(1, CFG["vocab_size"], n, dtype=np.int64)
            for n in lens]


def _rollout(pred, prompts, steps, forced):
    """The benchmark runner's own rollout (see test_hybrid_decode)."""
    from benchmark.lib import run_serveany

    rows, _ = run_serveany._direct_rollout(pred, prompts, steps, SLOTS,
                                           SEQ, forced=forced)
    return [np.stack(r) for r in rows]


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _reference(w, text, rows, variant="", cfg=CFG):
    """`rows` of the plain reference's logits over `text`, in one full
    forward pass. The reference is causal, so the text is padded to SEQ
    positions: its programs then compile once a file."""
    padded = np.zeros((SEQ,), np.int64)
    padded[:len(text)] = text
    return np.asarray(ref.serve_logits(
        w, jnp.asarray(padded), cfg, N_LAYER, rows=np.asarray(rows),
        variant=variant))


# (prompt length, teacher-forced steps), four prompts a rollout:
# inside window 0 (a step closes chunks only); exactly a window (the
# block restarts at the first step); exactly a chunk, mid-window; three
# windows deep, a run that closes a window | a run that closes TWO
# windows (positions 63 and 95) from inside window 1; one that starts a
# position before a window's end; one that lands on a chunk's last
# position; a prompt of the 256 bucket's last window
CASES = {
    "boundaries": ([9, 32, 44, 101], 30),
    "two_windows": ([34, 63, 39, 170], 66),
}


@pytest.fixture(scope="module")
def rollouts(pred):
    out = {}
    for name, (lens, k) in CASES.items():
        prompts = _prompts(lens, seed=len(name))
        forced = _prompts([k + 1] * len(lens), seed=4)
        out[name] = (prompts, forced, k, _rollout(pred, prompts, k, forced))
    return out


@pytest.mark.parametrize("case,which", [
    (c, i) for c in CASES for i in range(4)],
    ids=["%s-len%d" % (c, n) for c in CASES for n in CASES[c][0]])
def test_prefill_then_decode_matches_the_reference(rollouts, seeded, case,
                                                   which):
    """Every logit row of the last prompt position and of each decoded
    position, through the (slots, seq) step the server runs, against the
    reference's one full forward pass over the same tokens."""
    prompts, forced, k, got = rollouts[case]
    p, f = prompts[which], forced[which]
    want = _reference(seeded, np.concatenate([p, f[:k]]),
                      np.arange(len(p) - 1, len(p) + k))
    worst = max(_rel(g, w) for g, w in zip(got[which], want))
    assert worst < 1e-5, (case, len(p), worst)


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_a_reference_that_changes_a_part_is_told_apart(rollouts, seeded,
                                                       variant):
    """The comparison sees each mechanism: against a reference with the
    summaries left out, `mu` left out, pooling by the mean, unrotated
    keys pooled, a block that does not restart, summaries visible a
    window early or the norms' unit offset left out, the same logits are
    far away, where the program is 1e-6 from the true reference."""
    prompts, forced, k, got = rollouts["two_windows"]
    p, f = prompts[3], forced[3]
    want = _reference(seeded, np.concatenate([p, f[:k]]),
                      np.arange(len(p) - 1, len(p) + k), variant)
    assert _rel(got[3], want) > 5e-4, variant


def _is_greedy(seeded, prompt, generated):
    full = np.concatenate([prompt, generated])
    lg = _reference(seeded, full, np.arange(len(prompt) - 1, len(full) - 1))
    return lg.argmax(-1).tolist() == list(generated)


def test_prompts_of_two_buckets_admitted_together_and_beside_live_ones(
        pred, seeded):
    """Three slots, five requests through the server: the first two (the
    64 and the 128 bucket) are admitted in ONE prefill, padded to the
    longer's bucket; later ones reuse slots at other lengths beside live
    neighbours. Each answer is the reference's greedy rollout, which
    knows no slot and no last occupant: a block an admission did not
    replace, a summary row that leaks between neighbours, or a chunk
    pooled from a stale row fails here."""
    prompts = _prompts([40, 100, 31, 64, 7], seed=7)
    news = [30, 9, 12, 6, 29]
    srv = DecodeServer(pred, slots=3, max_seq=SEQ, max_new_tokens=32)
    futs = [srv.submit((p, np.array([n], np.int64)))
            for p, n in zip(prompts[:2], news[:2])]
    srv.start()
    deadline = time.time() + 120
    while len(srv.step_active_counts) < 3 and time.time() < deadline:
        time.sleep(0.005)
    futs += [srv.submit((p, np.array([n], np.int64)))
             for p, n in zip(prompts[2:], news[2:])]
    got = [np.asarray(f.result(timeout=300)[0]) for f in futs]
    srv.stop()
    assert [len(g) for g in got] == news
    assert all(_is_greedy(seeded, p, g) for p, g in zip(prompts, got))


def test_generate_matches_the_server(pred, seeded):
    prompts = _prompts([50, 20], seed=9)
    outs = pred.generate(prompts, max_new_tokens=20)
    assert all(_is_greedy(seeded, p, g) for p, g in zip(prompts, outs))


# -- the ops ----------------------------------------------------------------

def test_live_range_is_the_visible_summaries_then_the_blocks_rows():
    pos = jnp.asarray([0, 5, 31, 32, 100, 255], jnp.int32)
    start, end = eva.live_range(pos, W, C, N_SUM)
    per = W // C
    assert start.tolist() == [N_SUM, N_SUM, N_SUM, N_SUM - per,
                              N_SUM - 3 * per, N_SUM - 7 * per]
    assert end.tolist() == [N_SUM + 1, N_SUM + 6, N_SUM + 32, N_SUM + 1,
                            N_SUM + 5, N_SUM + 32]


def test_the_kernels_view_against_the_lax_path():
    """`ptpu.eva_attn` is `decode_stream.stream_attend` over `eva_view`
    with a start: interpreted, against the exact lax path, at live
    ranges that begin and end inside blocks, an empty block part and a
    full one."""
    r = np.random.default_rng(0)
    b, h, d, w, c, m = 6, 8, 128, 32, 4, 128
    n_sum = m // c
    shape = (b, n_sum + w, h, d)
    kc = jnp.asarray(r.normal(size=shape), jnp.float32)
    vc = jnp.asarray(r.normal(size=shape), jnp.float32)
    q = jnp.asarray(r.normal(size=(b, 1, h, d)), jnp.float32)
    pos = jnp.asarray([0, 5, 31, 32, 100, 127], jnp.int32)
    view = eva.eva_view(n_sum + w, h, d, jnp.float32)
    assert view.name == eva.EVA_ATTN and view.groups == view.score_rows == h
    assert decode_stream.block_positions(view) == 64
    got = eva.eva_decode(q, kc, vc, pos, w, c, interpret=True)
    want = eva.eva_decode_reference(
        q, kc, vc, *eva.live_range(pos, w, c, n_sum))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    # an empty range is a slot of length 0: zeros
    none = decode_stream.stream_attend(
        view, jnp.full((b,), 10, jnp.int32), q * 0.1, kc, vc, True,
        starts=jnp.full((b,), 10, jnp.int32))
    assert float(jnp.max(jnp.abs(none))) == 0.0


def test_a_start_of_zero_is_the_body_without_one():
    from paddle_tpu.ops import kv_cache

    r = np.random.default_rng(1)
    b, s, h, hkv, d = 3, 64, 16, 8, 128
    k = jnp.asarray(r.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(r.normal(size=(b, s, hkv, d)), jnp.float32)
    q = jnp.asarray(r.normal(size=(b, 1, h, d)), jnp.float32)
    lens = jnp.asarray([1, 37, 64], jnp.int32)
    view = kv_cache.decode_view(s, h, hkv, d, jnp.float32, block_s=16)
    plain = decode_stream.stream_attend(view, lens, q, k, v, True)
    ranged = decode_stream.stream_attend(view, lens, q, k, v, True,
                                         starts=jnp.zeros((b,), jnp.int32))
    assert jnp.array_equal(plain, ranged)
    # the blocks before the start are skipped as those past the end are
    starts = np.array([0, 20, 48])
    got = [int(decode_stream.live_block(j, lens, 1, 16, starts=starts))
           for j in range(4)]
    assert got == [1, 2, 2, 2]
    got = [int(decode_stream.second_pass_block(j, lens, 2, 16, 4,
                                               starts=starts))
           for j in range(8)]
    assert got == [3, 3, 3, 3, 3, 3, 3, 3]


def test_prefill_kernel_path_merges_windows_and_summaries():
    """The flash calls (interpreted; bfloat16 operands) against the
    exact lax form: each window's own rows and its summaries, merged by
    their log-sum-exp, at a length inside the last window."""
    r = np.random.default_rng(2)
    b, t, h, d, w, c = 3, 512, 2, 128, 256, 16
    q, k, v = (jnp.asarray(r.normal(size=(b, t, h, d)) * 0.5, jnp.float32)
               for _ in range(3))
    phi = jnp.asarray(r.normal(size=(h, d)), jnp.float32)
    mu = jnp.asarray(r.normal(size=(h, d)) * 0.5, jnp.float32)
    ks, vs = eva.eva_summaries(k, v, phi, mu, c)
    lens = jnp.asarray([512, 300, 200], jnp.int32)
    got = eva.eva_prefill(q, k, v, ks, vs, lens, w, c, interpret=True)
    want = eva.eva_prefill(q, k, v, ks, vs, lens, w, c)
    assert _rel(np.asarray(got[0]), np.asarray(want[0])) < 2e-2
    assert _rel(np.asarray(got[1, :300]), np.asarray(want[1, :300])) < 2e-2
    assert _rel(np.asarray(got[2, :200]), np.asarray(want[2, :200])) < 2e-2
    # a window wholly past a row's length is skipped: zeros no one reads
    assert float(jnp.max(jnp.abs(got[2, 256:]))) == 0.0


def test_pack_is_what_a_step_finds():
    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(3, 64, H, DH)), jnp.float32)
    xs = jnp.asarray(r.normal(size=(3, 64 // C, H, DH)), jnp.float32)
    lens = jnp.asarray([9, 32, 64], jnp.int32)
    out = np.asarray(eva.eva_pack(x, xs, lens, W, N_SUM))
    assert out.shape == (3, ROWS, H, DH)
    for i in range(3):  # chunk c at row N_SUM - 1 - c
        assert (out[i, N_SUM - 16:N_SUM] == np.asarray(xs[i])[::-1]).all()
        assert (out[i, :N_SUM - 16] == 0).all()
    assert (out[0, N_SUM:N_SUM + 9] == np.asarray(x[0, :9])).all()
    assert (out[1, N_SUM:] == np.asarray(x[1, 32:64])).all()  # window 1


# -- the four ASSUMED conventions -------------------------------------------

@pytest.mark.parametrize("field,other", [
    ("eva_pool_logit", "phi_dot_key_unscaled"),
    ("eva_key_offset", "added_to_every_key"),
    ("eva_pool_rotated", "before_rotation"),
    ("head", "last_vocab_size_columns")])
def test_each_assumed_fields_other_value_is_refused(field, other):
    cfg = dict(CFG, model=dict(CFG["model"], **{field: other}))
    with pytest.raises(ValueError, match="model.%s = %r" % (field, other)):
        evabyte_lm.decode_config(cfg, "serve")
    with pytest.raises(ValueError, match="model.%s = %r" % (field, other)):
        ref.check_assumed(cfg)
    cfg["model"].pop(field)
    with pytest.raises(ValueError, match="model.%s = None" % field):
        evabyte_lm.decode_config(cfg, "serve")


def test_config_and_builders_refuse_what_is_not_built():
    from paddle_tpu.models import jamba

    base = dict(n_layer=1, n_head=4, d_model=64, norm="rms_norm",
                ffn="gated_silu", positions=False, biases=False,
                layer_types=["eva"], max_len=64)
    for bad in (dict(window=32), dict(window=30, eva_chunk=4),
                dict(window=32, eva_chunk=4, max_len=66),
                dict(window=32, eva_chunk=4, n_kv_head=2)):
        with pytest.raises(ValueError, match="an eva layer needs"):
            DecodeConfig(97, **dict(base, **bad))
    ok = dict(base, window=32, eva_chunk=4)
    jamba._check(DecodeConfig(97, **ok))
    with pytest.raises(ValueError, match="an EVA layer is built without"):
        jamba._check(DecodeConfig(97, attn_gate="per_head", **ok))
    with pytest.raises(ValueError, match="norm_offset is an RMS norm's"):
        jamba._check(DecodeConfig(97, **dict(ok, norm="layer_norm",
                                             norm_offset=True)))
    with pytest.raises(ValueError, match="head_precision 'bf16'"):
        jamba._check(DecodeConfig(97, head_precision="bf16", **ok))


# -- the cache entries and the counts ---------------------------------------

def test_cache_spec_has_eva_entries(pred):
    spec = pred.cache_spec(SLOTS, SEQ)
    names = [e.name for e in spec]
    assert names == sorted(names) == [
        "keva_0", "keva_1", "keva_2", "veva_0", "veva_1", "veva_2"]
    assert all(tuple(e) == (e.name, (SLOTS, ROWS, H, DH), "float32", False)
               and e.stride == C for e in spec)
    assert {e.kind for e in spec} == {"eva"}
    assert pred.config.eva_rows == (N_SUM, W)
    _, feeds, fetches = pred._build("decode", SLOTS, SEQ, "greedy")
    assert feeds == ["tokens", "lengths", "seed"] + names
    assert len(fetches) == 2 + len(spec)
    # capacity: a row a chunk of max_len and the window's, whatever seq
    per_slot = N_LAYER * 2 * ROWS * H * DH * 4
    assert sum(e.nbytes for e in pred.cache_spec(1, SEQ)) == per_slot
    assert sum(e.nbytes for e in cache_spec(pred.config, 1, 64)) == per_slot
    assert kv_slab_slots(10 * per_slot + 1, pred.config, SEQ) == 10
    # K/V slabs of a row a position would be SEQ rows: 2.7 x these
    assert per_slot * SEQ == N_LAYER * 2 * SEQ * H * DH * 4 * ROWS
    with pytest.raises(ValueError, match="eva"):
        pred.cache_spec(SLOTS, SEQ, "int8")
    # an entry of another kind keeps its four fields and a stride of 1
    old = cache_spec(DecodeConfig(97, n_layer=1, n_head=4, d_model=64), 2, 16)
    assert all(e.stride == 1 and e.kind == "rows" for e in old)


def test_an_admission_replaces_both_entries_whole(pred):
    """What an admission writes: the prefill hands each entry over as a
    step finds it, (n, rows, heads, width) whatever the bucket, and the
    one jitted scatter replaces the slot's: the last occupant's block
    and summaries are gone, the neighbour's untouched."""
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=4)
    caches = [jnp.full(e.shape, 7.0, e.dtype) for e in srv._spec]
    outs, sp, _ = srv._prefill_prompts(_prompts([37], seed=5))
    sub = list(outs[1:1 + len(srv._spec)])
    assert sp == 64 and all(s.shape == (1, ROWS, H, DH) for s in sub)
    new = srv._scatter_prefill(caches, sub, [2], sp)
    for got, s in zip(new, sub):
        got = np.asarray(got)
        assert (got[2] == np.asarray(s[0])).all()
        assert (got[[0, 1, 3]] == 7.0).all()
    # 9 whole chunks of the prompt lie last first before the block, and
    # the block holds window 1's five rows
    k0 = np.asarray(sub[0][0])
    assert (k0[:N_SUM - 16] == 0).all() and (k0[N_SUM - 9:N_SUM] != 0).all()


def test_cache_spec_at_the_cells_sizes_holds_16_slots():
    """The cell's own numbers: 402.7 MB a slot, 6.44 GB for 16."""
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "evabyte.json")) as f:
        cfg = evabyte_lm.decode_config(json.load(f), "serve_closed")
    spec = cache_spec(cfg, 16, 16384)
    assert len(spec) == 8
    assert all(e.shape == (16, 3072, 32, 128) and e.stride == 16
               for e in spec)
    assert sum(e.nbytes for e in spec) == 16 * 402653184
    assert kv_slab_slots(6.5e9, cfg, 16384) == 16
    from paddle_tpu.models import jamba

    view = jamba.stream_view(cfg, 16384)
    assert (view.name, view.seq, view.k_block) == (
        "ptpu.eva_attn", 3072, (1, 1, 32, 128))
    # 128 rows a block: a window's 128 summaries are one block
    assert decode_stream.block_positions(view) == 128
    assert cfg.head_precision == "highest" and cfg.norm_offset


def test_server_books_window_rows_summary_rows_and_chunks_closed(pred):
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=4)
    assert srv._eva == (N_SUM, W, C) and srv._stream_rows is None
    before = {k: obs.EVA_ROWS.value(kind=k) for k in ("window", "summary")}
    lens = np.array([3, 0, 75, 64], np.int32)
    counts = srv._step_counts(lens, 3)
    per = W // C
    assert counts == {
        "active": 3, "attended": 4 + 12 + 1 + 4 * per,
        "streamed": SLOTS * ROWS, "state_bytes": 0,
        "eva_window_rows": 4 + 12 + 1, "eva_summary_rows": 4 * per,
        "eva_chunks_closed": 2}
    assert obs.EVA_ROWS.value(kind="window") - before["window"] == 17
    assert obs.EVA_ROWS.value(kind="summary") - before["summary"] == 4 * per
    # where a kernel streams blocks of 16 rows: a free slot one block; a
    # slot at 75 the blocks of rows [48, 76): 48 // 16 .. 75 // 16
    srv._stream_rows = 16
    assert srv._step_counts(lens, 3)["streamed"] == 16 * (1 + 1 + 2 + 2)
    prompts = _prompts([20, 75], seed=11)
    sc = srv._scatter_counts(2, prompts, bucket_rows=2 * 128)
    assert sc["entries"] == 6 and sc["state_slots"] == 0
    assert (sc["prompt_rows"], sc["bucket_rows"], sc["prompts"]) == (
        95, 256, 2)
    assert (sc["eva_window_rows"], sc["eva_summary_rows"]) == (20 + 11,
                                                               2 * per)
    # a query at t sees t mod 32 + 1 keys and 8 summaries a closed window
    by_hand = sum(t % W + 1 + per * (t // W) for n in (20, 75)
                  for t in range(n))
    assert sc["attn_pairs"] == by_hand == _eva_pairs(20, W, per) + _eva_pairs(
        75, W, per)


@pytest.mark.parametrize("kwargs", [
    {"speculative": True}, {"prefix_cache": True}, {"kv_dtype": "int8"}],
    ids=["speculative", "prefix_cache", "int8"])
def test_server_refuses_what_is_not_built_over_pooled_rows(pred, kwargs):
    with pytest.raises(ValueError, match="kind 'eva'"):
        DecodeServer(pred, slots=2, max_seq=SEQ, **kwargs)


# -- the manifests ----------------------------------------------------------

NEW_FIELDS = {"eva_chunk", "norm_offset", "head_precision"}


def test_manifest_round_trip(pred):
    cfg = evabyte_lm.decode_config(CFG, "serve")
    d = json.loads(json.dumps(cfg.to_dict()))
    again = DecodeConfig.from_dict(d)
    assert again.to_dict() == cfg.to_dict() == pred.config.to_dict()
    assert again.layer_kinds() == ["eva"] * 3 and again.has_eva
    assert not (again.has_state or again.has_ring or again.has_latent
                or again.is_opt_block)
    assert NEW_FIELDS <= set(d)
    assert (again.window, again.eva_chunk, again.norm_offset) == (W, C, True)
    assert again.rope == {"full": {"rotary_dim": DH, "theta": 1e5}}
    assert sorted(n for n in pred._state if ".l1.attention." in n) == sorted(
        "lm.l1.attention." + nm for nm in ("q.w", "k.w", "v.w", "o.w",
                                           "phi", "mu"))
    assert pred._state["lm.head.w"].shape == (64, 320)


@pytest.mark.parametrize("name,builder", [
    ("jamba2-3b", jamba_lm), ("laguna-xs.2", laguna_lm),
    ("phi4-mini-flash", phi4flash_lm), ("mistral-small-4", mistral4_lm),
    ("ling-3.0-flash", ling3_lm), ("dots3-note-prev", dots3_lm)])
def test_manifests_written_before_this_model_load_unchanged(name, builder):
    """The fields this model added are written only where set: the
    manifests of the six described models that stand hold none of them,
    come back as they were written, and describe no entry of a stride."""
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        cfg = builder.decode_config(json.load(f), "serve_closed")
    text = json.dumps(cfg.to_dict(), indent=2, sort_keys=True)
    assert not set(cfg.to_dict()) & NEW_FIELDS
    again = DecodeConfig.from_dict(json.loads(text))
    assert json.dumps(again.to_dict(), indent=2, sort_keys=True) == text
    assert not again.has_eva
    assert all(e.stride == 1 and e.kind != "eva"
               for e in cache_spec(again, 2, 64))
