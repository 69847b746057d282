"""A MiMo-V2-Flash-family LM (five sliding layers of a window of 8 with a
learned sink a head, on 4 key/value heads, to one full layer on 2; query
and key heads of 24 channels, 8 of them rotated, over value heads of 16
scaled by 0.707; a leading dense MLP, then a sigmoid router with a
selection bias over 32 experts, one chip's 8 held, no shared one)
through the normal serving path (`save_decode_model` ->
`DecodePredictor` -> `DecodeServer`) at a tiny size: prefill then decode
through slabs of FLAT rows and rings, LOGITS against the plain
reference's full forward pass (`benchmark/reference/mimo_v2.py`, which
imports nothing of the program), prompts shorter than the window, equal
to it and several wraps long; the sink's identity in the prefill kernel,
the lax prefill and the ring step; key/value heads and row widths by
layer kind in `cache_spec`; the new decode kernel in interpret mode; the
four shares of an expert-parallel deployment adding up to the uncut
layer; the manifest, what `_check` refuses of the new fields, and an
accepted configuration's graph as it was."""
import hashlib
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
from paddle_tpu.ops import attention as A  # noqa: E402
from paddle_tpu.ops import kv_cache as KV  # noqa: E402
from paddle_tpu.serving.decode import (  # noqa: E402
    DecodeConfig, DecodePredictor, DecodeServer, cache_spec,
    save_decode_model)

from benchmark.lib import weights  # noqa: E402
from benchmark.models import mimo_v2_lm  # noqa: E402
from benchmark.reference import mimo_v2 as ref  # noqa: E402


def _tiny(name):
    with open(os.path.join(_ROOT, "benchmark", "tests", "tiny", name)) as f:
        return json.load(f)


# hidden 64; layers full, sliding x4, full, sliding; 8 query heads of 24
# (8 rotated) over value heads of 16; 2 key/value heads on a full layer,
# 4 on a sliding one; a window of 8; 32 routed experts of 24, 4 a token,
# experts 0..7 held, no shared one
CFG = _tiny("mimo-v2-tiny.json")
SLOTS, SEQ, N_LAYER, WINDOW = 4, 128, 7, 8
FULL, SLIDING = (0, 5), (1, 2, 3, 4, 6)


def _seeded(cfg):
    specs = mimo_v2_lm.parameter_specs(cfg, "serve")
    return weights.seeded_weights(specs, 2 ** 31 + 7, mimo_v2_lm.init_rule)


def _pred(d, cfg, w):
    scope = fluid.Scope()
    for n in w:
        scope.set_var(n, w[n])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        save_decode_model(d, mimo_v2_lm.decode_config(cfg, "serve"), exe,
                          scope=scope)
    return DecodePredictor(d)


@pytest.fixture(scope="module")
def seeded():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def pred(tmp_path_factory, seeded):
    return _pred(str(tmp_path_factory.mktemp("mimo_model")), CFG, seeded)


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


K = 6
# shorter than the window of 8 (and a reply that stays inside it: 1 + 6);
# a reply that wraps the ring; equal to the window; one past it; several
# wraps; a dozen wraps that fill most of a bucket
PROBE_LENS = [1, 5, 8, 9, 29, 100]


@pytest.fixture(scope="module")
def probes(pred):
    out = []
    for at in range(0, len(PROBE_LENS), SLOTS):
        prompts = _prompts(PROBE_LENS[at:at + SLOTS], seed=3 + at)
        forced = _prompts([K + 1] * len(prompts), seed=40 + at)
        out += zip(prompts, forced, _rollout(pred, prompts, K, forced))
    return out


def _reference(w, text, rows, variant=""):
    """`rows` of the plain reference's logits over `text`, in one full
    forward pass. The reference is causal, so the text is padded to SEQ
    positions: its programs then compile once a file."""
    padded = np.zeros((SEQ,), np.int64)
    padded[:len(text)] = text
    return np.asarray(ref.serve_logits(
        w, jnp.asarray(padded), CFG, N_LAYER, rows=np.asarray(rows),
        variant=variant))


def _want(w, p, f, variant=""):
    return _reference(w, np.concatenate([p, f[:K]]),
                      np.arange(len(p) - 1, len(p) + K), variant)


# -- (a) prefill, then decode through slabs and rings ----------------------

@pytest.mark.parametrize("which", range(len(PROBE_LENS)),
                         ids=["len%d" % n for n in PROBE_LENS])
def test_prefill_then_decode_matches_the_reference(probes, seeded, which):
    """The last prompt position and six teacher-forced decode positions:
    a full layer's flat rows appended to and attended in place, a ring
    written at `position mod 8` and read with its sink, against one full
    forward pass that keeps nothing."""
    p, f, got = probes[which]
    assert _rel(got, _want(seeded, p, f)) < 2e-5


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_a_reference_that_leaves_a_part_out_is_told_apart(probes, seeded,
                                                          variant):
    """Each mechanism moves the logits by far more than the program
    differs from the whole reference: the sink, the value scale, the
    key/value heads by layer kind, either theta, the partial rotation,
    the window's last row, the routed experts, their renormalisation
    and the selection bias."""
    p, f, got = probes[PROBE_LENS.index(29)]
    assert _rel(got, _want(seeded, p, f, variant)) > 1e-3


def _is_greedy(seeded, prompt, generated):
    full = np.concatenate([prompt, generated])
    lg = _reference(seeded, full, np.arange(len(prompt) - 1, len(full) - 1))
    return lg.argmax(-1).tolist() == list(generated)


def test_a_request_admitted_beside_live_ones(pred, seeded):
    """Two slots, four requests: the second is admitted while the first
    is some steps into its reply, later ones reuse both slots at other
    lengths. Each answer is the reference's greedy rollout, which knows
    no slot and no last occupant: a ring or a flat row that leaks
    between neighbours, or a ring an admission did not replace whole,
    fails here."""
    prompts = _prompts([70, 6, 19, 81], seed=7)
    news = [7, 9, 7, 7]
    srv = DecodeServer(pred, slots=2, max_seq=SEQ, max_new_tokens=9)
    srv.start()
    futs = [srv.submit((prompts[0], np.array([news[0]], np.int64)))]
    deadline = time.time() + 120
    while len(srv.step_active_counts) < 3 and time.time() < deadline:
        time.sleep(0.005)
    futs += [srv.submit((p, np.array([n], np.int64)))
             for p, n in zip(prompts[1:], news[1:])]
    got = [np.asarray(f.result(timeout=300)[0]) for f in futs]
    srv.stop()
    assert [len(g) for g in got] == news
    assert all(_is_greedy(seeded, p, g) for p, g in zip(prompts, got))


# -- (b) the sink -----------------------------------------------------------

def _sink_case(t=256, h=4, hkv=2, dk=24, dv=16, seed=0):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(2, t, h, dk)), jnp.float32)
    k = jnp.asarray(r.normal(size=(2, t, hkv, dk)), jnp.float32)
    v = jnp.asarray(r.normal(size=(2, t, hkv, dv)), jnp.float32)
    sink = jnp.asarray(r.normal(size=(h,)) + 1.0, jnp.float32)
    return q, k, v, sink


def _appended_and_dropped(q, k, v, sink, window):
    """The softmax over a row's scores WITH the sink's column appended,
    the column dropped: the equation as the model's card writes it."""
    b, t, h, dk = q.shape
    g = h // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bthd,bshd->bhts", q, kk) / np.sqrt(dk)
        row, col = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        seen = (col <= row) & ((row - col < window) if window else True)
        s = jnp.where(seen, s, -jnp.inf)
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink[None, :, None, None], (b, h, t, 1))], axis=-1)
        a = jax.nn.softmax(s, axis=-1)[..., :t]
        return jnp.einsum("bhts,bshd->bthd", a, vv)


@pytest.mark.parametrize("window", [0, 8, 128], ids=["causal", "w8", "w128"])
def test_the_sink_in_the_lax_prefill(window):
    """`softmax x sigmoid(lse - s)` IS the softmax over the scores with
    the sink's column appended and dropped."""
    q, k, v, sink = _sink_case()
    with jax.default_matmul_precision("highest"):
        got = A.prefill_attention_reference(q, k, v, window, sink=sink)
        bare = A.prefill_attention_reference(q, k, v, window)
    want = _appended_and_dropped(q, k, v, sink, window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert float(jnp.max(jnp.abs(bare - want))) > 0.05  # the sink counts


@pytest.mark.parametrize("window", [0, 128], ids=["causal", "w128"])
def test_the_sink_in_the_prefill_kernel(window):
    """The flash forward in interpret mode hands back the row's
    log-sum-exp, and the sink enters by it; q and k of 24 channels are
    padded to the lanes, v keeps its 16; rows past a length give zeros.
    The kernel's operands are bfloat16, so it is held to the lax form on
    operands rounded the same way."""
    q, k, v, sink = _sink_case()
    lengths = jnp.asarray([256, 100], jnp.int32)
    got = A.prefill_attention(q, k, v, lengths, window=window, sink=sink,
                              interpret=True)
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    scale = 1.0 / np.sqrt(q.shape[-1])
    want = _appended_and_dropped(bf(q * scale) / scale, bf(k), bf(v), sink,
                                 window)
    assert got.shape == (2, 256, 4, 16)
    np.testing.assert_allclose(got[0], want[0], rtol=0.02, atol=0.02)
    np.testing.assert_allclose(got[1, :100], want[1, :100], rtol=0.02,
                               atol=0.02)
    bare = A.prefill_attention(q, k, v, lengths, window=window,
                               interpret=True)
    assert float(jnp.max(jnp.abs(bare[0] - want[0]))) > 0.05


@pytest.mark.parametrize("window", [0, 128], ids=["causal", "w128"])
def test_the_sink_in_the_kernels_last_write_is_the_share_applied_outside(
        window):
    """The kernel's last write times `sigmoid(m + log l - sink)` is, to
    float32's rounding, its output without a sink times `sink_share` of
    the log-sum-exp it hands back: the pass of XLA's that PR 57 took out
    of `prefill_attention`, applied here by hand; with a window (the
    band's one pass) and without (the walk over key blocks)."""
    q, k, v, sink = _sink_case()
    b, t, h, _ = q.shape
    lengths = jnp.asarray([256, 100], jnp.int32)
    block_q, block_k, band = A.prefill_blocks(window, t)
    assert band == bool(window)

    def call(sink):
        return A._mha_fwd_call_bthd(
            A.flash_operand(q / np.sqrt(q.shape[-1])), A.flash_operand(k),
            A.flash_operand(v), h, True, block_q, block_k, True,
            window=window, lengths=lengths, out_dtype=jnp.float32,
            group=h // k.shape[2], sink=sink, band=band)

    inside, lse_in = call(sink)
    bare, lse = call(None)
    np.testing.assert_array_equal(lse_in, lse)
    share = A.sink_share(jnp.swapaxes(lse.reshape(b, h, t), 1, 2), sink)
    outside = bare.reshape(b, t, h, -1) * share[..., None]
    np.testing.assert_allclose(inside.reshape(b, t, h, -1), outside,
                               rtol=2e-6, atol=1e-7)
    got = A.prefill_attention(q, k, v, lengths, window=window, sink=sink,
                              interpret=True)
    np.testing.assert_array_equal(
        got, inside.reshape(b, t, h, -1)[..., :v.shape[-1]])


@pytest.mark.parametrize("held", [3, 8, 21], ids=["part", "full", "wrapped"])
def test_the_sink_in_the_ring_step(held):
    """One query against a ring of 8 rows holding `held` positions: the
    live rows under the sink equal the appended-and-dropped softmax over
    those rows, whatever order the ring keeps them in."""
    r = np.random.default_rng(held)
    h, hkv, dk, dv, w = 4, 2, 24, 16, 8
    q = jnp.asarray(r.normal(size=(1, 1, h, dk)), jnp.float32)
    k = jnp.asarray(r.normal(size=(1, w, hkv, dk)), jnp.float32)
    v = jnp.asarray(r.normal(size=(1, w, hkv, dv)), jnp.float32)
    sink = jnp.asarray(r.normal(size=(h,)) + 1.0, jnp.float32)
    live = min(held, w)
    with jax.default_matmul_precision("highest"):
        got = KV.decode_attn_ring(q, k, v, jnp.asarray([held], jnp.int32),
                                  sink=sink)
        kk = jnp.repeat(k[0, :live], h // hkv, axis=1)
        vv = jnp.repeat(v[0, :live], h // hkv, axis=1)
        s = jnp.einsum("hd,shd->hs", q[0, 0], kk) / np.sqrt(dk)
        a = jax.nn.softmax(jnp.concatenate([s, sink[:, None]], axis=-1),
                           axis=-1)[:, :live]
        want = jnp.einsum("hs,shd->hd", a, vv)
    assert got.shape == (1, 1, h, dv)
    np.testing.assert_allclose(got[0, 0], want, rtol=2e-5, atol=2e-6)


# -- (c) the cache manager's one description -------------------------------

def test_cache_spec_has_heads_and_widths_by_layer_kind(pred):
    """A full layer keeps FLAT rows, K 2 x 24 beside V 2 x 16 floats; a
    sliding layer rings of its own 4 heads, K rows of 24 beside V rows
    of 16; sorted by name, the rows written by `[:sp]` and the rings
    whole."""
    spec = pred.cache_spec(SLOTS, SEQ)
    assert [e.name for e in spec] == sorted(
        ["kcache_%d" % i for i in FULL] + ["vcache_%d" % i for i in FULL]
        + ["kring_%d" % i for i in SLIDING]
        + ["vring_%d" % i for i in SLIDING])
    by = {e.name: e for e in spec}
    for i in FULL:
        assert by["kcache_%d" % i].shape == (SLOTS, SEQ, 2 * 24)
        assert by["vcache_%d" % i].shape == (SLOTS, SEQ, 2 * 16)
        assert by["kcache_%d" % i].kind == by["vcache_%d" % i].kind == "rows"
    for i in SLIDING:
        assert by["kring_%d" % i].shape == (SLOTS, WINDOW, 4, 24)
        assert by["vring_%d" % i].shape == (SLOTS, WINDOW, 4, 16)
        assert by["kring_%d" % i].kind == by["vring_%d" % i].kind == "ring"
        assert not by["kring_%d" % i].per_position
    cfg = pred.config
    assert cfg.uneven_kv and cfg.has_ring and not cfg.has_latent
    assert (cfg.kv_heads("attention"), cfg.kv_heads("sliding")) == (2, 4)
    assert cfg.kv_rows("attention") == ((48,), (32,))
    assert cfg.kv_rows("sliding") == ((4, 24), (4, 16))
    per_slot = sum(e.nbytes for e in spec) // SLOTS
    assert per_slot == (2 * SEQ * 2 * 40 + 5 * WINDOW * 4 * 40) * 4


def test_server_books_live_rows_ring_rows_and_pairs(pred):
    """The numerators of the cell's roofline readers: a step's live slab
    rows and ring rows, an admission's pairs inside the causal triangle
    (a full layer) and inside the window (a sliding one)."""
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=4)
    lens = np.array([5, 0, 29, 100], np.int32)
    c = srv._step_counts(lens, 3)
    assert c["attended"] == 5 + 29 + 100 + 3
    assert c["ring_rows"] == 6 + 8 + 8
    assert c["streamed"] == SLOTS * SEQ  # the CPU reads whole slabs
    assert {"expert_pairs", "experts_active"} <= set(c)
    prompts = _prompts([5, 29], seed=9)
    s = srv._scatter_counts(2, prompts, bucket_rows=64)
    assert (s["prompt_rows"], s["bucket_rows"], s["prompts"]) == (34, 64, 2)
    assert s["attn_pairs"] == 5 * 6 // 2 + 29 * 30 // 2
    assert s["window_pairs"] == 5 * 6 // 2 + (8 * 9 // 2 + 21 * 8)
    assert s["ring_rows"] == 5 + 8


# -- (d) the full layer's decode kernel --------------------------------------

@pytest.mark.parametrize("lens", [(1, 63, 200), (64, 128, 256), (65, 129, 0)],
                         ids=["inside", "at", "past"])
@pytest.mark.parametrize("hkv,dk,dv", [(4, 192, 128), (8, 64, 128),
                                       (2, 320, 128)],
                         ids=["4x192-128", "8x64-128", "2x320-128"])
def test_the_uneven_decode_kernel_in_interpret_mode(lens, hkv, dk, dv):
    """`ptpu.decode_attn_uneven` over slabs of flat rows in blocks of 64:
    lengths that end inside, at and past a block (and an empty slot),
    against the exact lax path over the same rows read as (S, heads,
    width). Head i's keys are read as the lane-aligned window that holds
    them; the zeros around the query add nothing."""
    r = np.random.default_rng(hkv * dk)
    b, s, h = 3, 256, 16
    q = jnp.asarray(r.normal(size=(b, 1, h, dk)), jnp.float32)
    k = jnp.asarray(r.normal(size=(b, s, hkv * dk)), jnp.float32)
    v = jnp.asarray(r.normal(size=(b, s, hkv * dv)), jnp.float32)
    lengths = jnp.asarray(lens, jnp.int32)
    view = KV.uneven_view(s, h, hkv, dk, dv, jnp.float32, 64)
    assert KV._DS.block_positions(view) == 64 and view.whole_tiles
    with jax.default_matmul_precision("highest"):
        want = KV.decode_attention_reference(
            q, k.reshape(b, s, hkv, dk), v.reshape(b, s, hkv, dv), lengths)
        got = KV.pallas_decode_attention_uneven(q, k, v, lengths, hkv,
                                                block_s=64, interpret=True)
    assert got.shape == (b, 1, h, dv)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    if 0 in lens:
        assert not np.asarray(got[lens.index(0)]).any()


def test_the_uneven_view_reads_keys_at_whole_tiles():
    """4 heads of 192: 256-lane windows at lanes 0, 128, 384 and 512,
    the head's channels 0 or 64 lanes in; rows that are no whole tiles
    have no kernel and the dispatch keeps the lax path."""
    assert KV._uneven_windows(4, 192) == (
        256, [(0, 0), (128, 64), (384, 0), (512, 64)])
    assert KV._uneven_windows(8, 128) == (
        128, [(i * 128, 0) for i in range(8)])
    q = jnp.arange(2 * 16 * 192, dtype=jnp.float32).reshape(2, 1, 16, 192)
    laid = KV.uneven_queries(q, 4)
    assert laid.shape == (2, 1, 16, 256)
    np.testing.assert_array_equal(laid[:, :, 4:8, 64:], q[:, :, 4:8])
    assert not np.asarray(laid[:, :, 4:8, :64]).any()
    np.testing.assert_array_equal(laid[:, :, :4, :192], q[:, :, :4])
    assert not KV.uneven_view(256, 8, 2, 24, 16, jnp.float32).whole_tiles
    r = np.random.default_rng(1)
    k = jnp.asarray(r.normal(size=(1, 128, 48)), jnp.float32)
    v = jnp.asarray(r.normal(size=(1, 128, 32)), jnp.float32)
    q = jnp.asarray(r.normal(size=(1, 1, 8, 24)), jnp.float32)
    n = jnp.asarray([77], jnp.int32)
    np.testing.assert_array_equal(
        KV.decode_attention_uneven(q, k, v, n, 2),
        KV.decode_attention_reference(q, k.reshape(1, 128, 2, 24),
                                      v.reshape(1, 128, 2, 16), n))


# -- (e) one chip's share: a quarter -----------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test: 32 routed experts in 4 shares of 8, 4 a
    token, a sigmoid router with a selection bias and NO shared expert.
    `ops/moe.py` with `experts_held` = each share in turn: the routed
    parts of all four == the uncut layer, by the program's ops and by
    the reference alike; every (token, expert) pair falls on exactly one
    share."""
    from paddle_tpu.ops import moe

    r = np.random.default_rng(5)
    d, f, n, k, per = 32, 8, 32, 4, 8
    x = jnp.asarray(r.normal(size=(11, d)), jnp.float32)
    p = {"router.w": jnp.asarray(r.normal(size=(d, n)) * 0.3, jnp.float32),
         "router.bias": jnp.asarray(r.normal(size=(n,)) * 0.2, jnp.float32)}
    for nm, shape in (("gate", (n, d, f)), ("up", (n, d, f)),
                      ("down", (n, f, d))):
        p["experts.%s.w" % nm] = jnp.asarray(r.normal(size=shape) * 0.1,
                                             jnp.float32)
    cfg = dict(CFG, num_experts_per_tok=k)
    whole = np.asarray(ref.moe(p, x, dict(cfg, experts_held=[0, n]),
                               "highest"))
    idx, w = moe.moe_route(x, p["router.w"], k, 1.0, score="sigmoid",
                           bias=p["router.bias"])
    # the bias chooses and the score weighs: another choice than by score
    assert (np.sort(np.asarray(idx), -1) != np.sort(np.asarray(
        moe.moe_route(x, p["router.w"], k, 1.0)[0]), -1)).any()
    total, total_ref, loads = 0.0, 0.0, 0
    for lo in range(0, n, per):
        held = {"experts.%s.w" % nm: p["experts.%s.w" % nm][lo:lo + per]
                for nm in ("gate", "up", "down")}
        part, load = moe.moe_experts(
            x, idx, w, held["experts.gate.w"], held["experts.up.w"],
            held["experts.down.w"], lo=lo)
        total = total + np.asarray(part)
        loads += int(load.sum())
        total_ref = total_ref + np.asarray(ref.moe(
            dict(p, **held), x, dict(cfg, experts_held=[lo, lo + per]),
            "highest"))
    assert loads == 11 * k  # every pair fell on exactly one share
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(total_ref, whole, rtol=2e-4, atol=2e-5)


# -- (f) the manifest, and what is refused -----------------------------------

def test_manifest_round_trip(pred, seeded):
    d = json.loads(json.dumps(pred.config.to_dict()))
    again = DecodeConfig.from_dict(d)
    assert again.to_dict() == pred.config.to_dict()
    assert (d["n_kv_head_by_kind"], d["v_head_dim"], d["attn_sink"],
            d["attn_value_scale"]) == ({"full": 2, "sliding": 4}, 16,
                                       ["sliding"], 0.707)
    assert "d_shared_expert" not in d  # 0: written only where set
    assert again.layer_kinds() == ["attention"] + ["sliding"] * 4 + [
        "attention", "sliding"]
    assert sorted(n for n in pred._state if ".l1.attention." in n) == sorted(
        "lm.l1.attention." + nm for nm in ("q.w", "k.w", "v.w", "o.w",
                                           "sink"))
    assert sorted(n for n in pred._state if ".l0.attention." in n) == sorted(
        "lm.l0.attention.%s.w" % nm for nm in "qkvo")
    assert tuple(seeded["lm.l1.attention.k.w"].shape) == (64, 4 * 24)
    assert tuple(seeded["lm.l1.attention.v.w"].shape) == (64, 4 * 16)
    assert tuple(seeded["lm.l0.attention.k.w"].shape) == (64, 2 * 24)
    assert tuple(seeded["lm.l0.attention.o.w"].shape) == (8 * 16, 64)
    assert tuple(seeded["lm.l1.attention.sink"].shape) == (8,)
    assert sorted(n for n in pred._state if ".l1.moe." in n) == sorted(
        "lm.l1.moe." + nm for nm in (
            "router.w", "router.bias", "experts.gate.w", "experts.up.w",
            "experts.down.w"))


_ACCEPTED = [
    # a manifest without the four fields, byte for byte, and its graphs:
    # (builder, tiny configuration, sha256 of the manifest's JSON, of the
    # prefill and of the decode Program), read on the tree before the
    # fields existed
    ("laguna_lm", "laguna-tiny.json"),
    ("phi4flash_lm", "phi4flash-tiny.json"),
    ("solar_open2_lm", "solar-open2-tiny.json"),
]


def _digests(builder, tiny):
    import importlib

    model = importlib.import_module("benchmark.models." + builder)
    cfg = model.decode_config(_tiny(tiny), "serve")
    manifest = json.dumps(cfg.to_dict(), sort_keys=True)
    pred = DecodePredictor.__new__(DecodePredictor)  # graph builder only
    pred.config = cfg
    pred.sample_k, pred.sample_p, pred.temperature = 40, 0.9, 1.0
    out = [hashlib.sha256(manifest.encode()).hexdigest()[:16]]
    for kind, b, s in (("prefill", 2, 32), ("decode", 2, 64)):
        program = pred._build(kind, b, s, "greedy")[0]
        ops = [(op.type, sorted((k, sorted(v)) for k, v in
                                op.inputs.items()),
                sorted((k, repr(v)) for k, v in op.attrs.items()))
               for op in program.global_block().ops]
        out.append(hashlib.sha256(repr(ops).encode()).hexdigest()[:16])
    return manifest, out


@pytest.mark.parametrize("builder,tiny", _ACCEPTED,
                         ids=[t[1].split(".")[0] for t in _ACCEPTED])
def test_an_accepted_manifest_and_its_graphs_are_what_they_were(builder,
                                                                tiny):
    """A manifest without the new fields round-trips byte for byte and
    carries none of them; its prefill and decode Programs (Laguna's: a
    shared expert of width 8 beside the routed ones; Phi's flat
    `kv_row`; Solar's grouped slab) hold the ops, inputs and attributes
    they held before `d_shared_expert` 0, key/value heads by layer kind,
    the sink and the value scale existed."""
    manifest, got = _digests(builder, tiny)
    again = DecodeConfig.from_dict(json.loads(manifest))
    assert json.dumps(again.to_dict(), sort_keys=True) == manifest
    assert not {"n_kv_head_by_kind", "attn_sink",
                "attn_value_scale"} & set(json.loads(manifest))
    assert not again.uneven_kv and again.v_head == again.d_head or (
        again.has_latent)
    assert again.kv_rows("attention") == (again.kv_row, again.kv_row)
    assert got == _WAS[tiny], (tiny, got)


# sha256[:16] of (manifest, prefill ops, decode ops) at 970e9af
_WAS = {
    "laguna-tiny.json": ["ff9fe950f9e9454f", "e177bbaa33b75788",
                         "3303bbc24398153e"],
    "phi4flash-tiny.json": ["3056b30adabe55bc", "98a0e4047dbd3442",
                            "7459c9179f7283d1"],
    "solar-open2-tiny.json": ["f3fbae64632d5502", "50cf3189d41036eb",
                              "3837d2f591a421ee"],
}


def test_config_and_builders_refuse_what_is_not_built():
    from paddle_tpu.models import jamba

    base = dict(n_layer=2, n_head=8, d_model=64, norm="rms_norm",
                ffn="gated_silu", positions=False, biases=False,
                n_kv_head=2, head_dim=24, attn_types=["full", "sliding"],
                window=8)
    jamba._check(DecodeConfig(97, n_kv_head_by_kind={"sliding": 4},
                              v_head_dim=16, attn_sink=["sliding"],
                              attn_value_scale=0.5, **base))
    with pytest.raises(ValueError, match="n_kv_head_by_kind"):
        DecodeConfig(97, n_kv_head_by_kind={"window": 4}, **base)
    with pytest.raises(ValueError, match="do not divide"):
        DecodeConfig(97, n_kv_head_by_kind={"sliding": 3}, **base)
    # a sink where no graph builds one
    with pytest.raises(ValueError, match="attn_sink"):
        jamba._check(DecodeConfig(97, attn_sink=["full"], **base))
    with pytest.raises(ValueError, match="attn_sink"):
        jamba._check(DecodeConfig(97, attn_sink=["sliding"], **dict(
            base, attn_types=["full", "full"])))
    flat = dict(base, diff_attn=True, head_dim=16)
    with pytest.raises(ValueError, match="attn_sink"):
        jamba._check(DecodeConfig(97, attn_sink=["sliding"], **flat))
    # a value width or heads by kind under differential attention's one
    # flat `kv_row`
    with pytest.raises(ValueError, match="n_kv_head_by_kind"):
        jamba._check(DecodeConfig(97, v_head_dim=8, **flat))
    with pytest.raises(ValueError, match="n_kv_head_by_kind"):
        jamba._check(DecodeConfig(97, n_kv_head_by_kind={"sliding": 4},
                                  **flat))
    with pytest.raises(ValueError, match="attn_value_scale"):
        jamba._check(DecodeConfig(97, attn_value_scale=0.5, **flat))
    # a latent layer reads `v_head_dim` for its own heads: the attention
    # layers beside it keep `head_dim`
    latent = DecodeConfig(
        97, n_layer=2, n_head=4, d_model=64, norm="rms_norm",
        ffn="gated_silu", positions=False, biases=False,
        layer_types=["latent", "attention"], kv_lora_rank=24,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=8)
    assert latent.v_head == latent.d_head == 16 and not latent.uneven_kv
    # no shared expert is a layer the program builds; a negative width not
    experts = dict(base, ffn_types=["dense", "experts"], n_expert=8,
                   expert_top_k=2, d_expert=8)
    jamba._check(DecodeConfig(97, d_shared_expert=0, **experts))
    with pytest.raises(ValueError, match="shared"):
        jamba._check(DecodeConfig(97, d_shared_expert=-8, **experts))
    # what the source's keys say and no graph builds is refused by the
    # builder, never ignored
    for key, value in (("add_full_attention_sink_bias", True),
                       ("n_shared_experts", 1), ("n_group", 2),
                       ("topk_method", "greedy"),
                       ("scoring_func", "softmax"),
                       ("swa_head_dim", 128), ("attention_bias", True)):
        with pytest.raises(AssertionError):
            mimo_v2_lm.decode_config(dict(CFG, **{key: value}), "serve")
