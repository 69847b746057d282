"""The ops a Laguna-family block adds, each against the plain reference
(`benchmark/reference/laguna.py`) or a closed form: the rotary op of
three kinds; the router; the expert layer that drops nothing, and the
rule that the shares of an expert-parallel
deployment add up to the uncut layer; window attention in prefill; the
ring a sliding layer keeps; their shape-inference rules."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.ops import attention as attn_ops  # noqa: E402
from paddle_tpu.ops import kv_cache, moe, rope  # noqa: E402

from benchmark.reference import laguna as ref  # noqa: E402

ROPES = {
    "plain": {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
    "partial": {"rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 0.5},
    "yarn": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
             "original_max_position_embeddings": 4096, "beta_slow": 1,
             "beta_fast": 64, "attention_factor": 1.4158883083359672,
             "partial_rotary_factor": 0.5},
}
DH = 128


def _cases(argnames, values, ids=None):
    """`pytest.mark.parametrize` as ONE test item that runs every case.
    xdist's `--dist loadfile` hands out files in the order of their item
    counts, largest first; with a case an item this file was scheduled
    among the first and moved every file after it to another worker and
    another moment, and `test_dataloader.py`'s zero-copy test, which
    passes or fails with what shares its worker and its moment (PR 31:
    reproduced on the parent tree), failed in every whole run. With few
    items this file is handed out after the files the suite had before
    it, which keep the schedule they had."""
    import inspect

    names = [a.strip() for a in argnames.split(",")]

    def deco(fn):
        fixtures = [p for p in inspect.signature(fn).parameters
                    if p not in names]

        def run(**kw):
            for v in values:
                fn(**kw, **dict(zip(names, v if len(names) > 1 else (v,))))

        run.__signature__ = inspect.Signature(
            [inspect.Parameter(p, inspect.Parameter.POSITIONAL_OR_KEYWORD)
             for p in fixtures])
        run.__name__, run.__doc__ = fn.__name__, fn.__doc__
        return run
    return deco


def _rope_args(p):
    """The op's arguments for one `rope_parameters` entry."""
    kw = {"rotary_dim": int(DH * p["partial_rotary_factor"]),
          "theta": float(p["rope_theta"])}
    yarn = None
    if p["rope_type"] == "yarn":
        yarn = {"factor": p["factor"],
                "original_max_position":
                    p["original_max_position_embeddings"],
                "beta_fast": p["beta_fast"], "beta_slow": p["beta_slow"]}
    return kw, yarn, float(p.get("attention_factor", 1.0))


@_cases("kind", sorted(ROPES))
def test_rope_matches_the_reference(kind):
    p = ROPES[kind]
    kw, yarn, factor = _rope_args(p)
    inv = rope.rope_inv_freq(kw["rotary_dim"], kw["theta"], yarn)
    np.testing.assert_allclose(inv, ref.inv_freq(p, DH), rtol=1e-12)
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(2, 9, 3, DH)), jnp.float32)
    want = np.stack([ref.rotate(x[b], p) for b in range(2)])
    got = rope.rope(x, None, inv, factor)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # a decode step: one row a slot at the slot's own position
    pos = jnp.asarray([3, 4000])
    one = rope.rope(x[:, :1], pos, inv, factor)
    for b in range(2):
        w = ref.rotate(x[b, :1], p, positions=[int(pos[b])])
        np.testing.assert_allclose(one[b], w, rtol=1e-4, atol=1e-4)


@_cases("kind", sorted(ROPES))
def test_rope_at_position_zero_is_the_closed_form(kind):
    """cos 0 = 1, sin 0 = 0: the rotated channels come back times the
    attention factor, the rest untouched."""
    p = ROPES[kind]
    kw, yarn, factor = _rope_args(p)
    inv = rope.rope_inv_freq(kw["rotary_dim"], kw["theta"], yarn)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 1, 2, DH)),
                    jnp.float32)
    got = np.asarray(rope.rope(x, jnp.zeros((1,), jnp.int32), inv, factor))
    r = kw["rotary_dim"]
    np.testing.assert_allclose(got[..., :r], np.asarray(x)[..., :r] * factor,
                               rtol=1e-6)
    np.testing.assert_array_equal(got[..., r:], np.asarray(x)[..., r:])


def test_yarn_ramp_of_the_published_numbers():
    """Laguna-XS.2's full layers: r = 64, base 5e5, factor 64 over 4096
    original positions, beta 64 / 1: channels below `low` keep the plain
    frequency, those above `high` are divided by the factor."""
    inv = rope.rope_inv_freq(64, 500000.0, {
        "factor": 64, "original_max_position": 4096, "beta_fast": 64,
        "beta_slow": 1})
    plain = 500000.0 ** (-np.arange(32) * 2.0 / 64)
    ratio = plain / inv
    assert abs(ratio[0] - 1.0) < 1e-9 and abs(ratio[-1] - 64.0) < 1e-6
    assert (np.diff(ratio) > -1e-9).all() and 1 < ratio[12] < 64


# -- the router ---------------------------------------------------------------

@pytest.mark.parametrize("score", moe.ROUTER_SCORES)
def test_route_renormalises_scales_and_breaks_ties_low(score):
    x = jnp.eye(3, 4, dtype=jnp.float32)
    w = jnp.asarray([[2.0, 0.0, 2.0, -1.0, 2.0]] * 4, jnp.float32)
    idx, wt = moe.moe_route(x, w, 2, scale=2.5, score=score)
    # experts 0, 2 and 4 tie: the two of lower index
    assert idx.tolist() == [[0, 2]] * 3 and idx.dtype == jnp.int32
    np.testing.assert_allclose(wt, np.full((3, 2), 1.25), rtol=1e-6)
    with pytest.raises(ValueError, match="score function 'tanh' is not "
                                         "built .sigmoid, softmax are."):
        moe.moe_route(x, w, 2, score="tanh")


@pytest.mark.parametrize("score", moe.ROUTER_SCORES)
def test_route_weights_are_the_scores_renormalised_over_the_chosen(score):
    """The k largest of the scores over ALL experts, renormalised: under
    "softmax" that is a softmax over the chosen LOGITS alone (the
    denominator over all experts cancels), under "sigmoid" the chosen
    sigmoids over their sum; the same experts either way (both scores
    rise with the logit)."""
    r = np.random.default_rng(11)
    x = jnp.asarray(r.normal(size=(7, 16)), jnp.float32)
    w = jnp.asarray(r.normal(size=(16, 12)), jnp.float32)
    idx, wt = moe.moe_route(x, w, 3, scale=1.0, score=score)
    logits = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    want_idx = np.argsort(-logits, axis=-1, kind="stable")[:, :3]
    assert np.asarray(idx).tolist() == want_idx.tolist()
    chosen = np.take_along_axis(logits, want_idx, axis=-1)
    if score == "softmax":
        e = np.exp(chosen - chosen.max(-1, keepdims=True))
    else:
        e = 1.0 / (1.0 + np.exp(-chosen))
    np.testing.assert_allclose(wt, e / e.sum(-1, keepdims=True), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(wt).sum(-1), 1.0, rtol=1e-6)


def _route_written_out(x, w, b, k, n_group, topk_group, scale):
    """DeepSeek-V3's `noaux_tc` by hand, in float64: sigmoid scores,
    chosen by score + bias inside the best groups (a group's score the
    sum of its two largest), weighted by score; ties to the lower
    index."""
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                               @ np.asarray(w, np.float64))))
    c = s if b is None else s + np.asarray(b, np.float64)
    n, e = c.shape
    idx = np.zeros((n, k), np.int64)
    for t in range(n):
        per = c[t].reshape(n_group, e // n_group)
        score = np.sort(per, axis=-1)[:, -2:].sum(-1) if n_group > 1 \
            else np.zeros(1)
        keep = np.argsort(-score, kind="stable")[:topk_group]
        masked = np.full(e, -np.inf)
        for g in keep:
            lo = g * (e // n_group)
            masked[lo:lo + e // n_group] = c[t, lo:lo + e // n_group]
        idx[t] = np.argsort(-masked, kind="stable")[:k]
    chosen = np.take_along_axis(s, idx, axis=-1)
    return idx, scale * chosen / chosen.sum(-1, keepdims=True)


@pytest.mark.parametrize("bias,n_group,topk_group", [
    (True, 1, 1), (False, 8, 4), (True, 8, 4), (True, 4, 1), (True, 8, 8)],
    ids=["bias", "groups", "bias+groups", "one-group-kept", "all-kept"])
def test_route_with_a_selection_bias_and_groups(bias, n_group, topk_group):
    """Chosen by `s + b`, weighted by `s`; the choice limited to the
    `topk_group` groups whose two best biased scores sum highest: against
    the rule written out by hand."""
    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(40, 24)), jnp.float32)
    w = jnp.asarray(r.normal(size=(24, 64)) * 0.4, jnp.float32)
    b = jnp.asarray(r.normal(size=(64,)) * 0.2, jnp.float32) if bias else None
    idx, wt = moe.moe_route(x, w, 4, scale=2.5, bias=b, n_group=n_group,
                            topk_group=topk_group)
    want_idx, want_w = _route_written_out(x, w, b, 4, n_group, topk_group,
                                          2.5)
    assert np.asarray(idx).tolist() == want_idx.tolist()
    np.testing.assert_allclose(wt, want_w, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(wt).sum(-1), 2.5, rtol=1e-6)
    groups = np.asarray(idx) // (64 // n_group)
    assert max(len(set(g)) for g in groups.tolist()) <= topk_group
    if bias:  # the bias changes choices, and never a weight's formula
        plain, _ = moe.moe_route(x, w, 4, scale=2.5, n_group=n_group,
                                 topk_group=topk_group)
        assert (np.asarray(plain) != np.asarray(idx)).any()


def test_route_breaks_ties_low_among_experts_and_groups():
    """Equal scores everywhere: the lower groups stay, and within them
    the lower experts."""
    x = jnp.ones((2, 4), jnp.float32)
    w = jnp.zeros((4, 16), jnp.float32)
    idx, wt = moe.moe_route(x, w, 4, bias=jnp.zeros((16,)), n_group=4,
                            topk_group=2)
    assert idx.tolist() == [[0, 1, 2, 3]] * 2
    np.testing.assert_allclose(wt, 0.25)
    # a bias lifts expert 9 of group 2: that group stays with group 0,
    # and expert 9 comes first
    b = jnp.zeros((16,)).at[9].set(0.1)
    idx, wt = moe.moe_route(x, w, 3, bias=b, n_group=4, topk_group=2)
    assert idx.tolist() == [[9, 0, 1]] * 2
    np.testing.assert_allclose(wt, 1 / 3, rtol=1e-6)  # weighted by s alone


def test_route_without_bias_and_groups_is_the_graph_it_was():
    """`n_group` 1 and no bias lower to the text they always have: the
    Laguna and Mistral cells' executables do not change."""
    x, w = jnp.zeros((6, 8)), jnp.zeros((8, 16))
    old = jax.jit(lambda a, b: moe.moe_route(a, b, 4, 2.5, "sigmoid")
                  ).lower(x, w).as_text()
    new = jax.jit(lambda a, b: moe.moe_route(
        a, b, 4, 2.5, "sigmoid", None, 1, 1)).lower(x, w).as_text()
    assert old == new and "top_k" in old or "TopK" in old or "sort" in old
    grouped = jax.jit(lambda a, b: moe.moe_route(
        a, b, 4, 2.5, "sigmoid", None, 4, 2)).lower(x, w).as_text()
    assert grouped != old


# -- the expert layer ----------------------------------------------------------

D, F, E, K = 32, 16, 16, 4
CFG = {"num_experts_per_tok": K, "moe_routed_scaling_factor": 2.5,
       "experts_held": [0, E], "model": {"router_score": "sigmoid"}}


@pytest.fixture(scope="module")
def layer():
    r = np.random.default_rng(2)

    def arr(*shape, s=0.2):
        return jnp.asarray(r.normal(size=shape) * s, jnp.float32)

    return {"router.w": arr(D, E, s=1.0), "experts.gate.w": arr(E, D, F),
            "experts.up.w": arr(E, D, F), "experts.down.w": arr(E, F, D),
            "shared.gate.w": arr(D, F), "shared.up.w": arr(D, F),
            "shared.down.w": arr(F, D)}


def _share(p, lo, hi):
    return (p["experts.gate.w"][lo:hi], p["experts.up.w"][lo:hi],
            p["experts.down.w"][lo:hi])


@_cases("n,block", [(40, 4096), (40, 64), (700, 512)])
def test_experts_match_the_reference(layer, n, block, monkeypatch):
    """The grouped product against the reference's Python loop over
    experts: a decode step's few tokens in one block and in three, and
    700 tokens x 4 pairs across six blocks of 512."""
    monkeypatch.setattr(moe, "_BLOCK_PAIRS", block)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(n, D)),
                    jnp.float32)
    idx, w = moe.moe_route(x, layer["router.w"], K, 2.5)
    got, load = moe.moe_experts(x, idx, w, *_share(layer, 0, E))
    want = ref.moe(layer, x, CFG, "highest", shared=False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert load.tolist() == [int((np.asarray(idx) == e).sum())
                             for e in range(E)]
    assert int(load.sum()) == n * K


@_cases("block", [4096, 256])
def test_no_token_is_dropped_when_all_pick_one_expert(layer, block,
                                                      monkeypatch):
    """Every token's pairs land on the same 4 experts (a router whose
    columns 3, 5, 6, 9 dominate): expert 5 receives all 300 tokens, 19x
    the even share, and every one of them is computed. A capacity
    factor would have zeroed most."""
    monkeypatch.setattr(moe, "_BLOCK_PAIRS", block)
    hot = np.full((D, E), 0.0, np.float32)
    x = jnp.abs(jnp.asarray(np.random.default_rng(4).normal(size=(300, D)),
                            jnp.float32))
    hot[:, [3, 5, 6, 9]] = [1.0, 4.0, 2.0, 3.0]
    p = dict(layer, **{"router.w": jnp.asarray(hot)})
    idx, w = moe.moe_route(x, p["router.w"], K, 2.5)
    assert set(np.asarray(idx).ravel().tolist()) == {3, 5, 6, 9}
    got, load = moe.moe_experts(x, idx, w, *_share(p, 4, 8), lo=4)
    assert load.tolist() == [0, 300, 300, 0]
    want = ref.moe(dict(p, **dict(zip(
        ("experts.gate.w", "experts.up.w", "experts.down.w"),
        _share(p, 4, 8)))), x, CFG, "highest", held=(4, 8), shared=False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(got).min(axis=-1).max()) > 0  # no zeroed token


@_cases("tokens", [300, 7])
def test_four_shares_and_one_shared_expert_add_up(layer, tokens):
    """`model-configs` 4: chip c of 4 holds experts [4c, 4c + 4); the
    router runs over all 16 on every chip; the parts the four shares
    give, with the shared expert (which every chip computes alike)
    counted ONCE, add up to the uncut layer of the reference."""
    x = jnp.asarray(np.random.default_rng(5).normal(size=(tokens, D)),
                    jnp.float32)
    idx, w = moe.moe_route(x, layer["router.w"], K, 2.5)
    parts, loads = [], []
    for c in range(4):
        out, load = moe.moe_experts(x, idx, w,
                                    *_share(layer, 4 * c, 4 * c + 4),
                                    lo=4 * c)
        parts.append(out)
        loads.append(load)
    total = sum(parts) + moe.moe_shared(
        x, layer["shared.gate.w"], layer["shared.up.w"],
        layer["shared.down.w"])
    uncut = ref.moe(layer, x, CFG, "highest")  # all 16 and the shared one
    np.testing.assert_allclose(total, uncut, rtol=2e-4, atol=2e-5)
    assert int(sum(l.sum() for l in loads)) == tokens * K  # each pair once
    # and a share is not the whole: one chip's part alone is far off
    assert float(jnp.linalg.norm(parts[0] - uncut)
                 / jnp.linalg.norm(uncut)) > 0.3


def test_padding_and_free_slots_route_nowhere(layer):
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2 * 5, D)),
                    jnp.float32)
    idx, w = moe.moe_route(x, layer["router.w"], K, 2.5)
    valid = jnp.asarray([True] * 3 + [False] * 2 + [True] * 5)
    out, load = moe.moe_experts(x, idx, w, *_share(layer, 0, E), valid=valid)
    assert int(load.sum()) == 8 * K
    assert float(jnp.abs(out[3:5]).max()) == 0.0


@pytest.mark.parametrize("n,blk,most,dead", [
    (64, 32, 4, 0), (64, 32, 4, 9), (8, 16, 8, 3), (40, 8, 1, 2)])
def test_add_by_token_sums_a_tokens_rows_without_a_scatter(n, blk, most,
                                                           dead):
    """``_add_by_token`` against a scatter-add: a token's rows (up to
    ``most``, side by side or apart) summed, rows of no token (index N,
    with whatever a grouped product left there) not read."""
    r = np.random.default_rng(n + blk)
    live = blk - dead
    if most == 1:
        rows = r.choice(n, live, replace=False)
    else:
        # every token at most ``most`` times, token 0 exactly that often
        pool = np.repeat(np.arange(1, n), most)
        rows = r.permutation(np.concatenate(
            [np.zeros(most, np.int64), r.permutation(pool)[:live - most]]))
    y = r.normal(size=(blk, 5)).astype(np.float32)
    out = r.normal(size=(n, 5)).astype(np.float32)
    want = out.copy()
    np.add.at(want, rows, y[:live])
    y[live:] = np.nan
    rows = np.concatenate([rows, np.full(dead, n)]).astype(np.int32)
    got = moe._add_by_token(jnp.asarray(out), jnp.asarray(rows),
                            jnp.asarray(y), most)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# -- window attention and the ring --------------------------------------------

def _naive_window(q, k, v, window):
    """One sequence: q (T, H, Dh), k/v (T, Hkv, Dh), a loop over
    queries."""
    t, h, dh = q.shape
    g = h // k.shape[1]
    out = np.zeros((t, h, dh), np.float32)
    for i in range(t):
        lo = max(0, i - window + 1)
        for hh in range(h):
            s = (k[lo:i + 1, hh // g] @ q[i, hh]) / np.sqrt(dh)
            p = np.exp(s - s.max())
            out[i, hh] = (p / p.sum()) @ v[lo:i + 1, hh // g]
    return out


def _qkv(t, h, hkv, dh, seed=7, b=2):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(b, t, h, dh)), jnp.float32),
            jnp.asarray(r.normal(size=(b, t, hkv, dh)), jnp.float32),
            jnp.asarray(r.normal(size=(b, t, hkv, dh)), jnp.float32))


def test_attn_window_reference_is_the_banded_softmax():
    q, k, v = _qkv(21, 6, 2, 16)
    got = attn_ops.prefill_attention(q, k, v, window=8)
    for b in range(2):
        np.testing.assert_allclose(
            got[b], _naive_window(*(np.asarray(a[b]) for a in (q, k, v)), 8),
            rtol=1e-4, atol=1e-5)
    # a window the sequence never fills is plain causal attention
    np.testing.assert_allclose(
        attn_ops.prefill_attention(q, k, v, window=64),
        attn_ops.prefill_attention_reference(q, k, v, 21),
        rtol=1e-5, atol=1e-6)


def test_attn_window_kernel_skips_and_masks_like_the_reference(monkeypatch):
    """The Pallas forward kernel in interpret mode at 512 positions,
    blocks of 128, window 200: q-blocks 2 and 3 start their loop past
    block 0 (skipped whole) and mask inside the blocks they read; at
    what the kernel's bfloat16 operands give (float32 sums)."""
    q, k, v = _qkv(512, 4, 2, 128, b=1)
    monkeypatch.setattr(attn_ops, "_FLASH_BLOCK", 128)
    got = attn_ops.prefill_attention(q, k, v, window=200, interpret=True)
    want = attn_ops.prefill_attention_reference(q, k, v, 200)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    assert np.linalg.norm(got - want) < 5e-3 * np.linalg.norm(want)


@_cases("lens", [[5, 3], [8, 9], [21, 40]])
def test_ring_holds_the_window_through_prefill_and_decode(lens):
    """`ring_pack` after a prefill, then `ring_append` + `decode_attn_
    ring` a token: each step equals window attention over the whole
    sequence at that position (prompts under, at and past the window of
    8; 40 wraps it five times)."""
    w, steps = 8, 11
    t = 64
    q, k, v = _qkv(t, 6, 2, 16, seed=8)
    lengths = jnp.asarray(lens, jnp.int32)
    want = attn_ops.prefill_attention_reference(q, k, v, w)
    kr = kv_cache.ring_pack(k, lengths, w)
    vr = kv_cache.ring_pack(v, lengths, w)
    assert kr.shape == (2, w, 2, 16)
    for b, n in enumerate(lens):  # position p sits at row p mod w
        for p in range(max(0, n - w), n):
            np.testing.assert_array_equal(kr[b, p % w], k[b, p])
    cur = np.asarray(lens)
    for _ in range(steps):
        at = jnp.asarray(cur, jnp.int32)
        rows = jnp.stack([k[b, cur[b]] for b in range(2)])[:, None]
        vals = jnp.stack([v[b, cur[b]] for b in range(2)])[:, None]
        kr = kv_cache.ring_append(kr, rows, at)
        vr = kv_cache.ring_append(vr, vals, at)
        qs = jnp.stack([q[b, cur[b]] for b in range(2)])[:, None]
        got = kv_cache.decode_attn_ring(qs, kr, vr, at + 1)
        for b in range(2):
            np.testing.assert_allclose(got[b, 0], want[b, cur[b]],
                                       rtol=1e-4, atol=1e-5)
        cur = cur + 1


def test_ring_pack_of_a_bucket_shorter_than_the_window():
    rows = jnp.arange(2 * 4, dtype=jnp.float32).reshape(2, 4, 1, 1)
    ring = kv_cache.ring_pack(rows, jnp.asarray([3, 4]), 8)
    assert ring.shape == (2, 8, 1, 1)
    assert ring[:, :4, 0, 0].tolist() == [[0, 1, 2, 3], [4, 5, 6, 7]]


# -- in the IR: layers, shape inference, scopes --------------------------------

def _program(build):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            return main, build()


def _data(name, shape, dtype="float32"):
    return layers.data(name=name, shape=list(shape), dtype=dtype,
                       append_batch_size=False)


def test_layers_infer_their_shapes():
    def build():
        x = _data("x", (2, 16, 32))
        q = _data("q", (2, 16, 6, 16))
        kv = _data("kv", (2, 16, 2, 16))
        lens = _data("lens", (2,), "int32")
        idx, w = layers.moe_route(x, _data("wr", (32, 8)), 2, scale=2.5)
        out, load = layers.moe_experts(
            x, idx, w, _data("wg", (4, 32, 8)), _data("wu", (4, 32, 8)),
            _data("wd", (4, 8, 32)), expert_lo=4, lengths=lens)
        sh = layers.moe_shared(x, _data("sg", (32, 8)), _data("su", (32, 8)),
                               _data("sd", (8, 32)))
        rq = layers.rope(q, rotary_dim=8, theta=5e5, attention_factor=1.4,
                         yarn={"factor": 64, "original_max_position": 32,
                               "beta_fast": 4, "beta_slow": 1})
        ctx = layers.prefill_attention(rq, kv, kv, window=8)
        ring = layers.ring_pack(kv, lens, 8)
        q1 = _data("q1", (2, 1, 6, 16))
        new = layers.ring_append(ring, _data("row", (2, 1, 2, 16)), lens)
        dec = layers.decode_attn_ring(q1, new, new, lens)
        return idx, w, out, load, sh, rq, ctx, ring, new, dec

    main, (idx, w, out, load, sh, rq, ctx, ring, new, dec) = _program(build)
    assert tuple(idx.shape) == tuple(w.shape) == (2, 16, 2)
    assert tuple(out.shape) == tuple(sh.shape) == (2, 16, 32)
    assert tuple(load.shape) == (4,)
    assert tuple(rq.shape) == tuple(ctx.shape) == (2, 16, 6, 16)
    assert tuple(ring.shape) == tuple(new.shape) == (2, 8, 2, 16)
    assert tuple(dec.shape) == (2, 1, 6, 16)
    from paddle_tpu.analysis import infer_program

    # every new op has a rule, and the rules agree with the layers
    result = infer_program(main)
    assert result.report.errors == [], result.report.errors
    assert result.info(load.name).shape == (4,)
    assert result.info(dec.name).shape == (2, 1, 6, 16)


@_cases("build,match", [
    (lambda: layers.rope(_data("q", (2, 4, 2, 16)), rotary_dim=7),
     "not an even part"),
    (lambda: layers.moe_route(_data("x", (2, 4, 32)), _data("w", (32, 4)),
                              8), "passes the router's 4 experts"),
    (lambda: layers.moe_experts(
        _data("x", (2, 4, 32)), _data("i", (2, 4, 2), "int32"),
        _data("w", (2, 4, 2)), _data("g", (4, 16, 8)), _data("u", (4, 16, 8)),
        _data("d", (4, 8, 16))), "does not take X"),
    (lambda: layers.prefill_attention(_data("q", (2, 4, 6, 16)),
                                      _data("k", (2, 4, 4, 16)),
                                      _data("v", (2, 4, 4, 16)), window=8),
     "does not divide"),
    (lambda: layers.decode_attn_ring(
        _data("q", (2, 1, 4, 16)), _data("k", (2, 8, 2, 8)),
        _data("v", (2, 8, 2, 8)), _data("l", (2,), "int32")), "depth dim"),
])
def test_infer_rules_name_the_mismatch(build, match):
    from paddle_tpu.analysis import infer_program

    main, _ = _program(build)
    errors = infer_program(main).report.errors
    assert errors and any(match in e.message for e in errors), errors


def test_ops_carry_their_scopes():
    """The names a lowered program shows for each new op."""
    def f(x, q, kv, lens, wr, wg, wd, sg, sd):
        idx, w = moe.moe_route(x, wr, 2)
        y, _ = moe.moe_experts(x, idx, w, wg, wg, wd)
        y = y + moe.moe_shared(x, sg, sg, sd)
        r = rope.rope(q, None, rope.rope_inv_freq(16, 1e4))
        c = attn_ops.prefill_attention(r, kv, kv, window=4)
        ring = kv_cache.ring_append(kv_cache.ring_pack(kv, lens, 4),
                                    kv[:, :1], lens)
        return y, c, kv_cache.decode_attn_ring(q[:, :1], ring, ring, lens)

    z = jnp.zeros
    text = jax.jit(f).lower(
        z((8, 32)), z((2, 8, 4, 16)), z((2, 8, 2, 16)),
        z((2,), jnp.int32), z((32, 8)), z((8, 32, 8)), z((8, 8, 32)),
        z((32, 8)), z((8, 32))).as_text(debug_info=True)
    for scope in ("ptpu.rope", "ptpu.moe_route", "ptpu.moe_experts",
                  "ptpu.moe_shared", "ptpu.attn_window", "ptpu.ring_pack",
                  "ptpu.ring_append", "ptpu.decode_attn_ring"):
        assert scope in text, scope
