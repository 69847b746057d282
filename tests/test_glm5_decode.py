"""A GLM-5-family LM (latent attention under a learned indexer in EVERY
layer, the indexer's rotation interleaved, value heads as wide as the
query/key heads; leading dense MLP, then routed experts with a shared
one, one chip's share held; ONE multi-token-prediction layer that
drafts) through the normal serving path (`save_decode_model` ->
`DecodePredictor` -> `DecodeServer`) at a tiny size: prefill then plain
steps LOGITS against the plain reference's full forward pass
(`benchmark/reference/glm5.py`, which imports nothing of the program);
a ROUND of two positions against two plain steps (logits, every cache
entry, the next draft); the prediction layer's logits against the
reference's `draft_logits`; a server whose draft is MADE to agree, and
one whose draft never does, against the plain greedy sequence; the
references with one part changed; the shares of an expert-parallel
deployment adding up; matrices held in bfloat16; the window forms of the
ops against their one-row forms; what `_rows_only` still refuses; the
manifest's new fields."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.ops import dsa, mla, speculative  # noqa: E402
from paddle_tpu.serving import decode  # noqa: E402
from paddle_tpu.serving.decode import (  # noqa: E402
    DecodeConfig, DecodePredictor, DecodeServer, cache_spec,
    save_decode_model)

from benchmark.lib import run_serveany, weights  # noqa: E402
from benchmark.models import (evabyte_lm, glm5_lm, jamba_lm,  # noqa: E402
                              laguna_lm, ling3_lm, mimo_v2_lm)
from benchmark.reference import glm5 as ref  # noqa: E402

_TINY = os.path.join(_ROOT, "benchmark", "tests", "tiny")
# hidden 64; 4 heads of 16 + 8 query/key and 24 value channels over a
# latent of 16 + 8 = 24 floats a position, an indexer of 4 heads of 16
# that picks 16 rows, its first 8 channels rotated on pairs; layers
# dense, sparse, sparse (32 routed experts, 2 a token, experts 0..7
# held, a shared one) and the prediction layer, sparse, as layer 3
with open(os.path.join(_TINY, "glm5-tiny.json")) as _f:
    CFG = json.load(_f)
SLOTS, SEQ, N_LAYER = 4, 128, 3
ENTRIES = 2 * (N_LAYER + 1)
V = CFG["vocab_size"]


def _seeded(cfg, seed=2 ** 31 + 17):
    specs = glm5_lm.parameter_specs(cfg, "serve")
    return weights.seeded_weights(specs, seed, glm5_lm.init_rule)


def _pred(d, cfg, w):
    scope = fluid.Scope()
    for n in w:
        scope.set_var(n, w[n])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        save_decode_model(d, glm5_lm.decode_config(cfg, "serve"), exe,
                          scope=scope)
    return DecodePredictor(d)


@pytest.fixture(scope="module")
def seeded():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def pred(tmp_path_factory, seeded):
    return _pred(str(tmp_path_factory.mktemp("glm5_model")), CFG, seeded)


def _prompts(lens, seed=3):
    r = np.random.default_rng(seed)
    return [r.integers(1, V, n, dtype=np.int64) for n in lens]


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


K = 6
# both pass the top-k of 16; 40 lies in the bucket of 64, 70 in 128
PROBE_LENS = [40, 70]


def _admit(pred, prompts):
    """Prefill `prompts` into fresh entries by hand, as the runner's
    rollout does: (caches, lens, first tokens, first drafts, logits)."""
    spec = pred.cache_spec(SLOTS, SEQ)
    caches = [jnp.zeros(e.shape, e.dtype) for e in spec]
    lens = np.zeros((SLOTS,), np.int32)
    first = np.zeros((SLOTS,), np.int64)
    draft = np.zeros((SLOTS,), np.int64)
    logits = []
    for i, p in enumerate(prompts):
        sp = min(run_serveany._bucket(len(p)), SEQ)
        pexe, _ = pred.acquire("prefill", 1, sp)
        tokens = np.zeros((1, sp), np.int64)
        tokens[0, :len(p)] = p
        outs = pexe({"tokens": tokens,
                     "lengths": np.array([len(p)], np.int32)}, pred._state)
        for j, (e, sub) in enumerate(zip(spec, outs[1:])):
            caches[j] = caches[j].at[i, :sp].set(jnp.asarray(sub)[0])
        lens[i] = len(p)
        logits.append(np.asarray(outs[0])[0])
        first[i] = int(logits[-1].argmax())
        draft[i] = int(np.asarray(outs[1 + len(spec)])[0])
    return caches, lens, first, draft, logits


def _plain(pred, caches, lens, cur, steps):
    """`steps` plain one-token steps: (caches, [ids], [logits],
    [drafts])."""
    spec = pred.cache_spec(SLOTS, SEQ)
    names = [e.name for e in spec]
    dexe, _ = pred.acquire("decode", SLOTS, SEQ, "greedy")
    lens, cur = lens.copy(), cur.copy()
    ids, logits, drafts = [], [], []
    for s in range(steps):
        feeds = {"tokens": cur.reshape(SLOTS, 1), "lengths": lens.copy(),
                 "seed": np.array([s], np.int64)}
        feeds.update(zip(names, caches))
        outs = dexe(feeds, pred._state)
        cur = np.asarray(outs[0]).astype(np.int64)
        ids.append(cur.copy())
        logits.append(np.asarray(outs[1]))
        caches = list(outs[2:2 + len(spec)])
        drafts.append(np.asarray(outs[2 + len(spec)]).astype(np.int64))
        lens += (lens > 0)
    return caches, ids, logits, drafts


def _round(pred, caches, lens, cur, draft):
    """One round: (caches, ids (B, 4), logits (B, 2, V), draft logits)."""
    spec = pred.cache_spec(SLOTS, SEQ)
    rexe, _ = pred.acquire("round", SLOTS, SEQ)
    feeds = {"tokens": np.stack([cur, draft], axis=1),
             "lengths": lens.copy()}
    feeds.update(zip([e.name for e in spec], caches))
    outs = rexe(feeds, pred._state)
    return (list(outs[3:3 + len(spec)]), np.asarray(outs[0]),
            np.asarray(outs[1]), np.asarray(outs[2]))


@pytest.fixture(scope="module")
def admitted(pred):
    prompts = _prompts(PROBE_LENS)
    return (prompts,) + _admit(pred, prompts)


@pytest.fixture(scope="module")
def two_steps(pred, admitted):
    _, caches, lens, first, _, _ = admitted
    return _plain(pred, caches, lens, first, 2)


def _reference(w, text, rows, precision="highest", cfg=CFG):
    padded = np.zeros((SEQ,), np.int64)
    padded[:len(text)] = text
    return np.asarray(ref.serve_logits(w, jnp.asarray(padded), cfg, N_LAYER,
                                       precision=precision, rows=rows))


def _draft_reference(w, text, nxt, rows, precision="highest"):
    a, b = np.zeros((SEQ,), np.int64), np.zeros((SEQ,), np.int64)
    a[:len(text)] = text
    b[:len(nxt)] = nxt
    return np.asarray(ref.draft_logits(
        w, jnp.asarray(a), jnp.asarray(b), CFG, N_LAYER,
        precision=precision, rows=rows))


# -- the manifest and the cache entries --------------------------------------

def test_manifest_carries_the_prediction_layer_and_the_matrix_type():
    dc = glm5_lm.decode_config(CFG, "serve")
    assert dc.n_predict_layers == 1 and dc.matrix_dtype == "float32"
    assert dc.layer_kinds() == ["latent_dsa"] * 3
    assert dc.ffn_kinds() == ["dense", "experts", "experts"]
    assert dc.sparse_layers() == [1, 2, 3]
    assert dc.cache_layers()[-1] == (3, "latent_dsa")
    assert dc.extra_fetches == ["draft", "moe_load"]
    assert dc.rope["index"] == {"theta": 1e6, "rotary_dim": 8,
                                "interleave": True}
    d = dc.to_dict()
    assert d["n_predict_layers"] == 1 and "matrix_dtype" not in d
    again = DecodeConfig.from_dict(d)
    assert again.to_dict() == d and again.n_predict_layers == 1
    bf = dict(CFG, precision={"matrices": "bfloat16"})
    assert glm5_lm.decode_config(bf, "serve").to_dict()[
        "matrix_dtype"] == "bfloat16"
    spec = cache_spec(dc, SLOTS, SEQ)
    assert [e.name for e in spec] == (
        ["index_%d" % i for i in range(4)]
        + ["latent_%d" % i for i in range(4)])
    assert {e.kind for e in spec} == {"index", "latent"}
    assert all(e.per_position and e.dtype == "float32" for e in spec)


@pytest.mark.parametrize("field,value,piece", [
    ("n_predict_layers", 2, "one prediction layer"),
    ("matrix_dtype", "float16", "float32 or bfloat16")])
def test_manifest_refuses_what_no_graph_builds(field, value, piece):
    d = glm5_lm.decode_config(CFG, "serve").to_dict()
    with pytest.raises(ValueError, match=piece):
        DecodeConfig.from_dict(dict(d, **{field: value}))


@pytest.mark.parametrize("key,value", [
    ("mtp_concat", "hidden_first"), ("mtp_hidden", "before_final_norm"),
    ("mtp_shares", "nothing"), ("mtp_layer", "dense"),
    ("index_rope_channels", "last"), ("draft_tokens", 2)])
def test_each_assumed_field_is_one_named_choice(key, value):
    cfg = dict(CFG, assumed=dict(CFG["assumed"], **{key: value}))
    with pytest.raises(ValueError, match="assumed.%s" % key):
        glm5_lm.decode_config(cfg, "serve")
    with pytest.raises(ValueError, match="assumed.%s" % key):
        ref.check_assumed(cfg)


def test_a_prediction_layer_needs_rows_a_position_under_it():
    from paddle_tpu.models import jamba

    cfg = jamba_lm.decode_config(_tiny("jamba-tiny.json"), "serve")
    d = dict(cfg.to_dict(), n_predict_layers=1)
    with pytest.raises(ValueError, match="prediction layer"):
        jamba._check(DecodeConfig.from_dict(d))
    d = dict(cfg.to_dict(), matrix_dtype="bfloat16")
    with pytest.raises(ValueError, match="matrix_dtype"):
        jamba._check(DecodeConfig.from_dict(d))
    # a matrix held in bfloat16 takes no precision: refused, not dropped
    bf = glm5_lm.decode_config(
        dict(CFG, precision={"matrices": "bfloat16"}), "serve").to_dict()
    with pytest.raises(ValueError, match="head_precision"):
        jamba._check(DecodeConfig.from_dict(
            dict(bf, head_precision="highest")))
    from paddle_tpu.ops.math import wmm

    x, w = jnp.ones((2, 8), jnp.float32), jnp.ones((8, 4), jnp.bfloat16)
    assert wmm(x, w).dtype == jnp.float32
    with pytest.raises(ValueError, match="held in bfloat16"):
        wmm(x, w, precision="highest")


def _tiny(name):
    with open(os.path.join(_TINY, name)) as f:
        return json.load(f)


@pytest.mark.parametrize("builder,tiny,piece", [
    (jamba_lm, "jamba-tiny.json", "recurrent state"),
    (ling3_lm, "ling3-tiny.json", "recurrent state"),
    (laguna_lm, "laguna-tiny.json", "ring of"),
    (mimo_v2_lm, "mimo-v2-tiny.json", "ring of"),
    (evabyte_lm, "evabyte-tiny.json", "entries of kind 'eva'")])
def test_a_window_is_still_refused_over_a_state_a_ring_and_eva(
        builder, tiny, piece):
    p = DecodePredictor.__new__(DecodePredictor)
    p.config = builder.decode_config(_tiny(tiny), "serve")
    with pytest.raises(ValueError, match=piece):
        p._rows_only("a round of two positions", latent_rows=True)
    with pytest.raises(ValueError, match=piece):
        p._rows_only("speculative decoding")


def test_latent_rows_roll_back_by_length_only_behind_a_prediction_layer(
        pred):
    pred._rows_only("a round of two positions", latent_rows=True)
    with pytest.raises(ValueError, match="neither K nor V"):
        pred._rows_only("speculative decoding")
    with pytest.raises(ValueError, match="neither K nor V"):
        DecodeServer(pred, slots=SLOTS, max_seq=SEQ, speculative=True)
    bare = DecodePredictor.__new__(DecodePredictor)
    bare.config = DecodeConfig.from_dict(dict(
        pred.config.to_dict(), n_predict_layers=0))
    with pytest.raises(ValueError, match="needs a prediction layer"):
        bare._rows_only("a round of two positions", latent_rows=True)


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("i", range(len(PROBE_LENS)))
def test_prefill_then_steps_match_the_reference(pred, seeded, i):
    prompts = _prompts(PROBE_LENS)
    forced = _prompts([K + 1] * len(prompts), seed=4)
    rows, _ = run_serveany._direct_rollout(pred, prompts, K, SLOTS, SEQ,
                                           forced=forced)
    p, f = prompts[i], forced[i]
    text = np.concatenate([p, f[:K]])
    want = _reference(seeded, text, np.arange(len(p) - 1, len(p) + K))
    assert _rel(np.stack(rows[i]), want) < 2e-5


@pytest.mark.parametrize("i", range(len(PROBE_LENS)))
def test_first_draft_is_the_references(seeded, admitted, i):
    prompts, _, _, first, draft, logits = admitted
    p = prompts[i]
    want = _reference(seeded, p, np.array([len(p) - 1]))
    assert _rel(logits[i], want[0]) < 2e-5
    nxt = np.concatenate([p[1:], [first[i]]])
    wd = _draft_reference(seeded, p, nxt, np.array([len(p) - 1]))
    assert int(wd[0].argmax()) == int(draft[i])


def test_a_round_is_two_plain_steps(pred, admitted, two_steps):
    """The model's own next token as the draft: both positions' logits,
    every entry's rows up to the new length and the next draft are those
    of two plain steps."""
    _, caches, lens, first, _, _ = admitted
    want_caches, ids, logits, drafts = two_steps
    got_caches, rids, rlogits, _ = _round(pred, caches, lens, first, ids[0])
    n = len(PROBE_LENS)
    assert (rids[:n, 0] == ids[0][:n]).all()
    assert (rids[:n, 1] == ids[1][:n]).all()
    assert (rids[:n, 2] == 1).all()                    # accepted
    assert (rids[:n, 3] == drafts[1][:n]).all()        # read at position 1
    np.testing.assert_allclose(rlogits[:n, 0], logits[0][:n], atol=1e-6)
    np.testing.assert_allclose(rlogits[:n, 1], logits[1][:n], atol=1e-6)
    assert len(got_caches) == ENTRIES
    for got, want in zip(got_caches, want_caches):
        for i in range(n):
            live = int(lens[i]) + 2
            np.testing.assert_allclose(np.asarray(got)[i, :live],
                                       np.asarray(want)[i, :live],
                                       atol=1e-6)


def test_the_greedy_step_is_the_round_itself(pred, admitted, two_steps):
    """ONE step program: the greedy plain step is the round executable
    with the current token standing in for the draft (bit for bit
    position 0 of a round, on any device): ids, logits, every entry's
    live row and the draft it hands on."""
    from paddle_tpu.serving.decode import _StepOfRound

    dexe, names = pred.acquire("decode", SLOTS, SEQ, "greedy")
    rexe, _ = pred.acquire("round", SLOTS, SEQ)
    assert isinstance(dexe, _StepOfRound) and dexe._round is rexe
    assert names[:2] == ["next_ids", "logits"] and names[-2:] == [
        "draft", "moe_load"]
    _, caches, lens, first, _, _ = admitted
    want_caches, ids, logits, drafts = _plain(pred, caches, lens, first, 1)
    got_caches, rids, rlogits, dlogits = _round(pred, caches, lens, first,
                                                first)
    n = len(PROBE_LENS)
    assert (rids[:n, 0] == ids[0][:n]).all()
    assert (dlogits[:n, 0].argmax(-1) == drafts[0][:n]).all()
    assert np.array_equal(rlogits[:n, 0], logits[0][:n])
    for got, want in zip(got_caches, want_caches):
        for i in range(n):
            assert np.array_equal(np.asarray(got)[i, :lens[i] + 1],
                                  np.asarray(want)[i, :lens[i] + 1])


def test_a_rejected_draft_leaves_one_token_and_a_draft_for_it(
        pred, admitted, two_steps):
    """A draft the model did not choose: position 0 is the plain step,
    nothing of position 1 is committed, and the next draft is the
    prediction layer's at position 0: a plain step's."""
    _, caches, lens, first, _, _ = admitted
    _, ids, logits, drafts = two_steps
    wrong = (ids[0] + 1) % V
    got_caches, rids, rlogits, _ = _round(pred, caches, lens, first, wrong)
    n = len(PROBE_LENS)
    assert (rids[:n, 0] == ids[0][:n]).all() and (rids[:n, 2] == 0).all()
    assert (rids[:n, 3] == drafts[0][:n]).all()
    np.testing.assert_allclose(rlogits[:n, 0], logits[0][:n], atol=1e-6)
    # the next round overwrites the hypothesis row: a round at the new
    # length gives the plain second step
    lens2 = lens + (lens > 0)
    _, rids2, rlogits2, _ = _round(pred, got_caches, lens2, ids[0],
                                   drafts[0])
    assert (rids2[:n, 0] == ids[1][:n]).all()
    np.testing.assert_allclose(rlogits2[:n, 0], logits[1][:n], atol=1e-6)


@pytest.mark.parametrize("i", range(len(PROBE_LENS)))
def test_a_rounds_draft_logits_match_the_reference(pred, seeded, admitted,
                                                   two_steps, i):
    prompts, caches, lens, first, _, _ = admitted
    _, ids, _, _ = two_steps
    _, rids, _, dlogits = _round(pred, caches, lens, first, ids[0])
    p = prompts[i]
    text = np.concatenate([p, [first[i], ids[0][i]]])
    nxt = np.concatenate([text[1:], [rids[i, 1]]])
    want = _draft_reference(seeded, text, nxt,
                            np.arange(len(p), len(p) + 2))
    assert _rel(dlogits[i], want) < 2e-5


_MAIN = ("all_rows", "topk_half", "index_keys_not_rotated",
         "index_half_split", "v_half", "kr_not_rotated", "no_shared")
_DRAFT = ("mtp_concat_reversed", "mtp_hidden_before_norm")


@pytest.mark.parametrize("variant", _MAIN + _DRAFT)
def test_a_reference_that_changes_a_part_is_told_apart(
        pred, seeded, admitted, two_steps, variant):
    """What separates the program from the reference (2e-5) against a
    reference with one part changed."""
    prompts, caches, lens, first, _, _ = admitted
    _, ids, logits, _ = two_steps
    p = prompts[1]
    text = np.concatenate([p, [first[1], ids[0][1]]])
    rows = np.arange(len(p), len(p) + 2)
    padded = np.zeros((SEQ,), np.int64)
    padded[:len(text)] = text
    if variant in _MAIN:
        got = np.stack([logits[0][1], logits[1][1]])
        want = np.asarray(ref.serve_logits(
            seeded, jnp.asarray(padded), CFG, N_LAYER,
            precision="highest+" + variant, rows=rows))
    else:
        _, rids, _, dlogits = _round(pred, caches, lens, first, ids[0])
        got = dlogits[1]
        nxt = np.zeros((SEQ,), np.int64)
        nxt[:len(text)] = np.concatenate([text[1:], [rids[1, 1]]])
        want = np.asarray(ref.draft_logits(
            seeded, jnp.asarray(padded), jnp.asarray(nxt), CFG, N_LAYER,
            precision="highest+" + variant, rows=rows))
    assert _rel(got, want) > 5e-4, variant


def test_the_shares_of_a_layer_add_up(seeded):
    """The guide's share test: the routed parts that the four shares of
    the tiny layer's 32 experts give, with the shared expert counted
    once, add up to the uncut layer."""
    r = np.random.default_rng(5)
    scored, d, f = 32, CFG["hidden_size"], CFG["moe_intermediate_size"]
    p = {n[len("lm.l1.moe."):]: jnp.asarray(v, jnp.float32)
         for n, v in seeded.items() if n.startswith("lm.l1.moe.")}
    full = {k: jnp.asarray(r.normal(0, 0.05, (scored,) + s), jnp.float32)
            for k, s in (("experts.gate.w", (d, f)), ("experts.up.w", (d, f)),
                         ("experts.down.w", (f, d)))}
    x = jnp.asarray(r.normal(0, 1, (12, d)), jnp.float32)
    whole = ref.moe(dict(p, **full), x, CFG, "highest", held=(0, scored))
    parts = ref.moe(p, x, CFG, "highest", held=(0, 0), shared=True)
    for lo in range(0, scored, 8):
        share = dict(p, **{k: v[lo:lo + 8] for k, v in full.items()})
        parts = parts + ref.moe(share, x, CFG, "highest",
                                held=(lo, lo + 8), shared=False)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)


# -- the server ---------------------------------------------------------------

class _ChainSpy:
    """Stands where the server's compiled select stands
    (`decode._round_chain_fn`): keeps every round's feeds as the select
    built them (`fresh`, `tokens`, `lengths`, `remaining`), and where
    `made(first, n)` is given writes its answer over each live slot's
    draft, chained slots too: `first` is the sequence's first token,
    `n` how many tokens it has once the round before this one is
    counted, so the model's choice at the round's position 0 is the
    sequence's token `n`."""

    def __init__(self, made=None):
        self.made, self.feeds, self.who = made, [], {}
        self._real = decode._round_chain_fn
        self._fed = ()

    def __call__(self, slots, seq, eos, device):
        inner = self._real(slots, seq, eos, device)

        def chain(fresh, tokens, lengths, remaining, *prev):
            # on the chip the round DONATES its feeds: what the select
            # built a round ago is gone by now
            for fed in self._fed:
                fed.delete()
            out, lens, state = inner(fresh, tokens, lengths, remaining,
                                     *prev)
            toks, at = np.array(out), np.asarray(lens)
            for i in range(slots):
                if fresh[i] and lengths[i] > 0:  # admitted since
                    self.who[i] = int(tokens[i, 0]), int(lengths[i])
                if at[i] > 0 and self.made is not None:
                    first, prompt = self.who[i]
                    toks[i, 1] = self.made(first, int(at[i]) - prompt + 1)
            self.feeds.append({"fresh": np.array(fresh), "tokens": toks,
                               "lengths": at.copy(),
                               "remaining": np.array(state)[:, 1]})
            self._fed = jnp.asarray(toks, out.dtype), lens
            return self._fed + (state,)

        return chain


def _serve(pred, prompts, max_new, made=None, spy=None):
    """The server's answers (`max_new`: one for all, or one a prompt);
    `made(first, n)` sets every live slot's draft before each round where
    given (`_ChainSpy`)."""
    if np.isscalar(max_new):
        max_new = [max_new] * len(prompts)
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ,
                       max_new_tokens=max(max_new), strategy="greedy")
    spy = spy or _ChainSpy(made)
    decode._round_chain_fn = spy
    try:
        srv.start()
        futs = [srv.submit((p, np.array([m], np.int64)))
                for p, m in zip(prompts, max_new)]
        outs = [np.asarray(f.result(timeout=600)[0]).reshape(-1)
                for f in futs]
        srv.stop()
    finally:
        decode._round_chain_fn = spy._real
    # no round reads or writes a row at or past the slab's end
    assert all((f["lengths"] + 1 < SEQ).all() for f in spy.feeds)
    return srv, outs


@pytest.fixture(scope="module")
def greedy(pred):
    """The plain greedy sequences of five prompts, by the predictor's
    executables by hand (a prefill, then ten plain steps), SLOTS at a
    time."""
    prompts = _prompts([40, 70, 33, 90, 21], seed=8)
    outs = []
    for at in range(0, len(prompts), SLOTS):
        group = prompts[at:at + SLOTS]
        caches, lens, first, _, _ = _admit(pred, group)
        _, ids, _, _ = _plain(pred, caches, lens, first, 10)
        outs += [np.array([first[i]] + [step[i] for step in ids])
                 for i in range(len(group))]
    return prompts, outs


def test_a_server_over_a_prediction_layer_runs_rounds_unasked(pred, greedy):
    prompts, want = greedy
    before = obs.DECODE_SPEC_PROPOSED.value()
    srv, got = _serve(pred, prompts, 11)
    assert srv.rounds
    for g, w in zip(got, want):
        assert (g == w).all()
    assert obs.DECODE_SPEC_PROPOSED.value() > before
    # tokens delivered, as a plain step's count
    assert sum(srv.step_active_counts) == sum(len(w) - 1 for w in want)


@pytest.mark.parametrize("agree", [True, False])
def test_a_draft_made_to_agree_commits_two_tokens_a_round(pred, greedy,
                                                          agree):
    """The test feeds the model's own next token as the draft (and one
    that never agrees): two tokens a round (one), and the plain greedy
    sequence token for token either way."""
    prompts, want = greedy
    by_first = {}
    for w in want:
        by_first.setdefault(int(w[0]), w)
    assert len(by_first) == len(want)

    def made(first, n):
        seq = by_first[first]
        nxt = int(seq[n]) if n < len(seq) else 0
        return nxt if agree else (nxt + 1) % V

    acc0 = obs.DECODE_SPEC_ACCEPTED.value()
    pro0 = obs.DECODE_SPEC_PROPOSED.value()
    srv, got = _serve(pred, prompts, 11, made=made)
    for g, w in zip(got, want):
        assert (g == w).all()
    accepted = obs.DECODE_SPEC_ACCEPTED.value() - acc0
    proposed = obs.DECODE_SPEC_PROPOSED.value() - pro0
    if agree:
        # 10 tokens after the first in 5 rounds of two
        assert accepted == proposed == 5 * len(prompts)
        assert max(srv.step_active_counts) > SLOTS
    else:
        assert accepted == 0 and proposed == 10 * len(prompts)
        assert max(srv.step_active_counts) <= SLOTS


# -- a round in flight behind another ----------------------------------------

# the select against the host's bookkeeping: a slot a case, one compile
_EOS = 7
_CASES = [(accept, left, room, fresh, eos_at)
          for accept in (0, 1) for left in (1, 2, 3)
          for room in (0, 1, 40)       # SEQ - (length + 2)
          for fresh in (False, True) for eos_at in (None, 0, 1)]


def _case_id(case):
    accept, left, room, fresh, eos_at = case
    return "accept%d-left%d-room%d-%s-eos%s" % (
        accept, left, room, "fresh" if fresh else "chained", eos_at)


@pytest.fixture(scope="module")
def chained_cases():
    """Every case as one slot of ONE call of the select: what the round
    before was fed and answered, the host's values beside them, and
    what the select made of them."""
    n = len(_CASES)
    ids = np.zeros((n, 4), np.int64)
    fresh = np.zeros((n,), np.bool_)
    prev_len, prev_left = np.zeros((n,), np.int32), np.zeros((n,), np.int32)
    host = {"tokens": np.zeros((n, 2), np.int64),
            "lengths": np.zeros((n,), np.int32),
            "remaining": np.zeros((n,), np.int32)}
    for i, (accept, left, room, is_fresh, eos_at) in enumerate(_CASES):
        ids[i] = 100 + i, 300 + i, accept, 500 + i
        if eos_at is not None:
            ids[i, eos_at] = _EOS
        fresh[i] = is_fresh
        prev_len[i], prev_left[i] = SEQ - 2 - room, left
        host["tokens"][i] = 700 + i, 900 + i
        host["lengths"][i], host["remaining"][i] = 20 + i % 5, 1 + i % 4
    chain = decode._round_chain_fn(n, SEQ, _EOS, jax.devices()[0])
    tokens, lengths, state = chain(
        fresh, host["tokens"], host["lengths"], host["remaining"], ids,
        np.stack([prev_len, prev_left], axis=1))
    assert (np.asarray(state)[:, 0] == np.asarray(lengths)).all()
    return ids, prev_len, prev_left, host, [
        np.asarray(tokens), np.asarray(lengths), np.asarray(state)[:, 1]]


@pytest.mark.parametrize("case", range(len(_CASES)),
                         ids=[_case_id(c) for c in _CASES])
def test_the_select_advances_a_slot_as_the_host_would(chained_cases, case):
    """`_accepted_tokens` and the round's commit as the oracle: what a
    chained slot is fed next is its last taken token, the next draft,
    and its length and budget advanced by the tokens taken; a slot that
    ended (budget, the slab's end, eos) is fed as a free one; a fresh
    slot is the host's."""
    ids, prev_len, prev_left, host, (tokens, lengths, remaining) = \
        chained_cases
    accept, left, room, fresh, eos_at = _CASES[case]
    if fresh:
        assert tokens[case].tolist() == host["tokens"][case].tolist()
        assert lengths[case] == host["lengths"][case]
        assert remaining[case] == host["remaining"][case]
        return
    at = int(prev_len[case])
    taken, stopped = decode._accepted_tokens(
        ids[case], accept, min(left, SEQ - at), _EOS)
    assert 1 <= len(taken) <= 2
    at, left = at + len(taken), left - len(taken)
    if stopped or left <= 0 or at + 1 >= SEQ:
        want = [0, 0], 0, 0
    else:
        want = [taken[-1], int(ids[case, 3])], at, left
    assert (tokens[case].tolist(), lengths[case], remaining[case]) == want
    assert lengths[case] + 1 < SEQ


def test_an_ended_slot_stays_a_free_slot(chained_cases):
    """A slot the select ended is fed on as a free one, whatever the
    rounds behind it answer, until the host hands it a fresh sequence."""
    ids, _, _, host, (tokens, lengths, remaining) = chained_cases
    n = len(_CASES)
    chain = decode._round_chain_fn(n, SEQ, _EOS, jax.devices()[0])
    ended = remaining == 0
    assert ended.any() and not ended.all()
    again = [np.asarray(g) for g in chain(
        np.zeros((n,), np.bool_), host["tokens"], host["lengths"],
        host["remaining"], ids, np.stack([lengths, remaining], axis=1))]
    assert not again[0][ended].any() and not again[1][ended].any()
    assert not again[2][ended].any()


_MIXED = [  # (prompt's length, max_new): odd and even budgets, one token
    # after the first, a reply that ends at the slab's last row, and two
    # requests more than the slots, admitted behind a round in flight
    (40, 7), (70, 10), (33, 3), (SEQ - 8, 8), (21, 2), (55, 5)]


@pytest.fixture(scope="module")
def mixed(pred):
    prompts = _prompts([n for n, _ in _MIXED], seed=12)
    outs = []
    for at in range(0, len(prompts), SLOTS):
        group = prompts[at:at + SLOTS]
        caches, lens, first, _, _ = _admit(pred, group)
        # seven steps: the one at the slab's end reads none past it
        _, ids, _, _ = _plain(pred, caches, lens, first, 7)
        for i in range(len(group)):
            m = _MIXED[at + i][1]
            outs.append(np.array([first[i]] + [step[i] for step in ids])[:m])
    # ten tokens of the second: three plain steps more of it alone
    caches, lens, first, _, _ = _admit(pred, prompts[1:2])
    _, ids, _, _ = _plain(pred, caches, lens, first, 9)
    outs[1] = np.array([first[0]] + [step[0] for step in ids])
    return prompts, outs


@pytest.mark.parametrize("draft", ["its_own", "agrees", "never"])
def test_rounds_in_flight_give_the_plain_greedy_sequences(pred, mixed,
                                                          draft):
    """Budgets odd and even, a reply that ends at the slab's end, more
    requests than slots: token for token the plain greedy sequences, cut
    where the plain loop cuts them, whether a round commits one token
    or two; and every round but a busy period's first went out behind
    an unread one."""
    prompts, want = mixed
    by_first = {int(w[0]): w for w in want}
    assert len(by_first) == len(want)

    def made(first, n):
        seq = by_first[first]
        nxt = int(seq[n]) if n < len(seq) else 0
        return nxt if draft == "agrees" else (nxt + 1) % V

    spy = _ChainSpy(None if draft == "its_own" else made)
    steps0 = {k: obs.DECODE_STEPS.value(in_flight=k) for k in "01"}
    srv, got = _serve(pred, prompts, [m for _, m in _MIXED], spy=spy)
    assert [g.tolist() for g in got] == [w.tolist() for w in want]
    assert sum(srv.step_active_counts) == sum(len(w) - 1 for w in want)
    # a sequence admitted while a round was in flight: fresh beside
    # slots that are chained to it
    assert any((f["fresh"] & (f["lengths"] > 0)).any()
               and not f["fresh"].all() for f in spy.feeds)
    # what the device was fed never passes what the sequence may take
    assert all((f["remaining"] >= 0).all() and (
        f["remaining"][f["lengths"] == 0] == 0).all() for f in spy.feeds)
    steps = {k: obs.DECODE_STEPS.value(in_flight=k) - steps0[k]
             for k in "01"}
    assert steps["0"] + steps["1"] == len(spy.feeds)
    assert steps["1"] >= len(spy.feeds) - 3, steps
    if draft == "agrees":
        assert max(srv.step_active_counts) > SLOTS


def test_an_eos_ends_a_sequence_a_round_late(pred, mixed):
    """The host learns of an `eos_id` when it reads the round: the
    sequence is cut after it as the plain loop cuts it, and the round
    behind ran its slot as a free one."""
    prompts, want = mixed
    eos = int(want[1][4])
    cut = [w[:list(w).index(eos) + 1] if eos in w else w for w in want]
    assert len(cut[1]) < len(want[1])
    served = DecodePredictor.__new__(DecodePredictor)
    served.__dict__.update(pred.__dict__)
    served.eos_id = eos
    spy = _ChainSpy()
    srv, got = _serve(served, prompts, [m for _, m in _MIXED], spy=spy)
    assert [g.tolist() for g in got] == [c.tolist() for c in cut]
    assert sum(srv.step_active_counts) == sum(len(c) - 1 for c in cut)
    # the second prompt's lengths are its own (70..79): none was fed
    # once the round that chose the eos had run
    fed = [int(n) for f in spy.feeds for n in f["lengths"] if 70 <= n < 80]
    assert sorted(fed) == list(range(70, 70 + len(cut[1]) - 1))


@pytest.mark.parametrize("where", ["dispatch", "fetch"])
def test_a_round_that_fails_takes_the_round_in_flight_with_it(pred, mixed,
                                                              where):
    """A round that raises at its dispatch, and one whose ids raise when
    they are read, with another round in flight either way: every live
    sequence and every one that waited for its last round fails with
    the error, what was queued is served, and the server serves on."""
    prompts, want = mixed
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=10,
                       strategy="greedy")
    calls = []
    real = srv._dispatch_round if where == "dispatch" else srv._fetch

    def failing(*args):
        calls.append(args)
        if len(calls) == (3 if where == "dispatch" else 2):
            raise RuntimeError("injected device failure")
        return real(*args)

    if where == "dispatch":
        srv._dispatch_round = failing
    else:
        srv._fetch = failing
    futs = [srv.submit((p, np.array([m], np.int64)))
            for p, (_, m) in zip(prompts, _MIXED)]
    srv.start()
    failed = 0
    for f, w in zip(futs, want):
        try:
            got = np.asarray(f.result(timeout=300)[0]).reshape(-1)
        except RuntimeError as e:
            assert "injected device failure" in str(e)
            failed += 1
        else:
            assert got.tolist() == w.tolist()
    assert 3 <= failed < len(futs)
    late = srv.submit((prompts[1], np.array([10], np.int64)))
    assert np.asarray(late.result(timeout=300)[0]).reshape(-1).tolist() == \
        want[1].tolist()
    srv.stop()


def test_rounds_are_greedy_only(pred):
    """ONE step program: a server over a prediction layer runs rounds or
    is refused, and no sampling step is built over such a model."""
    with pytest.raises(ValueError, match="greedy only"):
        DecodeServer(pred, slots=SLOTS, max_seq=SEQ, strategy="topk")
    with pytest.raises(ValueError, match="OPT's block only"):
        DecodeServer(pred, slots=SLOTS, max_seq=SEQ, strategy="greedy",
                     speculative=True)
    with pytest.raises(ValueError, match="steps by rounds"):
        pred.acquire("decode", SLOTS, SEQ, "topk")


def test_a_rounds_dispatch_carries_its_positions(pred):
    """A traced run: a round's `dispatch` carries its positions and
    `in_flight`, 1 on every round dispatched behind an unread one (all
    but the first after a park); what a round committed and its
    `decode.spec_round` spans are booked when it is READ, once."""
    from paddle_tpu.observability import tracing

    prompts = _prompts([40, 33], seed=9)
    steps0 = {k: obs.DECODE_STEPS.value(in_flight=k) for k in "01"}
    tracing.set_sample_rate(1.0)
    try:
        tracing.get_recorder().reset()
        spy = _ChainSpy()
        _, outs = _serve(pred, prompts, 6, spy=spy)
        spans = tracing.get_recorder().spans()
    finally:
        tracing.set_sample_rate(0.0)
    # a phase's counts land on its iteration's record
    records = sorted((s for s in spans if s["name"] == "decode.loop.iter"),
                     key=lambda s: s["ts"])
    iters = [s for s in records if "round_positions" in s]
    assert iters and all(
        s["round_positions"] == 2 * s["active"] for s in iters)
    assert all("rows_chosen" in s for s in iters)
    # behind an unread round: whenever the iteration before dispatched one
    behind = [int(i > 0 and "round_positions" in records[i - 1])
              for i, s in enumerate(records) if "round_positions" in s]
    assert [s["in_flight"] for s in iters] == behind
    assert behind[0] == 0 and sum(behind) >= len(behind) - 2
    steps = {k: obs.DECODE_STEPS.value(in_flight=k) - steps0[k]
             for k in "01"}
    assert steps == {"0": behind.count(0), "1": behind.count(1)}
    # every round is read once: by the iteration that dispatched the next
    # one, or by one that dispatched nothing (the last before a park)
    delivered = sum(len(o) - 1 for o in outs)
    assert sum(s.get("round_committed", 0) for s in records) == delivered
    assert sum("round_committed" in s for s in records) == len(iters)
    rounds = [s for s in spans if s["name"] == "decode.spec_round"]
    assert rounds and all(s["proposed"] == 1 for s in rounds)
    assert sum(1 + s["accepted"] for s in rounds) == delivered
    # the counts are the host's lengths with the unread round at one
    # token: where no draft was accepted, the rows the device was fed
    if not any(s["accepted"] for s in rounds):
        assert [s["attended"] for s in iters] == [
            int(f["lengths"].sum()) + s["active"]
            for f, s in zip(spy.feeds, iters)]


# -- matrices held in bfloat16 -----------------------------------------------

def test_matrices_held_in_bfloat16_compute_in_the_stated_arithmetic(
        tmp_path):
    cfg = dict(CFG, precision={"matrices": "bfloat16"})
    w = _seeded(cfg)
    held = {n: str(v.dtype) for n, v in w.items()}
    assert held["lm.l1.attention.q_b.w"] == "bfloat16"
    assert held["lm.l1.moe.experts.up.w"] == "bfloat16"
    assert held["lm.tok_emb"] == held["lm.head.w"] == "bfloat16"
    assert held["lm.mtp.eh_proj.w"] == "bfloat16"
    assert held["lm.l1.moe.router.w"] == "float32"
    assert held["lm.l1.norm_in.w"] == held["lm.mtp.enorm.w"] == "float32"
    assert held["lm.l1.attention.index.k_norm.b"] == "float32"
    p = _pred(str(tmp_path), cfg, w)
    assert {str(v.dtype) for v in p._state.values()} == {"bfloat16",
                                                         "float32"}
    prompts = _prompts([40])
    forced = _prompts([K + 1], seed=4)
    rows, _ = run_serveany._direct_rollout(p, prompts, K, SLOTS, SEQ,
                                           forced=forced)
    text = np.concatenate([prompts[0], forced[0][:K]])
    at = np.arange(len(prompts[0]) - 1, len(prompts[0]) + K)
    got = np.stack(rows[0])
    # the arithmetic the configuration states: both operands bfloat16,
    # float32 sums. At this size the rounding itself is 0.03-0.04 of the
    # logits (the two references are as far from each other: a top-16
    # flips rows at near-ties); the chip's check at the published widths
    # is the sharp one (configs/glm-5.json, check.serve)
    stated = _reference(w, text, at, "bf16_ops", cfg)
    assert _rel(got, stated) < 8e-2
    assert _rel(got, _reference(w, text, at, "highest", cfg)) > 1e-3
    # the matrices' control: float8 with a scale a matrix, the step
    # below; float32 parameters read as they are
    assert _rel(_reference(w, text, at, "fp8_w", cfg), stated) > 5e-2
    held = ref.Fp8Matrices(w)
    assert held["lm.l1.moe.router.w"] is w["lm.l1.moe.router.w"]
    assert float(jnp.abs(held["lm.head.w"].astype(jnp.float32)
                         - w["lm.head.w"].astype(jnp.float32)).max()) > 0
    caches, lens, first, draft, _ = _admit(p, prompts)
    _, ids, logits, _ = _plain(p, caches, lens, first, 2)
    _, rids, rlogits, _ = _round(p, caches, lens, first, ids[0])
    assert (rids[0, :2] == [ids[0][0], ids[1][0]]).all()
    np.testing.assert_allclose(rlogits[0, 0], logits[0][0], atol=1e-5)


# -- the ops' window forms ----------------------------------------------------

def test_interleaved_index_rotation_is_the_references():
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(1, 12, 3, 16)), jnp.float32)
    rot = {"theta": 1e6, "rotary_dim": 8, "interleave": True}
    got = np.asarray(dsa._rotate_first(x, None, rot))[0]
    want = np.asarray(ref.rotate_first_pairs(x[0], 8, 1e6))
    np.testing.assert_allclose(got, want, atol=1e-6)
    half = np.asarray(dsa._rotate_first(
        x, None, {"theta": 1e6, "rotary_dim": 8}))[0]
    np.testing.assert_allclose(
        half, np.asarray(ref.rotate_first_half_split(x[0], 8, 1e6)),
        atol=1e-6)
    assert np.abs(half - got).max() > 1e-2
    at = jnp.asarray([3, 7], jnp.int32)
    two = jnp.concatenate([x[:, :2], x[:, 2:4]], axis=0)
    win = np.asarray(dsa._rotate_first(two, at, rot))
    one = np.asarray(dsa._rotate_first(two[:, 1:], at + 1, rot))
    np.testing.assert_array_equal(win[:, 1:], one)


def _window_case(b=3, t=2, h=4, s=256, row=24, rank=16, j=4, d=16, seed=1):
    r = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)  # noqa
    lens = jnp.asarray([130, 1, 254][:b], jnp.int32)
    return dict(q=f(b, t * h, row), slab=f(b, s, row), lens=lens,
                q_i=f(b, t, j, d), w=f(b, t, j), keys=f(b, s, d),
                rank=rank, h=h, t=t)


def test_a_windows_choice_is_each_rows_own():
    c = _window_case()
    got = np.asarray(dsa.step_mask(c["q_i"], c["w"], c["keys"],
                                   c["lens"], 16))
    assert got.shape == (3, 2, 256)
    for t in range(2):
        one = np.asarray(dsa.step_mask(c["q_i"][:, t:t + 1],
                                       c["w"][:, t:t + 1], c["keys"],
                                       c["lens"] + t, 16))
        np.testing.assert_array_equal(got[:, t], one)
        assert (got[:, t].sum(-1) == np.minimum(
            np.asarray(c["lens"]) + t, 16)).all()


@pytest.mark.parametrize("block_s", [128, 256])
def test_the_window_kernels_are_the_step_kernels_a_row(block_s):
    """Interpret mode: a window's index scores and its attention under
    two masks, one fetch a block, bit for bit what the one-row kernels
    give each row at its own length."""
    c = _window_case()
    b, t, h = 3, c["t"], c["h"]
    j, d = c["q_i"].shape[2:]
    lens2 = c["lens"][:, None] + jnp.arange(t)[None, :]
    scores = np.asarray(dsa.pallas_step_scores(
        c["q_i"].reshape(b, t * j, d), c["w"].reshape(b, t * j), c["keys"],
        c["lens"] + (t - 1), block_s=block_s, interpret=True, n_q=t))
    chosen = dsa.step_mask(c["q_i"], c["w"], c["keys"], c["lens"], 16)
    out = np.asarray(mla.pallas_chosen_attend(
        c["q"], c["slab"], lens2, chosen, c["rank"], "ptpu.test_step",
        block_s=block_s, interpret=True))
    for i in range(t):
        live = np.arange(256)[None, :] < np.asarray(lens2[:, i])[:, None]
        one = np.asarray(dsa.pallas_step_scores(
            c["q_i"][:, i], c["w"][:, i], c["keys"], lens2[:, i],
            block_s=block_s, interpret=True))
        np.testing.assert_array_equal(np.where(live, scores[:, i], 0.0),
                                      np.where(live, one, 0.0))
        row = np.asarray(mla.pallas_chosen_attend(
            c["q"][:, i * h:(i + 1) * h], c["slab"], lens2[:, i],
            chosen[:, i], c["rank"], "ptpu.test_step", block_s=block_s,
            interpret=True))
        np.testing.assert_array_equal(out[:, i * h:(i + 1) * h], row)
    lax_out = np.asarray(mla._latent_attend_lax(
        c["q"], c["slab"], lens2, c["rank"], chosen))
    np.testing.assert_allclose(out, lax_out, atol=2e-2)


def test_a_windows_rows_land_at_their_positions():
    r = np.random.default_rng(2)
    slab = jnp.asarray(r.normal(size=(3, 32, 8)), jnp.float32)
    new = jnp.asarray(r.normal(size=(3, 2, 8)), jnp.float32)
    pos = jnp.asarray([0, 7, 30], jnp.int32)
    got = np.asarray(mla.mla_append(slab, new, pos))
    want = np.asarray(slab).copy()
    for i, p in enumerate([0, 7, 30]):
        want[i, p:p + 2] = np.asarray(new)[i]
    np.testing.assert_array_equal(got, want)
    one = np.asarray(mla.mla_append(slab, new[:, :1], pos))
    np.testing.assert_array_equal(one[1, 7], np.asarray(new)[1, 0])
    with pytest.raises(ValueError, match="ONE row"):
        mla.mla_append(slab, new, pos, ring=True)


def test_next_tokens_and_the_pick():
    tokens = jnp.asarray([[5, 6, 7, 0], [9, 8, 0, 0]], jnp.int32)
    got = np.asarray(speculative.mtp_next_tokens(
        tokens, jnp.asarray([3, 2]), jnp.asarray([41, 42])))
    assert got[0, :3].tolist() == [6, 7, 41]
    assert got[1, :2].tolist() == [8, 42]
    ids = jnp.asarray([[1, 2], [3, 4], [5, 6]])
    assert np.asarray(speculative.spec_pick(
        ids, jnp.asarray([0, 1, 0]))).tolist() == [1, 4, 5]
