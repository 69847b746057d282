"""A Solar-Open2-250B-family LM (three Kimi-Delta-Attention layers under
Kimi Linear's UNBOUNDED softplus decay gate, the decay and the output
gate each through a bottleneck, a write strength in (0, 2), to one
gated softmax layer of more query heads than key/value heads with NO
positional term; every layer ends in routed experts under a softmax
router, one chip's share held, with a shared one) through the normal
serving path (`save_decode_model` -> `DecodePredictor` ->
`DecodeServer`) at a tiny size: prefill (the CHUNKED delta rule in its
guarded form, causal attention) then decode (one update a step, the
grouped attention over the slab) LOGITS against the plain reference's
full forward pass (`benchmark/reference/solar_open2.py`: the recurrence
a token at a time, which imports nothing of the program), slots
admitted at different lengths and steps; the sixteen shares of an
expert-parallel deployment adding up to the uncut layer; the `state` +
`rows` entries of `cache_spec`; what `_check` refuses of the new
fields; the manifest, and an accepted configuration's manifest as it
was."""
import json
import os
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.serving.decode import (  # noqa: E402
    DecodeConfig, DecodePredictor, DecodeServer, cache_spec,
    save_decode_model)

from benchmark.lib import weights  # noqa: E402
from benchmark.models import solar_open2_lm  # noqa: E402
from benchmark.reference import solar_open2 as ref  # noqa: E402

with open(os.path.join(_ROOT, "benchmark", "tests", "tiny",
                       "solar-open2-tiny.json")) as _f:
    # hidden 64; layers gqa, kda, kda, kda; KDA: 4 heads of 16 key and
    # value channels, windows of 3 rows, bottlenecks of 16; attention: 4
    # query heads on 2 key/value heads of 16; 16 routed experts of 24, 4
    # a token, experts 0..3 held, and a shared one
    CFG = json.load(_f)
SLOTS, SEQ, N_LAYER = 4, 128, 4
KDA_LAYERS = (1, 2, 3)
STATE = 4 * 16 * 16 * 4  # a layer's matrix states a slot, bytes
WINDOW = 3 * 64 * 4      # one window a slot, bytes
ROW = 2 * 16 * 4         # a position's K (or V) row, bytes


def _seeded(cfg):
    specs = solar_open2_lm.parameter_specs(cfg, "serve")
    return weights.seeded_weights(specs, 2 ** 31 + 7,
                                  solar_open2_lm.init_rule)


def _pred(d, cfg, w):
    scope = fluid.Scope()
    for n in w:
        scope.set_var(n, w[n])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        save_decode_model(d, solar_open2_lm.decode_config(cfg, "serve"),
                          exe, scope=scope)
    return DecodePredictor(d)


@pytest.fixture(scope="module")
def seeded():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def pred(tmp_path_factory, seeded):
    return _pred(str(tmp_path_factory.mktemp("solar_model")), CFG, seeded)


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
# inside one chunk of 64; past one, with the bucket's padding inside a
# chunk (70 of 128); most of two
PROBE_LENS = [5, 70, 100]


@pytest.fixture(scope="module")
def probes(pred):
    prompts = _prompts(PROBE_LENS)
    forced = _prompts([K + 1] * len(prompts), seed=4)
    return prompts, forced, _rollout(pred, prompts, K, forced)


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


@pytest.mark.parametrize("which", range(len(PROBE_LENS)),
                         ids=["len%d" % n for n in PROBE_LENS])
def test_prefill_then_decode_matches_the_reference(probes, seeded, which):
    """Prompts of 5, 70 and 100 tokens in buckets of 16 and 128, in
    three neighbouring slots at three lengths, then 6 teacher-forced
    steps (one update of each matrix state, the grouped attention over
    the slab's live rows), against the reference's ONE full forward
    pass, whose delta rule runs a token at a time. LOGITS, tolerance
    2e-4 relative L2: float32 on the CPU on both sides, the chunked
    form's guarded blocks against the recurrence (the program reads
    1e-6)."""
    prompts, forced, got = probes
    err = _rel(got[which], _want(seeded, prompts[which], forced[which]))
    assert err < 2e-4, err


@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v])
def test_a_reference_that_leaves_a_part_out_is_told_apart(
        probes, seeded, variant):
    """The comparison sees each mechanism: against a reference whose
    beta is not doubled, without the decay, whose decay skips the
    bottleneck's second matrix or is bounded at -5 again, whose KDA gate
    is a head's and not a channel's, without the attention gate or the
    shared expert, with keys rotated where none should be, or whose
    state is a token stale where the prefill hands over to the step, the
    same logits are far away (5e-4 is 500 times the program's distance
    from the true reference)."""
    prompts, forced, got = probes
    err = _rel(got[2], _want(seeded, prompts[2], forced[2], variant))
    assert err > 5e-4, (variant, err)


def _is_greedy(seeded, prompt, generated):
    full = np.concatenate([prompt, generated])
    lg = _reference(seeded, full, np.arange(len(prompt) - 1, len(full) - 1))
    return lg.argmax(-1).tolist() == list(generated)


def test_a_request_admitted_beside_live_ones(pred, seeded):
    """Two slots, four requests: the second is admitted while the first
    is some steps into its reply, later ones reuse both slots at other
    lengths. Each answer is the reference's greedy rollout, which knows
    no slot, no state and no last occupant: a matrix state, a window, a
    K/V row or an expert load that leaks between neighbours, or a state
    an admission did not replace whole, fails here."""
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


# -- one chip's share: a sixteenth -----------------------------------------------

def test_the_sixteen_shares_and_the_shared_expert_add_up():
    """The guide's share test at the deployment's own counts: 320 routed
    experts over 16 chips, 20 a chip, 8 a token. `ops/moe.py` with
    `experts_held` = each share in turn: the routed parts of all sixteen
    + the shared expert counted ONCE == the uncut layer, by the
    program's ops and by the reference alike; every (token, expert) pair
    falls on exactly one share."""
    from paddle_tpu.ops import moe

    r = np.random.default_rng(5)
    d, f, n, k, per = 32, 8, 320, 8, 20
    x = jnp.asarray(r.normal(size=(11, d)), jnp.float32)
    p = {"router.w": jnp.asarray(r.normal(size=(d, n)) * 0.3, jnp.float32)}
    for nm, shape in (("gate", (n, d, f)), ("up", (n, d, f)),
                      ("down", (n, f, d))):
        p["experts.%s.w" % nm] = jnp.asarray(r.normal(size=shape) * 0.1,
                                             jnp.float32)
    for nm, shape in (("gate", (d, f)), ("up", (d, f)), ("down", (f, d))):
        p["shared.%s.w" % nm] = jnp.asarray(r.normal(size=shape) * 0.1,
                                            jnp.float32)
    cfg = dict(CFG, num_experts_per_tok=k)
    whole = np.asarray(ref.moe(p, x, dict(cfg, experts_held=[0, n]),
                               "highest"))
    idx, w = moe.moe_route(x, p["router.w"], k, 1.0, score="softmax")
    shared = moe.moe_shared(x, p["shared.gate.w"], p["shared.up.w"],
                            p["shared.down.w"])
    total, total_ref, loads = np.asarray(shared), None, 0
    for lo in range(0, n, per):
        held = {"experts.%s.w" % nm: p["experts.%s.w" % nm][lo:lo + per]
                for nm in ("gate", "up", "down")}
        part, load = moe.moe_experts(
            x, idx, w, held["experts.gate.w"], held["experts.up.w"],
            held["experts.down.w"], lo=lo)
        total = total + np.asarray(part)
        loads += int(load.sum())
        share = np.asarray(ref.moe(
            dict(p, **held), x, dict(cfg, experts_held=[lo, lo + per]),
            "highest", shared=(lo == 0)))
        total_ref = share if total_ref is None else total_ref + share
    assert loads == 11 * k  # every pair fell on exactly one share
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(total_ref, whole, rtol=2e-4, atol=2e-5)


# -- the cache manager's one description, the counts ---------------------------

def test_cache_spec_has_state_entries_beside_a_slab(pred):
    spec = pred.cache_spec(SLOTS, SEQ)
    names = [e.name for e in spec]
    assert names == sorted(names)
    assert names == sorted(
        ["%s_%d" % (n, i) for i in KDA_LAYERS
         for n in ("convq", "convk", "convv", "kda")]
        + ["kcache_0", "vcache_0"])
    by = {e.name: e for e in spec}
    assert tuple(by["kda_1"]) == ("kda_1", (SLOTS, 4, 16, 16), "float32",
                                  False)
    assert tuple(by["convk_3"]) == ("convk_3", (SLOTS, 3, 64), "float32",
                                    False)
    assert tuple(by["kcache_0"]) == ("kcache_0", (SLOTS, SEQ, 2, 16),
                                     "float32", True)
    slabs = ("kcache_0", "vcache_0")
    assert {e.name: e.kind for e in spec} == dict(
        {n: "state" for n in names if n not in slabs},
        **{n: "rows" for n in slabs})
    _, feeds, fetches = pred._build("decode", SLOTS, SEQ, "greedy")
    assert feeds == ["tokens", "lengths", "seed"] + names
    assert len(fetches) == 2 + len(spec) + 1
    per_slot = 3 * (STATE + 3 * WINDOW) + 2 * SEQ * ROW
    assert sum(e.nbytes for e in pred.cache_spec(1, SEQ)) == per_slot
    assert (sum(e.nbytes for e in cache_spec(pred.config, 1, 16))
            == per_slot - 2 * (SEQ - 16) * ROW)


def test_server_books_state_bytes_rows_and_scanned_tokens(pred):
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=4)
    assert srv._kda_state_bytes_per_slot == 3 * STATE
    counts = srv._step_counts(np.array([3, 0, 30, 0], np.int32), 2)
    assert (counts["active"], counts["attended"],
            counts["kda_state_bytes"]) == (2, 35, 2 * 3 * STATE)
    prompts = _prompts([20, 3], seed=11)
    sc = srv._scatter_counts(2, prompts, bucket_rows=2 * 32)
    assert sc["entries"] == 14 and sc["state_slots"] == 2
    assert (sc["kda_tokens"], sc["kda_pad_tokens"]) == (23, 41)
    # what a prefill's model FLOPs are counted from, latent layer or not
    assert (sc["prompt_rows"], sc["bucket_rows"], sc["prompts"],
            sc["attn_pairs"]) == (23, 64, 2, 20 * 21 // 2 + 3 * 4 // 2)
    srv.start()
    for f in [srv.submit((p, np.array([4], np.int64))) for p in prompts]:
        f.result(timeout=300)
    srv.stop()
    assert srv.moe_load_total.shape == (4, 4)
    assert int(srv.moe_load_total.sum()) > 0


# -- the manifest ---------------------------------------------------------------

def test_manifest_round_trip(pred, seeded):
    cfg = solar_open2_lm.decode_config(CFG, "serve")
    d = json.loads(json.dumps(cfg.to_dict()))
    again = DecodeConfig.from_dict(d)
    assert again.to_dict() == cfg.to_dict() == pred.config.to_dict()
    assert again.layer_kinds() == ["attention", "kda", "kda", "kda"]
    assert again.ffn_kinds() == ["experts"] * 4
    assert again.has_state and not (again.has_latent or again.has_ring)
    assert (again.n_head, again.n_kv_head, again.d_head) == (4, 2, 16)
    assert (again.kda_heads, again.kda_head_dim, again.kda_conv,
            again.kda_gate, again.kda_beta_max, again.kda_decay_rank) == (
        4, 16, 4, "softplus", 2.0, 16)
    assert again.attn_gate == "per_channel" and again.rope is None
    assert (again.n_expert, again.expert_top_k, again.held,
            again.router_score, again.router_scale) == (
        16, 4, (0, 4), "softmax", 1)
    assert {"kda_beta_max", "kda_decay_rank", "kda_gate",
            "attn_gate"} <= set(d)
    assert sorted(n for n in pred._state if ".l1.kda." in n) == sorted(
        "lm.l1.kda." + nm for nm in (
            "q.w", "k.w", "v.w", "conv_q.w", "conv_k.w", "conv_v.w",
            "f_a.w", "f_b.w", "beta.w", "A_log", "dt_bias", "o_norm.w",
            "gate_a.w", "gate_b.w", "o.w"))
    assert tuple(seeded["lm.l1.kda.f_a.w"].shape) == (64, 16)
    assert tuple(seeded["lm.l1.kda.gate_b.w"].shape) == (16, 64)
    assert sorted(n for n in pred._state if ".l0.attention." in n) == sorted(
        "lm.l0.attention.%s.w" % nm for nm in ("q", "k", "v", "gate", "o"))
    assert tuple(seeded["lm.l0.attention.gate.w"].shape) == (64, 64)
    assert tuple(seeded["lm.l0.attention.k.w"].shape) == (64, 32)


def test_an_accepted_manifest_loads_as_it_was():
    """The new fields are written to a manifest only where set, and a
    manifest written before they existed (Ling-3.0-flash's: KDA layers,
    a gate a head) loads with their defaults: beta in (0, 1), one full
    decay matrix, the same parameter set."""
    from benchmark.models import ling3_lm
    from paddle_tpu.models import jamba

    with open(os.path.join(_ROOT, "benchmark", "tests", "tiny",
                           "ling3-tiny.json")) as f:
        ling = json.load(f)
    cfg = ling3_lm.decode_config(ling, "serve")
    d = json.loads(json.dumps(cfg.to_dict()))
    assert not {"kda_beta_max", "kda_decay_rank"} & set(d)
    again = DecodeConfig.from_dict(d)
    assert (again.kda_beta_max, again.kda_decay_rank,
            again.attn_gate) == (1.0, 0, "per_head")
    jamba._check(again)
    names = {n for n, _, _ in ling3_lm.parameter_specs(ling, "serve")}
    assert "lm.l0.kda.f.w" in names and "lm.l0.kda.gate.w" in names
    assert not [n for n in names if n.endswith(("f_a.w", "gate_a.w"))]
    plain = DecodeConfig(97, n_layer=1, n_head=4, d_model=64, n_kv_head=2,
                         norm="rms_norm", ffn="gated_silu", positions=False,
                         biases=False)
    assert not [f for f in plain.to_dict() if f.startswith("kda_")]


def test_config_and_builders_refuse_what_is_not_built():
    from paddle_tpu.models import jamba
    from paddle_tpu.ops import kda as K

    base = dict(n_layer=1, n_head=4, d_model=64, norm="rms_norm",
                ffn="gated_silu", positions=False, biases=False)
    kda = dict(base, layer_types=["kda"], kda_heads=4, kda_head_dim=16)
    jamba._check(DecodeConfig(97, kda_beta_max=2.0, kda_decay_rank=16,
                              attn_gate="per_channel", **kda))
    for bad in (0.5, 3.0, 0):
        with pytest.raises(ValueError, match="kda_beta_max"):
            jamba._check(DecodeConfig(97, kda_beta_max=bad, **kda))
    for bad in (-1, 64, 1.5, 1000):
        with pytest.raises(ValueError, match="kda_decay_rank"):
            jamba._check(DecodeConfig(97, kda_decay_rank=bad, **kda))
    with pytest.raises(ValueError, match="attn_gate 'elementwise'"):
        jamba._check(DecodeConfig(97, attn_gate="elementwise", **base))
    latent = dict(base, layer_types=["latent"], kv_lora_rank=24,
                  qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)
    with pytest.raises(ValueError, match="beside a latent layer only a sigmoid gate"):
        jamba._check(DecodeConfig(97, attn_gate="per_channel", **latent))
    with pytest.raises(ValueError, match="write strength"):
        K.kda_gate(jnp.zeros((1, 1, 64)), jnp.zeros((1, 1, 4)),
                   jnp.zeros((4,)), jnp.zeros((64,)), beta_max=1.5)
    # what the source's keys say and no graph builds is refused by the
    # builder, never ignored
    for key, value in (("use_rope", True), ("kda_use_full_proj", True),
                       ("kda_allow_neg_eigval", False),
                       ("use_gqa_gate", False)):
        with pytest.raises(AssertionError):
            solar_open2_lm.decode_config(dict(CFG, **{key: value}), "serve")
