"""A Ling-3.0-flash-family LM (Kimi-Delta-Attention layers: a matrix
state a head written by a delta rule under a decay a channel, three
convolution windows; one latent-attention layer whose query has no
bottleneck and whose query/key head is wider than its value head; a
sigmoid gate a head on both mixers; a leading dense MLP, then routed
experts under a sigmoid router with a selection bias and group-limited
choice, one GROUP held here) through the normal serving path
(`save_decode_model` -> `DecodePredictor` -> `DecodeServer`) at a tiny
size: prefill (the CHUNKED delta rule, the expanded attention) then
decode (one update a step, the absorbed attention) LOGITS against the
plain reference's full forward pass (`benchmark/reference/ling3.py`: the
recurrence a token at a time, which imports nothing of the program),
slots admitted at different lengths and steps; both decay gates; the
shares of an expert-parallel deployment adding up to the uncut layer;
the `state` + `latent` entries of `cache_spec`; what such a cache
refuses by name; the manifest."""
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
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.serving.decode import (  # noqa: E402
    DecodeConfig, DecodePredictor, DecodeServer, cache_spec, kv_slab_slots,
    save_decode_model)

from benchmark.lib import weights  # noqa: E402
from benchmark.models import ling3_lm  # noqa: E402
from benchmark.reference import ling3 as ref  # noqa: E402

# hidden 64, 4 heads; KDA heads of 16 key and value channels, windows of
# 3 rows; the latent layer's heads 16 + 8 query/key and 16 value
# channels over a latent of 24 + 8 = 32 floats a position; layers kda,
# kda, latent, kda (a period of 3); layer 0 dense, then 16 routed
# experts in 4 groups of which 2 are kept, 4 a token, GROUP 0 (experts
# 0..3) held, and a shared one
CFG = dict(
    model_type="bailing_hybrid", vocab_size=97, hidden_size=64,
    intermediate_size=96, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, qk_head_dim=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, rotary_dim=8, v_head_dim=16, q_lora_rank=None,
    kv_lora_rank=24, layer_group_size=3, first_k_dense_replace=1,
    short_conv_kernel_size=4, kda_lower_bound=-5, kda_safe_gate=True,
    no_kda_lora=True, use_kda_lora=False, linear_silu=True,
    group_norm_size=1, use_qk_norm=True, value_norm=False,
    gated_attention_proj_granularity_type="head_wise",
    use_bias=False, use_qkv_bias=False, use_nGPT=False, up_proj_norm=False,
    use_mla_nope=False, hidden_act="silu", rms_norm_eps=1e-6,
    num_experts=4, num_experts_scored=16, experts_held=[0, 4],
    num_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=24, norm_topk_prob=True, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, score_function="sigmoid",
    scoring_func="sigmoid", topk_method="noaux_tc",
    moe_router_enable_expert_bias=True, scale_router_input=False,
    rope_interleave=True, rope_scaling=None, rope_theta=6000000,
    tie_word_embeddings=False,
    expert_swiglu_limit_list=[0] * 4 + [4],
    share_expert_swiglu_limit_list=[0] * 4 + [5],
    assumed={"kda_gate": "lower_bound_sigmoid", "kda_decay_rank": "full",
             "output_gate": "per_head", "qk_norm_scope": "kda_l2",
             "group_score": "top2_sum"},
    serve={"max_seq": 128})
SLOTS, SEQ, N_LAYER, ROW = 4, 128, 4, 32
KDA_LAYERS = (0, 1, 3)
STATE = 4 * 16 * 16 * 4  # a layer's matrix states a slot, bytes
WINDOW = 3 * 64 * 4      # one window a slot, bytes


def _seeded(cfg):
    specs = ling3_lm.parameter_specs(cfg, "serve")
    return weights.seeded_weights(specs, 2 ** 31 + 7, ling3_lm.init_rule)


def _pred(d, cfg, w):
    scope = fluid.Scope()
    for n in w:
        scope.set_var(n, w[n])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        save_decode_model(d, ling3_lm.decode_config(cfg, "serve"), exe,
                          scope=scope)
    return DecodePredictor(d)


@pytest.fixture(scope="module")
def seeded():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def pred(tmp_path_factory, seeded):
    return _pred(str(tmp_path_factory.mktemp("ling3_model")), CFG, seeded)


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


def _reference(w, text, rows, variant="", cfg=CFG):
    """`rows` of the plain reference's logits over `text`, in one full
    forward pass. The reference is causal, so the text is padded to SEQ
    positions: its programs then compile once a file."""
    padded = np.zeros((SEQ,), np.int64)
    padded[:len(text)] = text
    return np.asarray(ref.serve_logits(
        w, jnp.asarray(padded), cfg, N_LAYER, rows=np.asarray(rows),
        variant=variant))


def _want(w, p, f, variant="", cfg=CFG):
    return _reference(w, np.concatenate([p, f[:K]]),
                      np.arange(len(p) - 1, len(p) + K), variant, cfg)


@pytest.mark.parametrize("which", range(len(PROBE_LENS)),
                         ids=["len%d" % n for n in PROBE_LENS])
def test_prefill_then_decode_matches_the_reference(probes, seeded, which):
    """Prompts of 5, 70 and 100 tokens in buckets of 16 and 128, in
    three neighbouring slots at three lengths, then 6 teacher-forced
    steps (one update of each matrix state, the absorbed path through
    the latent slab), against the reference's ONE full forward pass,
    whose delta rule runs a token at a time. LOGITS, tolerance 2e-4
    relative L2: float32 on the CPU on both sides."""
    prompts, forced, got = probes
    err = _rel(got[which], _want(seeded, prompts[which], forced[which]))
    assert err < 2e-4, err


@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v])
def test_a_reference_that_leaves_a_part_out_is_told_apart(
        probes, seeded, variant):
    """The comparison sees each mechanism: against a reference without
    the decay, with beta 1, without the convolutions, the L2 norm, the
    output gates, the shared expert, the rotation of k_r, the group
    limit or the selection bias, or whose state is a token stale where
    the prefill hands over to the step or zeroed at a chunk boundary,
    the same logits are far away, where the program is 1e-6 from the
    true reference."""
    prompts, forced, got = probes
    err = _rel(got[2], _want(seeded, prompts[2], forced[2], variant))
    assert err > 5e-4, (variant, err)


def _is_greedy(seeded, prompt, generated):
    full = np.concatenate([prompt, generated])
    lg = _reference(seeded, full, np.arange(len(prompt) - 1, len(full) - 1))
    return lg.argmax(-1).tolist() == list(generated)


def test_generate_is_the_reference_greedy_rollout(pred, seeded):
    prompts = _prompts([5, 17])
    outs = pred.generate(prompts, max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)
    assert all(_is_greedy(seeded, p, o) for p, o in zip(prompts, outs))


def test_a_request_admitted_beside_live_ones(pred, seeded):
    """Two slots, four requests: the second is admitted while the first
    is some steps into its reply, later ones reuse both slots at other
    lengths. Each answer is the reference's greedy rollout, which knows
    no slot, no state and no last occupant: a matrix state, a window, a
    latent row or an expert load that leaks between neighbours, or a
    state an admission did not replace whole, fails here."""
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


def test_the_published_softplus_gate_is_built_too(tmp_path):
    """`assumed.kda_gate` = "softplus" (Kimi Linear's published gate: no
    lower bound, so the chunked form takes its guarded path) through the
    same predictor against the reference with the same field."""
    cfg = dict(CFG, assumed=dict(CFG["assumed"], kda_gate="softplus"))
    w = _seeded(cfg)
    pred = _pred(str(tmp_path), cfg, w)
    assert pred.config.kda_gate == "softplus"
    (p,), (f,) = _prompts([70]), _prompts([K + 1], seed=4)
    (got,) = _rollout(pred, [p], K, [f])
    assert _rel(got, _want(w, p, f, cfg=cfg)) < 2e-4
    # and the two gates are two models
    assert _rel(got, _want(w, p, f)) > 1e-2


# -- one chip's share: a GROUP ---------------------------------------------------

def test_the_shares_routed_parts_and_the_shared_expert_add_up(seeded):
    """`ops/moe.py` with `experts_held` = each of the groups in turn
    (here four of 4 experts; the cell's are eight of 64): the routed
    parts of all shares + the shared expert counted ONCE == the uncut
    layer, by the program's ops and by the reference alike; and every
    token sends exactly `topk_group` of the shares something."""
    from paddle_tpu.ops import moe

    r = np.random.default_rng(5)
    d, f, n, k = 64, 24, 16, 4
    x = jnp.asarray(r.normal(size=(11, d)), jnp.float32)
    p = {"router.w": jnp.asarray(r.normal(size=(d, n)) * 0.3, jnp.float32),
         "router.bias": jnp.asarray(r.normal(size=(n,)) * 0.1, jnp.float32)}
    for nm, shape in (("gate", (n, d, f)), ("up", (n, d, f)),
                      ("down", (n, f, d))):
        p["experts.%s.w" % nm] = jnp.asarray(r.normal(size=shape) * 0.1,
                                             jnp.float32)
    for nm, shape in (("gate", (d, f)), ("up", (d, f)), ("down", (f, d))):
        p["shared.%s.w" % nm] = jnp.asarray(r.normal(size=shape) * 0.1,
                                            jnp.float32)
    uncut = dict(CFG, experts_held=[0, n])
    whole = np.asarray(ref.moe(p, x, uncut, "highest"))
    idx, w = moe.moe_route(x, p["router.w"], k, 2.5, bias=p["router.bias"],
                           n_group=4, topk_group=2)
    shared = moe.moe_shared(x, p["shared.gate.w"], p["shared.up.w"],
                            p["shared.down.w"])
    total, total_ref, loads, elsewhere = np.asarray(shared), None, 0, 0
    for lo in range(0, n, 4):
        part, load = moe.moe_experts(
            x, idx, w, p["experts.gate.w"][lo:lo + 4],
            p["experts.up.w"][lo:lo + 4], p["experts.down.w"][lo:lo + 4],
            lo=lo, count_elsewhere=True)
        total = total + np.asarray(part)
        loads += int(load[:-1].sum())
        elsewhere += int(load[-1])
        sub = dict(p, **{"experts.%s.w" % nm: p["experts.%s.w" % nm][
            lo:lo + 4] for nm in ("gate", "up", "down")})
        share = np.asarray(ref.moe(sub, x, dict(CFG, experts_held=[lo, lo + 4]),
                                   "highest", shared=(lo == 0)))
        total_ref = share if total_ref is None else total_ref + share
    assert loads == 11 * k  # every pair fell on exactly one share
    # a token's pairs lie in at most 2 of the 4 groups
    assert elsewhere >= 11 * 2
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(total_ref, whole, rtol=2e-4, atol=2e-5)


def test_moe_load_counts_pairs_and_tokens_elsewhere(pred):
    (p,) = _prompts([21], seed=5)
    pexe, _ = pred.acquire("prefill", 1, 32)
    tokens = np.zeros((1, 32), np.int64)
    tokens[0, :21] = p
    outs = pexe({"tokens": tokens, "lengths": np.array([21], np.int32)},
                pred._state)
    load = np.asarray(outs[-1])
    # three sparse layers; four held experts and the tokens that sent
    # them nothing
    assert load.shape == (3, 5) and load.dtype == np.int32
    assert len(outs) == 1 + len(pred.cache_spec(1, 32)) + 1
    assert 0 < load[:, :4].sum() <= 3 * 21 * 4
    assert (load[:, 4] <= 21).all() and load[:, 4].sum() > 0
    # a token with a pair here has at most 4; one without is counted
    assert (load[:, :4].sum(axis=1) <= 4 * (21 - load[:, 4])).all()


# -- the cache manager's one description, the counts ---------------------------

def test_cache_spec_has_state_and_latent_entries(pred):
    spec = pred.cache_spec(SLOTS, SEQ)
    names = [e.name for e in spec]
    assert names == sorted(names)
    assert names == sorted(
        ["%s_%d" % (n, i) for i in KDA_LAYERS
         for n in ("convq", "convk", "convv", "kda")] + ["latent_2"])
    by = {e.name: e for e in spec}
    assert tuple(by["kda_1"]) == ("kda_1", (SLOTS, 4, 16, 16), "float32",
                                  False)
    assert tuple(by["convk_3"]) == ("convk_3", (SLOTS, 3, 64), "float32",
                                    False)
    assert tuple(by["latent_2"]) == ("latent_2", (SLOTS, SEQ, ROW),
                                     "float32", True)
    assert {e.name: e.kind for e in spec} == dict(
        {n: "state" for n in names if n != "latent_2"}, latent_2="latent")
    _, feeds, fetches = pred._build("decode", SLOTS, SEQ, "greedy")
    assert feeds == ["tokens", "lengths", "seed"] + names
    assert len(fetches) == 2 + len(spec) + 1
    # capacity: the states cost the same whatever the slab's length
    per_slot = 3 * (STATE + 3 * WINDOW) + SEQ * ROW * 4
    assert sum(e.nbytes for e in pred.cache_spec(1, SEQ)) == per_slot
    assert kv_slab_slots(10 * per_slot + 1, pred.config, SEQ) == 10
    assert (sum(e.nbytes for e in cache_spec(pred.config, 1, 16))
            == per_slot - (SEQ - 16) * ROW * 4)
    with pytest.raises(ValueError, match="latent.*state|state.*latent"):
        pred.cache_spec(SLOTS, SEQ, "int8")


def test_server_books_state_bytes_and_scanned_tokens(pred):
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=4)
    assert srv._kda_state_bytes_per_slot == 3 * STATE
    assert srv._state_bytes_per_slot == 3 * (STATE + 3 * WINDOW)
    counts = srv._step_counts(np.array([3, 0, 30, 0], np.int32), 2)
    assert counts == {
        "active": 2, "attended": 35, "streamed": SLOTS * SEQ,
        "state_bytes": 2 * SLOTS * 3 * (STATE + 3 * WINDOW),
        "expert_pairs": 0, "experts_active": 0, "latent_rows": 35,
        "latent_row_bytes": ROW * 4, "kda_state_bytes": 2 * 3 * STATE}
    prompts = _prompts([20, 3], seed=11)
    sc = srv._scatter_counts(2, prompts, bucket_rows=2 * 32)
    assert sc["entries"] == 13 and sc["state_slots"] == 2
    assert (sc["kda_tokens"], sc["kda_pad_tokens"]) == (23, 41)
    assert (sc["prompt_rows"], sc["bucket_rows"], sc["prompts"]) == (
        23, 64, 2)

    def elsewhere():
        return sum(v for _, v in obs.MOE_TOKENS_ELSEWHERE.samples())

    before = elsewhere()
    srv.start()
    for f in [srv.submit((p, np.array([4], np.int64))) for p in prompts]:
        f.result(timeout=300)
    srv.stop()
    assert srv.moe_load_total.shape == (3, 4)
    assert int(srv.moe_load_total.sum()) > 0
    assert elsewhere() > before


# -- what a matrix state refuses, by name ----------------------------------------

@pytest.mark.parametrize("kwargs", [
    {"speculative": True}, {"prefix_cache": True},
    {"prefix_store": object()}, {"kv_dtype": "int8"}],
    ids=["speculative", "prefix_cache", "prefix_store", "int8"])
def test_server_refuses_what_is_not_built_over_a_state(pred, kwargs):
    with pytest.raises(ValueError, match=r"delta-rule \(KDA\) layers keep a "
                                         "recurrent state .*kind 'state'"):
        DecodeServer(pred, slots=2, max_seq=SEQ, **kwargs)


@pytest.mark.parametrize("call", ["generate_speculative", "generate_beam"])
def test_predictor_refuses_what_is_not_built_over_a_state(pred, call):
    with pytest.raises(ValueError, match="kind 'state'"):
        getattr(pred, call)(_prompts([5]), max_new_tokens=4)


# -- the manifest ---------------------------------------------------------------

def test_manifest_round_trip(pred, seeded):
    cfg = ling3_lm.decode_config(CFG, "serve")
    d = json.loads(json.dumps(cfg.to_dict()))
    again = DecodeConfig.from_dict(d)
    assert again.to_dict() == cfg.to_dict() == pred.config.to_dict()
    assert again.layer_kinds() == ["kda", "kda", "latent", "kda"]
    assert again.ffn_kinds() == ["dense"] + ["experts"] * 3
    assert again.has_latent and again.has_state
    assert not (again.has_ring or again.is_opt_block)
    assert again.latent_row == ROW and again.held == (0, 4)
    assert (again.router_groups, again.router_topk_groups,
            again.router_bias) == (4, 2, True)
    assert (again.kda_heads, again.kda_head_dim, again.kda_conv,
            again.kda_gate, again.kda_gate_bound) == (
        4, 16, 4, "lower_bound_sigmoid", -5.0)
    assert again.q_lora_rank == 0 and again.attn_gate == "per_head"
    assert again.rope == {"latent": {"theta": 6e6, "interleave": True}}
    # the new fields are written only where set: the gate and the conv
    # at their defaults are not, and no other model's manifest has any
    assert {"kda_heads", "kda_head_dim", "router_groups",
            "router_topk_groups", "router_bias"} <= set(d)
    assert not {"kda_gate", "kda_gate_bound", "kda_conv",
                "q_lora_rank"} & set(d)
    plain = DecodeConfig(97, n_layer=1, n_head=4, d_model=64, n_kv_head=2,
                         norm="rms_norm", ffn="gated_silu", positions=False,
                         biases=False)
    assert not [f for f in plain.to_dict()
                if f.startswith(("kda_", "router_g", "router_t",
                                 "router_b"))]
    # a latent layer without a query bottleneck holds ONE query matrix
    assert sorted(n for n in pred._state if ".l2.attention." in n) == sorted(
        "lm.l2.attention.%s.w" % nm
        for nm in ("q", "kv_a", "kv_norm", "kv_b", "gate", "o"))
    assert tuple(seeded["lm.l2.attention.q.w"].shape) == (64, 4 * 24)
    assert sorted(n for n in pred._state if ".l0.kda." in n) == sorted(
        "lm.l0.kda." + nm for nm in (
            "q.w", "k.w", "v.w", "conv_q.w", "conv_k.w", "conv_v.w", "f.w",
            "beta.w", "A_log", "dt_bias", "o_norm.w", "gate.w", "o.w"))
    assert tuple(seeded["lm.l1.moe.router.bias"].shape) == (16,)


def test_config_and_builders_refuse_what_is_not_built():
    from paddle_tpu.models import jamba

    base = dict(n_layer=1, n_head=4, d_model=64, norm="rms_norm",
                ffn="gated_silu", positions=False, biases=False)
    with pytest.raises(ValueError, match="a kda layer needs kda_heads"):
        DecodeConfig(97, layer_types=["kda"], **base)
    kda = dict(base, layer_types=["kda"], kda_heads=4, kda_head_dim=16)
    jamba._check(DecodeConfig(97, **kda))
    with pytest.raises(ValueError, match="kda_gate 'tanh'"):
        jamba._check(DecodeConfig(97, kda_gate="tanh", **kda))
    with pytest.raises(ValueError, match="without differential"):
        jamba._check(DecodeConfig(97, attn_biases=True, **kda))
    experts = dict(base, ffn_types=["experts"], n_expert=16, expert_top_k=4,
                   d_expert=8, d_shared_expert=8)
    with pytest.raises(ValueError, match="16 experts in 3 groups"):
        DecodeConfig(97, router_groups=3, **experts)
    with pytest.raises(ValueError, match="do not hold a top-4"):
        DecodeConfig(97, router_groups=8, router_topk_groups=1, **experts)
    # a clamp on the gated product is refused, not ignored
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        with pytest.raises(ValueError, match="clamped SwiGLU is not built"):
            ling3_lm.decode_config(dict(CFG, **{key: [0, 0, 4, 0]}), "serve")
