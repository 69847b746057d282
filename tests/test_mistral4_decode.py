"""A Mistral-Small-4-family LM (every layer multi-head latent attention:
a latent cache row of `kv_lora_rank + qk_rope_head_dim` floats a
position, a prefill that EXPANDS it to K and V of every head, a decode
step that attends it ABSORBED; rotary pairs (2i, 2i+1) under YaRN, a
position-dependent query scale; a softmax router over routed experts
with a shared one, the experts held here; an untied head) through the
normal serving path (`save_decode_model` -> `DecodePredictor` ->
`DecodeServer`) at a tiny size: prefill-then-decode LOGITS against the
plain reference's full forward pass (`benchmark/reference/mistral4.py`,
the expanded form only, which imports nothing of the program), slots
admitted at different lengths and steps; the eight shares of an
expert-parallel deployment adding up to the uncut layer; the latent
entry of `cache_spec`; what a latent row refuses by name; the manifest."""
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
from benchmark.models import mistral4_lm  # noqa: E402
from benchmark.reference import mistral4 as ref  # noqa: E402

# hidden 64, 4 heads of 8 + 8 query/key and 16 value channels, a latent
# of 24 + 8 = 32 floats a position, queries through a bottleneck of 32;
# 3 layers, each with 16 routed experts (4 a token, experts 8..11 held:
# one of four shares) and a shared one; YaRN over an original context
# of 16 positions, so the texts here pass it and the query scale turns
CFG = dict(
    model_type="mistral4", vocab_size=97, hidden_size=64,
    intermediate_size=96, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, qk_head_dim=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=32, kv_lora_rank=24,
    max_position_embeddings=4096, attention_bias=False, mlp_bias=False,
    hidden_act="silu", rms_norm_eps=1e-6, first_k_dense_replace=0,
    n_routed_experts=4, n_routed_experts_scored=16, experts_held=[8, 12],
    n_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=24,
    norm_topk_prob=True, n_group=1, topk_group=1, routed_scaling_factor=1,
    rope_interleave=True, tie_word_embeddings=False, sliding_window=None,
    rope_parameters={
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"},
    assumed={"router_score": "softmax",
             "softmax_scale": "yarn_mscale_all_dim",
             "query_scale": "llama4"},
    serve={"max_seq": 64})
SLOTS, SEQ, N_LAYER, ROW = 4, 64, 3, 32


@pytest.fixture(scope="module")
def seeded():
    specs = mistral4_lm.parameter_specs(CFG, "serve")
    return weights.seeded_weights(specs, 2 ** 31 + 7, mistral4_lm.init_rule)


@pytest.fixture(scope="module")
def pred(tmp_path_factory, seeded):
    d = str(tmp_path_factory.mktemp("mistral4_model"))
    scope = fluid.Scope()
    for n in seeded:
        scope.set_var(n, seeded[n])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        save_decode_model(d, mistral4_lm.decode_config(CFG, "serve"), exe,
                          scope=scope)
    return DecodePredictor(d)


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
PROBE_LENS = [5, 21, 40]  # below the original context of 16, and past it


@pytest.fixture(scope="module")
def probes(pred):
    prompts = _prompts(PROBE_LENS)
    forced = _prompts([K + 1] * len(prompts), seed=4)
    return prompts, forced, _rollout(pred, prompts, K, forced)


def _reference(seeded, text, rows, variant=""):
    """`rows` of the plain reference's logits over `text`, in one full
    forward pass. The reference is causal, so the text is padded to SEQ
    positions: its programs then compile once a file."""
    padded = np.zeros((SEQ,), np.int64)
    padded[:len(text)] = text
    return np.asarray(ref.serve_logits(
        seeded, jnp.asarray(padded), CFG, N_LAYER, rows=np.asarray(rows),
        variant=variant))


def _want(seeded, p, f, variant=""):
    return _reference(seeded, np.concatenate([p, f[:K]]),
                      np.arange(len(p) - 1, len(p) + K), variant)


@pytest.mark.parametrize("which", range(len(PROBE_LENS)),
                         ids=["len%d" % n for n in PROBE_LENS])
def test_prefill_then_decode_matches_the_reference(probes, seeded, which):
    """Prompts of 5, 21 and 40 tokens in buckets of 16, 32 and 64, in
    three neighbouring slots at three lengths, then 6 teacher-forced
    steps through the latent slab by the absorbed path, against the
    reference's ONE full expanded forward pass. LOGITS, tolerance 2e-4
    relative L2: float32 on the CPU on both sides; readings are ~3e-7."""
    prompts, forced, got = probes
    err = _rel(got[which], _want(seeded, prompts[which], forced[which]))
    assert err < 2e-4, err


@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v])
def test_a_reference_that_leaves_a_part_out_is_told_apart(
        probes, seeded, variant):
    """The comparison sees each mechanism: against a reference without
    the shared expert, the renormalisation, all routed experts (they
    speak a tenth as loud as the rest: `mistral4_lm.init_rule`), the
    rotation of k_r, the query scale (the 40-token text passes the
    original context of 16), the norm on c_kv, or one cache row, the
    same logits are 0.08% to 50% away, where the program is 3e-7 from
    the true reference."""
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


def test_neighbouring_slots_admitted_at_different_steps(pred, seeded):
    """Two slots, four requests: the second is admitted while the first
    is some steps into its reply, later ones reuse both slots at other
    lengths. Each answer is the reference's greedy rollout, which knows
    no slot, no slab and no last occupant: a latent row, a length or an
    expert load that leaks between neighbours fails here."""
    prompts = _prompts([30, 6, 19, 41], seed=7)
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


# -- one chip's share of eight ------------------------------------------------

def test_the_shares_routed_parts_and_the_shared_expert_add_up(seeded):
    """`ops/moe.py` with `experts_held` = each of the shares in turn
    (here four of 4 experts; the cell's are eight of 16): the routed
    parts of all shares + the shared expert counted ONCE == the uncut
    layer, by the program's ops and by the reference alike."""
    from paddle_tpu.ops import moe

    r = np.random.default_rng(5)
    d, f, n, k = 64, 24, 16, 4
    x = jnp.asarray(r.normal(size=(11, d)), jnp.float32)
    p = {"router.w": jnp.asarray(r.normal(size=(d, n)) * 0.3, jnp.float32)}
    for nm, shape in (("gate", (n, d, f)), ("up", (n, d, f)),
                      ("down", (n, f, d))):
        p["experts.%s.w" % nm] = jnp.asarray(r.normal(size=shape) * 0.1,
                                             jnp.float32)
    for nm, shape in (("gate", (d, f)), ("up", (d, f)), ("down", (f, d))):
        p["shared.%s.w" % nm] = jnp.asarray(r.normal(size=shape) * 0.1,
                                            jnp.float32)
    uncut = dict(CFG, experts_held=[0, n])
    whole = np.asarray(ref.moe(p, x, uncut, "highest"))
    idx, w = moe.moe_route(x, p["router.w"], k, 1.0, score="softmax")
    shared = moe.moe_shared(x, p["shared.gate.w"], p["shared.up.w"],
                            p["shared.down.w"])
    total, total_ref, loads = np.asarray(shared), None, 0
    for lo in range(0, n, 4):
        part, load = moe.moe_experts(
            x, idx, w, p["experts.gate.w"][lo:lo + 4],
            p["experts.up.w"][lo:lo + 4], p["experts.down.w"][lo:lo + 4],
            lo=lo)
        total = total + np.asarray(part)
        loads += int(load.sum())
        sub = dict(p, **{"experts.%s.w" % nm: p["experts.%s.w" % nm][
            lo:lo + 4] for nm in ("gate", "up", "down")})
        share = np.asarray(ref.moe(sub, x, dict(CFG, experts_held=[lo, lo + 4]),
                                   "highest", shared=(lo == 0)))
        total_ref = share if total_ref is None else total_ref + share
    assert loads == 11 * k  # every pair fell on exactly one share
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(total_ref, whole, rtol=2e-4, atol=2e-5)


def test_moe_load_counts_the_held_experts_real_tokens(pred):
    (p,) = _prompts([21], seed=5)
    pexe, _ = pred.acquire("prefill", 1, 32)
    tokens = np.zeros((1, 32), np.int64)
    tokens[0, :21] = p
    outs = pexe({"tokens": tokens, "lengths": np.array([21], np.int32)},
                pred._state)
    load = np.asarray(outs[-1])
    assert load.shape == (3, 4) and load.dtype == np.int32
    assert len(outs) == 1 + len(pred.cache_spec(1, 32)) + 1
    assert 0 < load.sum() <= 3 * 21 * 4
    # the prefill hands over the prompt's latent rows, one array a layer
    assert [tuple(np.asarray(o).shape) for o in outs[1:4]] == [
        (1, 32, ROW)] * 3


# -- the cache manager's one description, the counts ---------------------------

def test_cache_spec_has_a_latent_entry(pred):
    spec = pred.cache_spec(SLOTS, SEQ)
    assert [e.name for e in spec] == ["latent_0", "latent_1", "latent_2"]
    assert [e.name for e in spec] == sorted(e.name for e in spec)
    assert tuple(spec[1]) == ("latent_1", (SLOTS, SEQ, ROW), "float32", True)
    # a row per position that is neither K nor V: its own kind
    assert {e.kind for e in spec} == {"latent"}
    assert spec[0]._replace(shape=()).kind == "latent"
    _, feeds, fetches = pred._build("decode", SLOTS, SEQ, "greedy")
    assert feeds == ["tokens", "lengths", "seed"] + [e.name for e in spec]
    assert len(fetches) == 2 + len(spec) + 1
    # capacity: 32 floats a position a layer, where K and V of 4 heads
    # of 16 would be 128
    per_slot = 3 * SEQ * ROW * 4
    assert sum(e.nbytes for e in pred.cache_spec(1, SEQ)) == per_slot
    assert kv_slab_slots(10 * per_slot + 1, pred.config, SEQ) == 10
    assert cache_spec(pred.config, 2, 16)[0].shape == (2, 16, ROW)
    with pytest.raises(ValueError, match="latent"):
        pred.cache_spec(SLOTS, SEQ, "int8")


def test_server_books_latent_rows_and_admission_rows(pred):
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=4)
    assert srv._latent_row_bytes == ROW * 4 and srv._stream_rows is None
    counts = srv._step_counts(np.array([3, 0, 30, 0], np.int32), 2)
    assert counts == {"active": 2, "attended": 35, "streamed": SLOTS * SEQ,
                      "state_bytes": 0, "expert_pairs": 0,
                      "experts_active": 0, "latent_rows": 35,
                      "latent_row_bytes": ROW * 4}
    prompts = _prompts([20, 3], seed=11)
    sc = srv._scatter_counts(2, prompts, bucket_rows=2 * 32)
    assert sc["entries"] == 3 and sc["state_slots"] == 0
    assert (sc["prompt_rows"], sc["bucket_rows"], sc["prompts"]) == (
        23, 64, 2)
    assert sc["attn_pairs"] == 20 * 21 // 2 + 3 * 4 // 2
    assert "expert_pairs" in sc
    srv.start()
    for f in [srv.submit((p, np.array([4], np.int64))) for p in prompts]:
        f.result(timeout=300)
    srv.stop()
    assert srv.moe_load_total.shape == (3, 4)
    assert int(srv.moe_load_total.sum()) > 0


def test_traces_name_their_path(pred):
    """A prefill is traced with the expanded path, a decode step with
    the absorbed one, and neither with the other's."""
    def counts():
        return {k["path"]: v for k, v in obs.MLA_TRACES.samples()}

    before = counts()
    pred._step("prefill", 1, 16, "greedy").fn(
        {"tokens": np.zeros((1, 16), np.int64),
         "lengths": np.ones((1,), np.int32)}, pred._state)
    mid = counts()
    assert mid["expanded"] - before.get("expanded", 0) == N_LAYER
    assert mid.get("absorbed", 0) == before.get("absorbed", 0)
    step = pred._step("decode", 2, 16, "greedy")
    feeds = {"tokens": np.zeros((2, 1), np.int64),
             "lengths": np.ones((2,), np.int32),
             "seed": np.zeros((1,), np.int64)}
    feeds.update({e.name: np.zeros(e.shape, np.float32)
                  for e in pred.cache_spec(2, 16)})
    step.fn(feeds, pred._state)
    after = counts()
    assert after["absorbed"] - mid.get("absorbed", 0) == N_LAYER
    assert after["expanded"] == mid["expanded"]


# -- what a latent row refuses, by name ----------------------------------------

@pytest.mark.parametrize("kwargs", [
    {"speculative": True}, {"prefix_cache": True},
    {"prefix_store": object()}, {"kv_dtype": "int8"}],
    ids=["speculative", "prefix_cache", "prefix_store", "int8"])
def test_server_refuses_what_is_not_built_over_latent_rows(pred, kwargs):
    with pytest.raises(ValueError, match="one row of 32 floats a position "
                                         ".*neither K nor V"):
        DecodeServer(pred, slots=2, max_seq=SEQ, **kwargs)


@pytest.mark.parametrize("call", ["generate_speculative", "generate_beam"])
def test_predictor_refuses_what_is_not_built_over_latent_rows(pred, call):
    with pytest.raises(ValueError, match="kind 'latent'"):
        getattr(pred, call)(_prompts([5]), max_new_tokens=4)


# -- the manifest ---------------------------------------------------------------

def test_manifest_round_trip_and_one_w_kvb(pred, seeded):
    cfg = mistral4_lm.decode_config(CFG, "serve")
    d = json.loads(json.dumps(cfg.to_dict()))
    again = DecodeConfig.from_dict(d)
    assert again.to_dict() == cfg.to_dict() == pred.config.to_dict()
    assert again.layer_kinds() == ["latent"] * 3 and again.has_latent
    assert again.ffn_kinds() == ["experts"] * 3
    assert not (again.has_ring or again.has_state or again.is_opt_block)
    assert again.latent_row == ROW and again.held == (8, 12)
    assert again.router_score == "softmax"
    rot = again.rope["latent"]
    assert rot["interleave"] and rot["scale_beta"] == 0.1
    assert rot["yarn"]["original_max_position"] == 16
    want = 16 ** -0.5 * (0.1 * np.log(128.0) + 1) ** 2
    assert abs(again.softmax_scale - want) < 1e-9
    # the five widths are written only where set
    assert {"q_lora_rank", "kv_lora_rank", "qk_nope_dim", "qk_rope_dim",
            "v_head_dim", "softmax_scale"} <= set(d)
    plain = DecodeConfig(97, n_layer=1, n_head=4, d_model=64, n_kv_head=2,
                         norm="rms_norm", ffn="gated_silu", positions=False,
                         biases=False)
    assert not set(plain.to_dict()) & set(DecodeConfig.LATENT_WIDTHS)
    # the exported model holds W_kvb ONCE a layer: W^K_h and W^V_h are
    # views of it inside the decode step
    kvb = [n for n in seeded if "kv_b" in n]
    assert kvb == ["lm.l%d.attention.kv_b.w" % i for i in range(3)]
    assert tuple(seeded[kvb[0]].shape) == (24, 4 * (8 + 16))
    assert sorted(n for n in pred._state if ".attention." in n) == sorted(
        "lm.l%d.attention.%s.w" % (i, nm) for i in range(3)
        for nm in ("q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o"))


@pytest.mark.parametrize("bad,match", [
    (dict(layer_types=["latent"]), "a latent layer needs kv_lora_rank"),
    (dict(layer_types=["latent"], q_lora_rank=8, kv_lora_rank=8,
          qk_nope_dim=4, qk_rope_dim=4), "a latent layer needs"),
])
def test_config_refuses_a_latent_layer_without_its_widths(bad, match):
    with pytest.raises(ValueError, match=match):
        DecodeConfig(97, n_layer=1, n_head=4, d_model=64, **bad)


def test_builders_refuse_what_no_latent_graph_computes():
    from paddle_tpu.models import jamba

    base = dict(n_layer=1, n_head=4, d_model=64, norm="rms_norm",
                ffn="gated_silu", positions=False, biases=False,
                layer_types=["latent"], q_lora_rank=8, kv_lora_rank=8,
                qk_nope_dim=4, qk_rope_dim=4, v_head_dim=8)
    jamba._check(DecodeConfig(97, **base))
    yarn = {"factor": 8, "original_max_position": 16}
    jamba._check(DecodeConfig(97, rope={"latent": {
        "theta": 1e4, "yarn": yarn, "scale_beta": 0.1,
        "interleave": True}}, **base))
    for bad, match in [
            (dict(base, rope={"latent": {"rotary_dim": 4}}),
             "a latent layer's rotation"),
            (dict(base, rope={"latent": {"scale_beta": 0.1}}),
             "a latent layer's rotation"),
            (dict(base, attn_gate="per_channel"), "only a sigmoid gate"),
            (dict(base, attn_biases=True), "without differential")]:
        with pytest.raises(ValueError, match=match):
            jamba._check(DecodeConfig(97, **bad))
    with pytest.raises(ValueError, match="query_scale 'ntk' is not built"):
        mistral4_lm.rope_of(dict(CFG, assumed=dict(
            CFG["assumed"], query_scale="ntk")))
    with pytest.raises(ValueError, match="softmax_scale 'x' is not built"):
        mistral4_lm.softmax_scale(dict(CFG, assumed=dict(
            CFG["assumed"], softmax_scale="x")))
    with pytest.raises(ValueError, match="sigmoid or softmax scores"):
        jamba._check(mistral4_lm.decode_config(dict(CFG, assumed=dict(
            CFG["assumed"], router_score="tanh")), "serve"))
