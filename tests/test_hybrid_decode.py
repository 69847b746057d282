"""A hybrid state-space / attention LM through the normal serving path
(`save_decode_model` -> `DecodePredictor` -> `DecodeServer`) at a tiny
size: prefill-then-decode logits against the plain reference
(`benchmark/reference/jamba.py`, which imports nothing of the
program), a padded prompt and a reused slot included; the cache
manager's one description (`cache_spec`); what a recurrent state
refuses; and that OPT's block is served by the very programs it was
served by before (manifests, fingerprints)."""
import json
import os
import sys

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
from benchmark.models import jamba_lm  # noqa: E402
from benchmark.reference import jamba as ref  # noqa: E402

# hidden 64, 4 query heads on 1 K/V head of 16, d_inner 128, N 16, R 4,
# K 4, 4 layers: mamba, mamba, attention, mamba
CFG = dict(model_type="jamba", hidden_act="silu", num_experts=1,
           tie_word_embeddings=True, mamba_conv_bias=True,
           mamba_proj_bias=False, vocab_size=97, num_hidden_layers=4,
           num_attention_heads=4, num_key_value_heads=1, hidden_size=64,
           intermediate_size=96, attn_layer_period=4, attn_layer_offset=2,
           mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=4,
           mamba_expand=2, rms_norm_eps=1e-6, serve={"max_seq": 64})
SLOTS, SEQ = 4, 64


def seeded_weights():
    specs = jamba_lm.parameter_specs(CFG, "serve")
    return weights.seeded_weights(specs, 2 ** 31 + 11, jamba_lm.init_rule)


@pytest.fixture(scope="module")
def seeded():
    return seeded_weights()


def export_hybrid(d, seeded):
    """The tiny hybrid model, exported for decode serving into ``d``
    (tests/test_decode_serving.py serves it too)."""
    scope = fluid.Scope()
    for n in seeded:
        scope.set_var(n, seeded[n])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        save_decode_model(d, jamba_lm.decode_config(CFG, "serve"), exe,
                          scope=scope)
    return d


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory, seeded):
    return export_hybrid(str(tmp_path_factory.mktemp("hybrid_model")),
                         seeded)


@pytest.fixture(scope="module")
def pred(model_dir):
    return DecodePredictor(model_dir)


def _prompts(lens, seed=3):
    r = np.random.default_rng(seed)
    return [r.integers(1, CFG["vocab_size"], n, dtype=np.int64)
            for n in lens]


def _rollout(pred, prompts, steps, forced):
    """The benchmark runner's own rollout (prefill at each prompt's
    bucket, then the (SLOTS, SEQ) decode step, teacher-forced; caches
    as `cache_spec` describes them): the logits of the last prompt
    position and of each decoded one."""
    from benchmark.lib import run_serveany

    rows, _ = run_serveany._direct_rollout(pred, prompts, steps, SLOTS,
                                           SEQ, forced=forced)
    return [np.stack(r) for r in rows]


def _reference(seeded, text, rows):
    """`rows` of the plain reference's logits over `text`, padded to SEQ
    positions: see `tests/test_laguna_decode.py::_reference`."""
    padded = np.zeros((SEQ,), np.int64)
    padded[:len(text)] = text
    return np.asarray(ref.serve_logits(
        seeded, jnp.asarray(padded), CFG, CFG["num_hidden_layers"],
        rows=np.asarray(rows)))


def test_prefill_then_decode_matches_the_reference(pred, seeded):
    """Prompts of 5, 21 and 40 tokens in buckets of 16, 32 and 64 (none
    fills its bucket: padding that advanced a state, or a window taken
    at the bucket's end, would fail the first decoded position), then 6
    teacher-forced steps through the caches. Tolerance 2e-4 relative
    L2: float32 on the CPU on both sides, the orders of summation
    differ (the program's scan carries (N, d_inner), the reference's
    (d_inner, N); XLA's dot against `highest`); readings are ~1e-6."""
    k = 6
    prompts = _prompts([5, 21, 40])
    forced = _prompts([k + 1] * 3, seed=4)
    got = _rollout(pred, prompts, k, forced)
    for p, f, g in zip(prompts, forced, got):
        want = _reference(seeded, np.concatenate([p, f[:k]]),
                          np.arange(len(p) - 1, len(p) + k))
        err = (np.linalg.norm(g - want) / np.linalg.norm(want))
        assert err < 2e-4, (len(p), err)


def _greedy_reference(seeded, prompt, n):
    seq = list(prompt)
    out = []
    for _ in range(n):
        lg = _reference(seeded, seq, [len(seq) - 1])
        out.append(int(lg[0].argmax()))
        seq.append(out[-1])
    return out


def test_generate_is_the_reference_greedy_rollout(pred, seeded):
    """The static-batch surface: three prompts padded into one batch of
    4 x 32, states padded past the prompts' own bucket."""
    prompts = _prompts([5, 11, 17])
    outs = pred.generate(prompts, max_new_tokens=5)
    assert [o.tolist() for o in outs] == [
        _greedy_reference(seeded, p, 5) for p in prompts]


def test_server_with_more_requests_than_slots(pred, seeded):
    """Nine requests on two slots: every slot is reused several times,
    prompts of unequal length share admissions, and each answer is the
    greedy rollout of the plain reference's full forward pass, which
    knows no slot, no padding and no last occupant. (Greedy tokens of
    random weights are compared here because the gaps between the two
    largest logits of these seeds are far above the 1e-6 agreement.)"""
    prompts = _prompts([5, 9, 14, 3, 21, 7, 12, 30, 4], seed=7)
    srv = DecodeServer(pred, slots=2, max_seq=SEQ, max_new_tokens=4)
    futs = [srv.submit((p, np.array([4], np.int64))) for p in prompts]
    srv.start()
    got = [np.asarray(f.result(timeout=300)[0]).tolist() for f in futs]
    srv.stop()
    assert got == [_greedy_reference(seeded, p, 4) for p in prompts]


def test_reused_slot_equals_a_fresh_one(pred):
    """A long sequence, then a short one in the same (only) slot: the
    short one's answer is what a fresh server gives it. Admission must
    replace the whole state: no length masks a slot's last occupant."""
    long_p, short_p = _prompts([40, 6], seed=9)
    opts = np.array([6], np.int64)
    srv = DecodeServer(pred, slots=1, max_seq=SEQ, max_new_tokens=6)
    srv.start()
    srv.submit((long_p, opts)).result(timeout=300)
    reused = np.asarray(srv.submit((short_p, opts)).result(timeout=300)[0])
    srv.stop()
    fresh_srv = DecodeServer(pred, slots=1, max_seq=SEQ, max_new_tokens=6)
    fresh_srv.start()
    fresh = np.asarray(fresh_srv.submit((short_p, opts)).result(
        timeout=300)[0])
    fresh_srv.stop()
    np.testing.assert_array_equal(reused, fresh)


def test_scatter_and_step_counts_carry_the_state(pred):
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ)
    per_slot = 3 * ((4 - 1) * 128 + 128 * 16) * 4  # 3 mamba layers
    assert srv._state_bytes_per_slot == per_slot
    counts = srv._step_counts(np.array([3, 0, 9, 0], np.int32), 2)
    assert counts == {"active": 2, "attended": 14,
                      "streamed": SLOTS * SEQ,  # the lax path: whole slab
                      "state_bytes": 2 * SLOTS * per_slot}
    assert srv._scatter_counts(3) == {"entries": 8, "state_slots": 3,
                                      "ssm_tokens": 0, "ssm_pad_tokens": 0}
    # the real rows each selective scan walks, and the bucket's beyond
    prompts = [np.arange(n, dtype=np.int64) % 7 for n in (20, 3, 9)]
    sc = srv._scatter_counts(3, prompts, bucket_rows=4 * 32)
    assert (sc["ssm_tokens"], sc["ssm_pad_tokens"]) == (32, 96)


# -- the cache manager's one description -------------------------------------

def test_cache_spec_of_the_hybrid_model(pred):
    spec = pred.cache_spec(SLOTS, SEQ)
    assert [e.name for e in spec] == sorted(e.name for e in spec)
    by = {e.name: e for e in spec}
    assert set(by) == {"conv_0", "ssm_0", "conv_1", "ssm_1", "kcache_2",
                       "vcache_2", "conv_3", "ssm_3"}
    assert by["conv_0"][1:] == ((SLOTS, 3, 128), "float32", False)
    assert by["ssm_3"][1:] == ((SLOTS, 128, 16), "float32", False)
    assert by["kcache_2"][1:] == ((SLOTS, SEQ, 1, 16), "float32", True)
    # the decode program feeds and fetches them in this very order
    _, feeds, fetches = pred._build("decode", SLOTS, SEQ, "greedy")
    assert feeds == ["tokens", "lengths", "seed"] + [e.name for e in spec]
    assert len(fetches) == 2 + len(spec)
    # capacity counts both kinds: 3 states and windows, 2 slabs
    per_slot = 3 * (3 * 128 + 128 * 16) * 4 + 2 * SEQ * 16 * 4
    assert kv_slab_slots(10 * per_slot + 1, pred.config, SEQ) == 10
    with pytest.raises(ValueError, match="OPT's block only"):
        pred.cache_spec(SLOTS, SEQ, "int8")


OPT = DecodeConfig(vocab_size=37, n_layer=2, n_head=2, d_model=16,
                   d_inner=32, max_len=64)


@pytest.mark.parametrize("kv_dtype,names,shapes", [
    ("float32", ["kcache_0", "vcache_0", "kcache_1", "vcache_1"],
     [(4, 32, 2, 8)] * 4),
    ("int8", ["kcache_0", "vcache_0", "kscale_0", "vscale_0",
              "kcache_1", "vcache_1", "kscale_1", "vscale_1"],
     [(4, 32, 2, 8), (4, 32, 2, 8), (4, 32), (4, 32)] * 2),
])
def test_cache_spec_of_opt_is_what_the_server_always_built(
        kv_dtype, names, shapes):
    spec = cache_spec(OPT, 4, 32, kv_dtype)
    assert [e.name for e in spec] == names
    assert [e.shape for e in spec] == shapes
    assert all(e.per_position for e in spec)
    assert {e.dtype for e in spec if "cache" in e.name} == {kv_dtype}
    # the capacity arithmetic the int8 slab was sold on is unchanged
    per_pos = 2 * 8 * (1 if kv_dtype == "int8" else 4) + (
        4 if kv_dtype == "int8" else 0)
    assert kv_slab_slots(7 * 2 * 2 * 32 * per_pos, OPT, 32, kv_dtype) == 7


# -- what a recurrent state refuses ------------------------------------------

@pytest.mark.parametrize("kwargs", [
    {"speculative": True}, {"prefix_cache": True},
    {"prefix_store": object()}, {"kv_dtype": "int8"}],
    ids=["speculative", "prefix_cache", "prefix_store", "int8"])
def test_server_refuses_what_a_state_cannot_do(pred, kwargs):
    with pytest.raises(ValueError, match="recurrent state"):
        DecodeServer(pred, slots=2, max_seq=SEQ, **kwargs)


@pytest.mark.parametrize("call", ["generate_speculative", "generate_beam"])
def test_predictor_refuses_what_a_state_cannot_do(pred, call):
    with pytest.raises(ValueError, match="recurrent state"):
        getattr(pred, call)(_prompts([5]), max_new_tokens=4)


# -- OPT's block is served as it was -----------------------------------------

PRE_PR_MANIFEST = {"d_inner": 32, "d_model": 16, "eos_id": None,
                   "max_len": 64, "n_head": 2, "n_layer": 2, "prefix": "lm",
                   "tie_embeddings": False, "vocab_size": 37}


def test_pre_pr_manifest_loads_as_what_it_was():
    cfg = DecodeConfig.from_dict(PRE_PR_MANIFEST)
    assert cfg.is_opt_block and not cfg.has_state
    assert cfg.layer_kinds() == ["attention", "attention"]
    assert cfg.n_kv_head == 2 and cfg.positions and cfg.biases
    assert cfg.to_dict() == PRE_PR_MANIFEST  # and is written as it was
    hybrid = jamba_lm.decode_config(CFG, "serve")
    again = DecodeConfig.from_dict(json.loads(json.dumps(hybrid.to_dict())))
    assert again.to_dict() == hybrid.to_dict()
    assert again.layer_kinds() == ["mamba", "mamba", "attention", "mamba"]
    assert again.has_state and not again.is_opt_block


# content fingerprints of the tiny OPT programs at (4, 32), read on the
# parent commit (6c6bb68): the AOT keys of the cells that stand
PARENT_FP = {"prefill": "e9d7a333", "decode": "0dc064fa",
             "draft": "f1fbc2eb", "verify": "849a33e4",
             "decode_kv8": "3abf9b2b", "decode_topk": "cd94d347"}


@pytest.mark.parametrize("case", sorted(PARENT_FP))
def test_opt_programs_keep_their_fingerprints(case):
    p = DecodePredictor.__new__(DecodePredictor)  # graph builder only
    p.config = DecodeConfig.from_dict(PRE_PR_MANIFEST)
    p.sample_k, p.sample_p, p.temperature = 40, 0.9, 1.0
    p.draft_n_layer = 1
    kind = case.split("_")[0]
    kw = {"window": 5} if kind == "verify" else {}
    if case == "decode_kv8":
        kw["kv_dtype"] = "int8"
    prog, feeds, fetches = p._build(
        kind, 4, 32, "topk" if case == "decode_topk" else "greedy", **kw)
    assert obs.program_fp(prog) == PARENT_FP[case]
    if kind == "decode":
        assert feeds[:4] == ["tokens", "positions", "lengths", "seed"]
