"""A Laguna-family LM (full and sliding-window attention layers mixed,
query heads by layer, rotary positions of two kinds, a per-head output
gate, a dense layer and then routed experts with a shared one, an
untied head) through the normal serving path (`save_decode_model` ->
`DecodePredictor` -> `DecodeServer`) at a tiny size: prefill-then-decode
logits against the plain reference (`benchmark/reference/laguna.py`,
which imports nothing of the program) on prompts shorter and longer
than the window; sequences admitted at different steps into
neighbouring slots; the ring entry of `cache_spec`; what a ring
refuses; the manifest; and that the parent's OPT and hybrid manifests
still build the parent's programs."""
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
from benchmark.models import laguna_lm  # noqa: E402
from benchmark.reference import laguna as ref  # noqa: E402

# hidden 64, 2 K/V heads of 16 under 4 (full) or 6 (sliding) query
# heads, 6 layers: full + dense MLP, then sliding x3, full, sliding with
# 16 routed experts (4 a token, experts 4..7 held) and a shared one;
# window 8; YaRN over half a head on the full layers, plain over the
# whole head on the sliding ones
CFG = dict(
    model_type="laguna", vocab_size=97, hidden_size=64,
    intermediate_size=96, num_hidden_layers=6, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, max_position_embeddings=4096,
    attention_bias=False, rms_norm_eps=1e-6, num_experts=4,
    num_experts_routed=16, experts_held=[4, 8], num_experts_per_tok=4,
    moe_intermediate_size=24, shared_expert_intermediate_size=24,
    tie_word_embeddings=False, gating=True, sliding_window=8,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention", "sliding_attention"],
    moe_apply_router_weight_on_input=False,
    mlp_layer_types=["dense"] + ["sparse"] * 5,
    moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[4, 6, 6, 6, 4, 6],
    model={"router_score": "sigmoid", "attention_gate": "per_head"},
    serve={"max_seq": 64})
SLOTS, SEQ, WINDOW, N_LAYER = 4, 64, 8, 6


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


@pytest.fixture(scope="module")
def seeded():
    specs = laguna_lm.parameter_specs(CFG, "serve")
    return weights.seeded_weights(specs, 2 ** 31 + 5, laguna_lm.init_rule)


@pytest.fixture(scope="module")
def pred(tmp_path_factory, seeded):
    d = str(tmp_path_factory.mktemp("laguna_model"))
    scope = fluid.Scope()
    for n in seeded:
        scope.set_var(n, seeded[n])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        save_decode_model(d, laguna_lm.decode_config(CFG, "serve"), exe,
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
PROBE_LENS = [5, 21, 40]  # under the window, and wrapping it 2 and 5 times


@pytest.fixture(scope="module")
def probes(pred):
    prompts = _prompts(PROBE_LENS)
    forced = _prompts([K + 1] * len(prompts), seed=4)
    return prompts, forced, _rollout(pred, prompts, K, forced)


def _reference(seeded, text, rows, variant=""):
    """`rows` of the plain reference's logits over `text`, in one full
    forward pass. The reference is causal (a row sees nothing after it),
    so the text is padded to SEQ positions: its per-layer programs then
    compile once a file, not once a new length (seconds each)."""
    padded = np.zeros((SEQ,), np.int64)
    padded[:len(text)] = text
    return np.asarray(ref.serve_logits(
        seeded, jnp.asarray(padded), CFG, N_LAYER, rows=np.asarray(rows),
        variant=variant))


def _want(seeded, p, f, variant=""):
    return _reference(seeded, np.concatenate([p, f[:K]]),
                      np.arange(len(p) - 1, len(p) + K), variant)


@_cases("which", range(len(PROBE_LENS)),
                         ids=["len%d" % n for n in PROBE_LENS])
def test_prefill_then_decode_matches_the_reference(probes, seeded, which):
    """Prompts of 5, 21 and 40 tokens in buckets of 16, 32 and 64 (none
    fills its bucket; 5 never wraps the ring of 8 rows, 21 and 40 do),
    then 6 teacher-forced steps through slabs and rings. Tolerance 2e-4
    relative L2: float32 on the CPU on both sides; readings are ~3e-7."""
    prompts, forced, got = probes
    err = _rel(got[which], _want(seeded, prompts[which], forced[which]))
    assert err < 2e-4, err


@_cases("variant", ["no_shared", "no_gate", "no_renorm",
                                     "ring_row_short"])
def test_a_reference_that_leaves_a_part_out_is_told_apart(
        probes, seeded, variant):
    """The comparison sees each mechanism: against a reference without
    the shared expert, the gate, the renormalisation or one ring row
    the same logits are 0.9% (the renormalisation: the routed experts
    speak a tenth as loud as the rest, `laguna_lm.init_rule`) to 70%
    away, where the program is 3e-7 from the true reference."""
    prompts, forced, got = probes
    err = _rel(got[2], _want(seeded, prompts[2], forced[2], variant))
    assert err > 0.005, (variant, err)


def _is_greedy(seeded, prompt, generated):
    """Each generated token is the argmax of the reference's logits
    over what came before it (one full forward over the whole text)."""
    full = np.concatenate([prompt, generated])
    lg = _reference(seeded, full, np.arange(len(prompt) - 1, len(full) - 1))
    return lg.argmax(-1).tolist() == list(generated)


def test_generate_is_the_reference_greedy_rollout(pred, seeded):
    """The static-batch surface: prompts padded into one batch of
    2 x 32, rings and slabs padded past the prompts' own bucket."""
    prompts = _prompts([5, 17])
    outs = pred.generate(prompts, max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)
    assert all(_is_greedy(seeded, p, o) for p, o in zip(prompts, outs))


def test_neighbouring_slots_admitted_at_different_steps(pred, seeded):
    """Two slots, four requests: the second is admitted while the first
    is some steps into its reply, later ones reuse both slots, prompts
    shorter and longer than the window. Each answer is the reference's
    greedy rollout, which knows no slot, no ring and no last occupant:
    a ring row, a length or an expert load that leaks between
    neighbours fails here. (The texts are of four lengths only: the
    plain reference compiles for every new one.)"""
    prompts = _prompts([30, 6, 19, 41], seed=7)
    news = [7, 9, 7, 7]  # 37 / 15 / 26 / 48 tokens of text
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


def test_moe_load_counts_real_tokens_only(pred, seeded):
    """A prefill's last output: pairs each held expert received, layer
    by sparse layer, from the prompt's 21 real tokens (the 11 rows of
    padding route nowhere). Against the reference's own routing."""
    (p,) = _prompts([21], seed=5)
    pexe, names = pred.acquire("prefill", 1, 32)
    tokens = np.zeros((1, 32), np.int64)
    tokens[0, :21] = p
    outs = pexe({"tokens": tokens, "lengths": np.array([21], np.int32)},
                pred._state)
    load = np.asarray(outs[-1])
    assert load.shape == (5, 4) and load.dtype == np.int32
    assert len(outs) == 1 + len(pred.cache_spec(1, 32)) + 1
    # layer 1's routing from the reference: its input is layer 0's output
    eps = CFG["rms_norm_eps"]
    h = seeded["lm.tok_emb"][jnp.asarray(p)]
    sub = {n[len("lm.l0."):]: v for n, v in seeded.items()
           if n.startswith("lm.l0.")}
    u = ref._rms(h, sub["norm_in.w"], eps)
    h = h + ref.attention(ref._sub(sub, "attention."), u, 0, CFG, "highest")
    u = ref._rms(h, sub["norm_ff.w"], eps)
    h = h + ref.gated_mlp(u, sub["mlp.gate.w"], sub["mlp.up.w"],
                          sub["mlp.down.w"], "highest")
    l1 = {n[len("lm.l1."):]: v for n, v in seeded.items()
          if n.startswith("lm.l1.")}
    h = h + ref.attention(ref._sub(l1, "attention."),
                          ref._rms(h, l1["norm_in.w"], eps), 1, CFG,
                          "highest")
    idx, _ = ref.route(ref._rms(h, l1["norm_ff.w"], eps),
                       l1["moe.router.w"], CFG)
    want = [int((np.asarray(idx) == e).sum()) for e in range(4, 8)]
    assert load[0].tolist() == want
    assert 0 < load.sum() <= 5 * 21 * 4


def test_server_books_loads_and_ring_rows(pred):
    """The counts of a step and of an admission, the running total, the
    counter and the gauge a layer."""
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=4)
    assert srv._state_bytes_per_slot == 0 and srv._ring_window == WINDOW
    assert srv._moe_layers == [1, 2, 3, 4, 5]
    counts = srv._step_counts(np.array([3, 0, 30, 0], np.int32), 2)
    assert counts == {"active": 2, "attended": 35, "streamed": SLOTS * SEQ,
                      "state_bytes": 0, "ring_rows": 4 + 8,
                      "expert_pairs": 0, "experts_active": 0}
    before = sum(v for k, v in obs.MOE_EXPERT_PAIRS.samples())
    srv.start()
    prompts = _prompts([20, 3], seed=11)
    for f in [srv.submit((p, np.array([4], np.int64))) for p in prompts]:
        f.result(timeout=300)
    srv.stop()
    total = int(srv.moe_load_total.sum())
    assert srv.moe_load_total.shape == (5, 4) and total > 0
    assert sum(v for k, v in obs.MOE_EXPERT_PAIRS.samples()) - before \
        == total
    gauge = {k["layer"]: v for k, v in obs.MOE_LOAD_MAX_OVER_MEAN.samples()}
    assert set(gauge) >= set("12345") and all(
        v >= 1.0 for v in gauge.values())
    sc = srv._scatter_counts(2, prompts)
    assert sc["entries"] == 12 and sc["state_slots"] == 0
    assert sc["ring_rows"] == 8 + 3 and "expert_pairs" in sc


def test_server_asks_stream_rows_with_the_slabs_own_heads(pred, monkeypatch):
    """`streamed` of a grouped configuration follows the kernel's block
    over the slab's key/value heads under the full layers' query
    heads."""
    from paddle_tpu.serving import decode as D

    asked = []

    def rows(view):
        asked.append((view.seq, view.k_block, view.score_rows))
        return 16

    monkeypatch.setattr(D._KV, "decode_stream_rows", rows)
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=4)
    # the two full layers' 4 query heads, not the sliding layers' 6
    assert asked == [(SEQ, (1, 1, CFG["num_key_value_heads"],
                            CFG["head_dim"]), 4)]
    counts = srv._step_counts(np.array([3, 0, 30, 0], np.int32), 2)
    assert counts["streamed"] == 16 * (1 + 1 + 2 + 1) < SLOTS * SEQ
    assert counts["attended"] == 35


# -- an admission is bounded by tokens as well as by prompts ------------------

def _waiting(lens):
    return [(i, np.zeros((n,), np.int64), 4, None)
            for i, n in enumerate(lens)]


@_cases("lens,free,want", [
    ([2048] * 12, 12, 8),      # 8 x 2048 = 16,384: what stood, stands
    ([3000] * 12, 12, 4),      # 4 x 4096
    # the 100s ran in the 3000's program, 4 x 4096 rows for 3,300, until
    # an admission took one length bucket: now the 3000 goes alone
    ([3000, 100, 100, 100, 100, 100, 100, 100], 8, 1),
    ([100] * 5, 3, 3), ([4000], 8, 1)])
def test_admit_room_bounds_bucketed_tokens(pred, lens, free, want):
    srv = DecodeServer(pred, slots=16, max_seq=SEQ)
    srv.seq = 4096  # the bound reads the server's slab length only
    pending = _waiting(lens)
    assert srv._admit_group(free, pending) == list(range(want))
    assert srv._admit_room(free) == min(free, 8)  # the most, unasked
    srv.continuous = False
    assert srv._admit_room(free) == free
    assert srv._admit_group(free, pending) == list(
        range(min(free, len(lens))))


# -- an admission takes one length bucket --------------------------------------

@_cases("lens,free,seq,want", [
    ([600, 900], 8, 4096, [0, 1]),              # one bucket: together
    ([300, 1100], 8, 4096, [0]),                # 512 + 2048 rows, not 4096
    ([1100, 300], 8, 4096, [0]),
    ([20, 300, 100, 500], 8, 4096, [0, 1, 2, 3]),  # under the floor
    ([100, 3000, 200, 513], 8, 4096, [0, 2]),   # the larger waits
    ([3000, 100, 4000, 100], 8, 4096, [0, 2]),  # the smaller too
    ([1500, 1500, 100, 1500], 2, 4096, [0, 1]),  # of the next free - 1
    ([3000] * 12, 12, 4096, [0, 1, 2, 3]),      # the bound cuts a group
    ([3000, 100, 2100, 100, 2500, 100, 4000, 3000], 8, 4096, [0, 2, 4, 6]),
    ([9000, 9000, 9000], 8, 16384, [0]),        # 1 x 16384
    ([5000, 100, 5000, 6000], 8, 16384, [0, 2]),  # 2 x 8192
    ([3000, 1100, 300], 8, 1024, [0, 1]),       # buckets end at the slab
    ([300, 20, 40], 8, 64, [0, 1, 2]),          # a slab under the floor
    # a batch is padded to a power of two only under the floor's rows
    ([700] * 3, 8, 4096, [0, 1]), ([700] * 7, 8, 4096, [0, 1, 2, 3]),
    ([700] * 8, 8, 4096, list(range(8))),
    ([200] * 3, 8, 4096, [0, 1]),               # 4 x 256 rows
    ([100] * 3, 8, 4096, [0, 1, 2]),            # 4 x 128: free
    ([100] * 5, 8, 4096, [0, 1, 2, 3]), ([60] * 5, 8, 4096, list(range(5))),
    ([100, 3000, 200, 513, 512], 8, 4096, [0, 2])],
    ids=["same", "300-1100", "1100-300", "under-512", "larger-waits",
         "smaller-waits", "room", "tokens", "tokens-skipping",
         "16384", "8192", "slab-cap", "short-slab", "3-as-2", "7-as-4",
         "8", "3x256-as-2", "3x128", "5x128-as-4", "5x64", "3-of-5-as-2"])
def test_an_admission_takes_the_oldest_and_its_buckets_neighbours(
        pred, lens, free, seq, want):
    """The choice as a function of (lengths waiting, free slots, slab
    length): the oldest and, of the next `room - 1`, those of its
    bucket; under the floor of 512 rows every bucket shares; and past
    it 3 (5, 6, 7) run as 2 (4)."""
    srv = DecodeServer(pred, slots=16, max_seq=SEQ)
    srv.seq = seq
    pending = _waiting(lens)
    got = srv._admit_group(free, pending)
    assert got == want and got[0] == 0   # the oldest always goes
    assert len(pending) == len(lens)     # a choice: nothing is taken here
    srv.continuous = False               # gang scheduling: the head
    assert srv._admit_group(free, pending) == list(
        range(min(free, len(lens))))


def test_a_request_is_passed_over_at_most_as_often_as_it_had_elders(pred):
    """Replay of the rule alone on a queue that refills: each
    iteration's group leaves the rest in place, and request i is taken
    by iteration i at the latest."""
    srv = DecodeServer(pred, slots=16, max_seq=SEQ)
    srv.seq = 4096
    r = np.random.RandomState(5)
    lens = [int(n) for n in np.exp(r.normal(np.log(700), 1.0, 64)).clip(
        16, 3500)]
    pending, taken_at = _waiting(lens[:8]), {}
    arrivals = iter(_waiting(lens)[8:])
    for it in range(len(lens)):
        if not pending:
            break
        take = srv._admit_group(4, pending)
        assert take[0] == 0 and take == sorted(set(take))
        for i in take:
            taken_at[pending[i][0]] = it
        left = [p for i, p in enumerate(pending) if i not in take]
        assert [p[0] for p in left] == sorted(p[0] for p in left)
        pending = left + [p for _, p in zip(take, arrivals)]
    assert sorted(taken_at) == list(range(len(lens)))
    assert all(it <= rid for rid, it in taken_at.items())


# -- the cache manager's one description --------------------------------------

def test_cache_spec_has_a_ring_entry(pred):
    spec = pred.cache_spec(SLOTS, SEQ)
    assert [e.name for e in spec] == sorted(e.name for e in spec)
    by = {e.name: e for e in spec}
    assert set(by) == {"%s%s_%d" % (kv, "cache" if i in (0, 4) else "ring",
                                    i) for kv in "kv" for i in range(6)}
    assert tuple(by["kcache_4"]) == ("kcache_4", (SLOTS, SEQ, 2, 16),
                                     "float32", True)
    assert by["kcache_4"].kind == "rows"
    # a ring is `window` rows whatever the slab's length, replaced whole
    # at an admission (the prefill hands it over as it is stored)
    assert tuple(by["vring_3"]) == ("vring_3", (SLOTS, WINDOW, 2, 16),
                                    "float32", False)
    # the kind is read from the entry's own fields: a copy keeps it
    assert by["vring_3"]._replace(shape=()).kind == "ring"
    assert pred.cache_spec(SLOTS, 32)[2].shape == (SLOTS, WINDOW, 2, 16)
    # the decode program feeds and fetches them in this very order, and
    # returns the experts' loads after them
    _, feeds, fetches = pred._build("decode", SLOTS, SEQ, "greedy")
    assert feeds == ["tokens", "lengths", "seed"] + [e.name for e in spec]
    assert len(fetches) == 2 + len(spec) + 1
    # capacity: 2 full layers of SEQ rows and 4 rings of WINDOW rows
    row = 2 * 16 * 4  # one position of K or V
    per_slot = 2 * (2 * SEQ + 4 * WINDOW) * row
    assert sum(e.nbytes for e in pred.cache_spec(1, SEQ)) == per_slot
    assert kv_slab_slots(10 * per_slot + 1, pred.config, SEQ) == 10
    # without rings every layer would hold SEQ rows
    assert per_slot < 2 * 6 * SEQ * row
    with pytest.raises(ValueError, match="ring, rows"):
        pred.cache_spec(SLOTS, SEQ, "int8")


@_cases("kwargs", [
    {"speculative": True}, {"prefix_cache": True},
    {"prefix_store": object()}, {"kv_dtype": "int8"}],
    ids=["speculative", "prefix_cache", "prefix_store", "int8"])
def test_server_refuses_what_a_ring_cannot_do(pred, kwargs):
    with pytest.raises(ValueError, match="ring of 8 rows.*no rows to roll"):
        DecodeServer(pred, slots=2, max_seq=SEQ, **kwargs)


@_cases("call", ["generate_speculative", "generate_beam"])
def test_predictor_refuses_what_a_ring_cannot_do(pred, call):
    with pytest.raises(ValueError, match="kind 'ring'"):
        getattr(pred, call)(_prompts([5]), max_new_tokens=4)


# -- the manifest --------------------------------------------------------------

def test_manifest_round_trip(pred):
    cfg = laguna_lm.decode_config(CFG, "serve")
    d = json.loads(json.dumps(cfg.to_dict()))
    again = DecodeConfig.from_dict(d)
    assert again.to_dict() == cfg.to_dict()
    assert again.layer_kinds() == ["attention", "sliding", "sliding",
                                   "sliding", "attention", "sliding"]
    assert again.ffn_kinds() == ["dense"] + ["experts"] * 5
    assert again.has_ring and not again.has_state
    assert not again.is_opt_block and again.extra_fetches == ["moe_load"]
    # a head width of its own: 64 // 4 happens to be 16, 64 // 6 is not
    assert again.d_head == 16 and [again.heads(i) for i in range(6)] == [
        4, 6, 6, 6, 4, 6]
    assert DecodeConfig(97, n_head=6, d_model=64, head_dim=16,
                        n_kv_head=2).d_head == 16
    assert DecodeConfig(97, n_head=8, d_model=64).d_head == 8
    assert again.held == (4, 8) and again.rope["full"]["rotary_dim"] == 8
    assert pred.config.to_dict() == cfg.to_dict()
    # only what differs from a block without them is written
    assert "n_expert" in d and "head_dim" in d
    hybrid = DecodeConfig(97, n_layer=2, n_head=4, d_model=64, n_kv_head=1,
                          attn_layer_period=2, attn_layer_offset=1,
                          norm="rms_norm", ffn="gated_silu",
                          positions=False, biases=False,
                          tie_embeddings=True)
    assert not set(hybrid.to_dict()) & {f for f, _ in
                                        DecodeConfig.MORE_FIELDS}


@_cases("bad,match", [
    (dict(ffn_types=["experts"], n_expert=4, expert_top_k=8, d_expert=8),
     "expert layer needs"),
    (dict(ffn_types=["experts"], n_expert=4, expert_top_k=2, d_expert=8,
          experts_held=[2, 6]), "expert layer needs"),
    (dict(attn_types=["sliding"]), "needs a window"),
    (dict(n_head_by_layer=[6], n_kv_head=4), "do not divide"),
    (dict(ffn_types=[]), "names 0 layers of 1"),
])
def test_config_refuses_what_does_not_add_up(bad, match):
    with pytest.raises(ValueError, match=match):
        DecodeConfig(97, n_layer=1, n_head=4, d_model=64, **bad)
    # a list longer than the depth is the source's, cut to the depth
    assert DecodeConfig(97, n_layer=1, attn_types=["full", "sliding"],
                        window=4).attn_types == ["full"]


def test_builders_refuse_what_no_graph_computes():
    from paddle_tpu.models import jamba

    base = dict(n_layer=1, n_head=4, d_model=64, norm="rms_norm",
                ffn="gated_silu", positions=False, biases=False)
    jamba._check(DecodeConfig(97, **base))  # an untied head is built now
    experts = dict(base, ffn_types=["experts"], n_expert=8, expert_top_k=2,
                   d_expert=16, d_shared_expert=16, experts_held=[0, 8])
    jamba._check(DecodeConfig(97, **experts))
    for bad, match in [
            (dict(base, biases=True), "no biases"),
            (dict(base, attn_gate="elementwise"), "per_head"),
            (dict(experts, router_score="tanh"),
             "sigmoid or softmax scores"),
            # no shared expert (0) is a layer the program builds since
            # PR 54 (MiMo-V2-Flash has none): a negative width is not
            (dict(experts, d_shared_expert=-16), "a shared expert"),
            (dict(base, rope={"full": {"rotary_dim": 64}}), "rotation"),
            (dict(base, ffn_types=["glu"]), "no graph computes")]:
        with pytest.raises(ValueError, match=match):
            jamba._check(DecodeConfig(97, **bad))


# -- the parent's manifests build the parent's programs -----------------------

# manifests as the parent commit (232c7a0) wrote them, and the content
# fingerprints of the programs it built from them at prefill (2, 32) and
# decode (4, 64), read on that commit: the AOT keys of the cells that
# stand (their cached executables still load). The hybrid's prefill is
# PR 45's: its attention layer's op became `prefill_attention` (the
# serving prefills' forward-only entry), a program of another key.
PARENT = {
    "opt": ({"d_inner": 64, "d_model": 32, "eos_id": None, "max_len": 64,
             "n_head": 4, "n_layer": 2, "prefix": "lm",
             "tie_embeddings": False, "vocab_size": 97},
            {"prefill": "9f57666b", "decode": "d70ff695"}),
    "hybrid": ({"attn_layer_offset": 2, "attn_layer_period": 4,
                "biases": False, "d_inner": 96, "d_model": 64,
                "eos_id": None, "ffn": "gated_silu", "mamba_d_conv": 4,
                "mamba_d_state": 16, "mamba_dt_rank": 4, "mamba_expand": 2,
                "max_len": 64, "n_head": 4, "n_kv_head": 1, "n_layer": 4,
                "norm": "rms_norm", "norm_eps": 1e-06, "positions": False,
                "prefix": "lm", "tie_embeddings": True, "vocab_size": 97},
               {"prefill": "53bffb68", "decode": "3a4ba361"}),
}


@_cases("model,kind", [(m, k) for m in sorted(PARENT)
                                        for k in ("prefill", "decode")])
def test_parent_manifests_build_the_parents_programs(model, kind):
    manifest, fps = PARENT[model]
    p = DecodePredictor.__new__(DecodePredictor)  # graph builder only
    p.config = DecodeConfig.from_dict(manifest)
    p.sample_k, p.sample_p, p.temperature, p.draft_n_layer = 40, 0.9, 1.0, 1
    assert p.config.to_dict() == manifest  # and is written as it was
    assert p.config.extra_fetches == []
    batch, seq = (2, 32) if kind == "prefill" else (4, 64)
    prog = p._build(kind, batch, seq, "greedy")[0]
    assert obs.program_fp(prog) == fps[kind]



# -- chip_smoke.py's Laguna phase, off the chip -------------------------------

def test_chip_smoke_laguna_phase_tiny(capsys, monkeypatch, tmp_path):
    """`chip_smoke.phase_laguna` tiny on the CPU, the rehearsal that
    precedes a chip run (here and not in test_chip_smoke.py, whose item
    count is part of the schedule: `_cases`): a prompt of 21 tokens
    wraps a ring of 8 rows, then six steps, against the full-forward
    rollout."""
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    tiny = dict(chip_smoke.LAGUNA, vocab=97, d_model=64, head_dim=16,
                d_inner=96, window=8, d_expert=24, seq=64, slots=2,
                prompt=21, new_tokens=6, require_tpu=False)
    chip_smoke.phase_laguna(tiny, fluid.CPUPlace())
    out = capsys.readouterr().out
    assert '"phase": "laguna"' in out
    assert '"rollout_tokens_agreeing": 6' in out
