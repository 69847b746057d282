"""A dots3-note-family LM (multi-head latent attention UNDER A LEARNED
INDEXER in the full layers: a query attends the `index_topk` earlier
positions the indexer scores highest; latent attention OF ANOTHER
GEOMETRY over a window in the sliding layers, which keep a ring of
latent rows; rescaled latents; a sigmoid gate a head on both; a leading
dense MLP, then routed experts under a sigmoid router with a selection
bias, one chip's share held) through the normal serving path
(`save_decode_model` -> `DecodePredictor` -> `DecodeServer`) at a tiny
size: prefill (the expanded attention under the indexer's (query, key)
mask, or over the window) then decode (the absorbed attention over the
chosen rows of the slab, or over the ring) LOGITS against the plain
reference's full forward pass (`benchmark/reference/dots3.py`:
`lax.top_k` over every pair's score, which imports nothing of the
program), contexts that pass the top-k, wrap the ring and lie in two
buckets; the exact choice; the four ASSUMED fields; the shares of an
expert-parallel deployment adding up; the `index` and `latent_ring`
entries of `cache_spec`; the counts; the manifests that stand."""
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
from paddle_tpu.ops import dsa  # noqa: E402
from paddle_tpu.serving.decode import (  # noqa: E402
    DecodeConfig, DecodePredictor, DecodeServer, cache_spec, kv_slab_slots,
    save_decode_model)

from benchmark.lib import weights  # noqa: E402
from benchmark.models import (dots3_lm, jamba_lm, laguna_lm,  # noqa: E402
                              ling3_lm, mistral4_lm, phi4flash_lm)
from benchmark.reference import dots3 as ref  # noqa: E402

# hidden 64; full layers: 4 heads of 16 + 8 query/key and 16 value
# channels over a latent of 16 + 8 = 24 floats a position, an indexer of
# 4 heads of 16 that picks 16 rows; sliding layers: 2 heads of 24 + 8
# and 16 over a latent of 24 + 8 = 32, a window of 9; layers full, full,
# sliding, sliding, sliding; layer 0 dense, then 32 routed experts, 2 a
# token, experts 0..7 held, and a shared one
with open(os.path.join(_ROOT, "benchmark", "tests", "tiny",
                       "dots3-tiny.json")) as _f:
    CFG = json.load(_f)
SLOTS, SEQ, N_LAYER = 4, 128, 5
ROW, KEY, RING_ROW, WINDOW, TOPK = 24, 16, 32, 9, 16


def _seeded(cfg):
    specs = dots3_lm.parameter_specs(cfg, "serve")
    return weights.seeded_weights(specs, 2 ** 31 + 11, dots3_lm.init_rule)


def _pred(d, cfg, w):
    scope = fluid.Scope()
    for n in w:
        scope.set_var(n, w[n])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        save_decode_model(d, dots3_lm.decode_config(cfg, "serve"), exe,
                          scope=scope)
    return DecodePredictor(d)


@pytest.fixture(scope="module")
def seeded():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def pred(tmp_path_factory, seeded):
    return _pred(str(tmp_path_factory.mktemp("dots3_model")), CFG, seeded)


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


K = 8
# every one passes the top-k of 16 and wraps the ring of 9; 40 lies in
# the bucket of 64, 70 and 100 in the bucket of 128
PROBE_LENS = [40, 70, 100]


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
    """Prompts of 40, 70 and 100 tokens in buckets of 64 and 128, in
    three neighbouring slots, then 8 teacher-forced steps (the indexer's
    choice over the slab, one row of each ring overwritten), against the
    reference's ONE full forward pass. LOGITS, 1e-5 relative L2:
    float32 on the CPU on both sides, and the SAME rows chosen."""
    prompts, forced, got = probes
    err = _rel(got[which], _want(seeded, prompts[which], forced[which]))
    assert err < 1e-5, err


@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v])
def test_a_reference_that_changes_a_part_is_told_apart(probes, seeded,
                                                       variant):
    """The comparison sees each mechanism: against a reference with no
    selection, half the top-k, index keys not rotated, the newest
    position's index key missing at a step, a window one short, no
    rescale, no gate, k_r not rotated, no shared expert or no selection
    bias, the same logits are far away, where the program is 1e-6 from
    the true reference."""
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
    no slot and no last occupant: an index key, a latent row or a ring
    row that leaks between neighbours, or a ring an admission did not
    replace whole, fails here."""
    prompts = _prompts([70, 26, 19, 81], seed=7)
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


# -- the choice -----------------------------------------------------------------

def _top_k_mask(scores, live, k):
    masked = jnp.where(live, scores, -jnp.inf)
    _, idx = jax.lax.top_k(masked, min(k, scores.shape[-1]))
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, -1)
    return want & np.asarray(live)


@pytest.mark.parametrize("case", ["distinct", "many_ties", "few_live",
                                  "all_equal", "none_live"])
def test_select_is_lax_top_k_with_ties_to_the_lower_position(case):
    """`ops/dsa.select` (a threshold found bit by bit, no sort) names
    the rows `lax.top_k` names: exactly k where k are live, every live
    row where fewer are, and of equal scores the lower positions."""
    r = np.random.default_rng(len(case))
    s, k = 200, 16
    x = r.normal(size=(3, 5, s)).astype(np.float32)
    live = r.random((3, 5, s)) < 0.9
    if case == "many_ties":
        x = np.round(x * 2) / 2 - 0.0
    elif case == "few_live":
        live = r.random((3, 5, s)) < 0.05
    elif case == "all_equal":
        x[:] = 0.25
    elif case == "none_live":
        live[:] = False
    got = np.asarray(jax.jit(lambda a, b: dsa.select(a, b, k))(x, live))
    assert (got == _top_k_mask(jnp.asarray(x), jnp.asarray(live), k)).all()
    assert (got.sum(-1) == np.minimum(live.sum(-1), k)).all()
    if case == "all_equal":  # constructed equal scores: the first k live
        first = np.cumsum(live, -1) <= k
        assert (got == (live & first)).all()


def test_prefill_and_step_masks_hold_k_rows_and_all_rows_before_k():
    r = np.random.default_rng(2)
    b, t, j, d, k = 2, 64, 4, 16, 16
    q_i = jnp.asarray(r.normal(size=(b, t, j, d)), jnp.float32)
    w = jnp.asarray(r.normal(size=(b, t, j)), jnp.float32)
    k_i = jnp.asarray(r.normal(size=(b, t, d)), jnp.float32)
    mask = np.asarray(dsa.prefill_mask(q_i, w, k_i, k, rows=16))
    assert mask.shape == (b, t, t) and mask.dtype == np.int8
    at = np.arange(t)
    assert (mask.sum(-1) == np.minimum(at + 1, k)[None]).all()
    assert not (mask * (at[None, :] > at[:, None])).any()  # causal
    assert (mask[:, :k] == (at[None, :] <= at[:k, None])).all()
    scores = dsa.index_scores(q_i, w, k_i)
    seen = jnp.broadcast_to(at[None, :] <= at[:, None], (b, t, t))
    assert (mask.astype(bool) == _top_k_mask(scores, seen, k)).all()
    # a step at position 40 of slot 0 and 9 of slot 1 is the prefill's
    # row at those positions
    lens = np.array([40, 9], np.int32)
    one = np.stack([np.arange(b), lens])
    step = np.asarray(dsa.step_mask(
        q_i[one[0], one[1]][:, None], w[one[0], one[1]][:, None], k_i,
        jnp.asarray(lens + 1), k))
    assert (step == mask[one[0], one[1]].astype(bool)).all()


# -- the four ASSUMED conventions ------------------------------------------------

@pytest.mark.parametrize("field,other", [
    ("mla_qkv_lora_rescale", "none"), ("attention_gate", "per_channel"),
    ("index_rope", "last_interleaved"), ("sliding_window", "excludes_query")])
def test_each_assumed_fields_other_value_is_refused(field, other):
    cfg = dict(CFG, assumed=dict(CFG["assumed"], **{field: other}))
    with pytest.raises(ValueError, match="assumed.%s = %r" % (field, other)):
        dots3_lm.decode_config(cfg, "serve")
    with pytest.raises(ValueError, match="assumed.%s = %r" % (field, other)):
        ref.check_assumed(cfg)
    cfg["assumed"].pop(field)
    with pytest.raises(ValueError, match="assumed.%s = None" % field):
        dots3_lm.decode_config(cfg, "serve")


def test_config_and_builders_refuse_what_is_not_built():
    from paddle_tpu.models import jamba

    base = dict(n_layer=1, n_head=4, d_model=64, norm="rms_norm",
                ffn="gated_silu", positions=False, biases=False)
    latent = dict(base, kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
                  v_head_dim=16)
    with pytest.raises(ValueError, match="under an indexer needs"):
        DecodeConfig(97, layer_types=["latent_dsa"], **latent)
    dsa_cfg = dict(latent, layer_types=["latent_dsa"], q_lora_rank=32,
                   index_heads=4, index_head_dim=16, index_topk=16)
    with pytest.raises(ValueError, match=r"needs rope\['index'\]"):
        jamba._check(DecodeConfig(97, **dsa_cfg))
    with pytest.raises(ValueError, match="rotary_dim within"):
        jamba._check(DecodeConfig(
            97, rope={"index": {"theta": 1e4, "rotary_dim": 32}}, **dsa_cfg))
    jamba._check(DecodeConfig(
        97, rope={"index": {"theta": 1e4, "rotary_dim": 8}}, **dsa_cfg))
    with pytest.raises(ValueError, match="needs a window"):
        DecodeConfig(97, layer_types=["latent_ring"], **base)
    with pytest.raises(ValueError, match="needs latent_ring = "):
        DecodeConfig(97, layer_types=["latent_ring"], window=9,
                     latent_ring={"n_head": 2}, **base)


# -- one chip's share -------------------------------------------------------------

def test_the_shares_routed_parts_and_the_shared_expert_add_up():
    """`ops/moe.py` with `experts_held` = each eighth in turn (four
    shares of 8 of a 32-expert layer; the cell's are 32 of 8 of 256):
    the routed parts of all shares + the shared expert counted ONCE ==
    the uncut layer, by the program's ops and by the reference alike."""
    from paddle_tpu.ops import moe

    r = np.random.default_rng(5)
    d, f, n, k = 64, 32, 32, 2
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
    whole = np.asarray(ref.moe(p, x, dict(CFG, experts_held=[0, n]),
                               "highest"))
    idx, w = moe.moe_route(x, p["router.w"], k, 1.0, bias=p["router.bias"])
    total = np.asarray(moe.moe_shared(x, p["shared.gate.w"],
                                      p["shared.up.w"], p["shared.down.w"]))
    total_ref, loads = None, 0
    for lo in range(0, n, 8):
        part, load = moe.moe_experts(
            x, idx, w, p["experts.gate.w"][lo:lo + 8],
            p["experts.up.w"][lo:lo + 8], p["experts.down.w"][lo:lo + 8],
            lo=lo)
        total = total + np.asarray(part)
        loads += int(load.sum())
        sub = dict(p, **{"experts.%s.w" % nm: p["experts.%s.w" % nm][
            lo:lo + 8] for nm in ("gate", "up", "down")})
        share = np.asarray(ref.moe(
            sub, x, dict(CFG, experts_held=[lo, lo + 8]), "highest",
            shared=(lo == 0)))
        total_ref = share if total_ref is None else total_ref + share
    assert loads == 11 * k  # every pair fell on exactly one share
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(total_ref, whole, rtol=2e-4, atol=2e-5)


# -- the cache manager's one description, the counts ---------------------------

def test_cache_spec_has_index_and_latent_ring_entries(pred):
    spec = pred.cache_spec(SLOTS, SEQ)
    names = [e.name for e in spec]
    assert names == sorted(names) == [
        "index_0", "index_1", "latent_0", "latent_1", "lring_2", "lring_3",
        "lring_4"]
    by = {e.name: e for e in spec}
    assert tuple(by["index_1"]) == ("index_1", (SLOTS, SEQ, KEY), "float32",
                                    True)
    assert tuple(by["latent_0"]) == ("latent_0", (SLOTS, SEQ, ROW),
                                     "float32", True)
    assert tuple(by["lring_3"]) == ("lring_3", (SLOTS, WINDOW, RING_ROW),
                                    "float32", False)
    assert [e.kind for e in spec] == ["index"] * 2 + ["latent"] * 2 + [
        "latent_ring"] * 3
    _, feeds, fetches = pred._build("decode", SLOTS, SEQ, "greedy")
    assert feeds == ["tokens", "lengths", "seed"] + names
    assert len(fetches) == 2 + len(spec) + 1
    # capacity: a ring costs the same whatever the slab's length
    per_slot = 2 * SEQ * (ROW + KEY) * 4 + 3 * WINDOW * RING_ROW * 4
    assert sum(e.nbytes for e in pred.cache_spec(1, SEQ)) == per_slot
    assert kv_slab_slots(10 * per_slot + 1, pred.config, SEQ) == 10
    assert (sum(e.nbytes for e in cache_spec(pred.config, 1, 16))
            == per_slot - 2 * (SEQ - 16) * (ROW + KEY) * 4)
    with pytest.raises(ValueError, match="latent.*ring|ring.*latent"):
        pred.cache_spec(SLOTS, SEQ, "int8")


def test_cache_spec_at_the_cells_sizes_holds_32_slots():
    """The cell's own numbers: 99.0 MB a slot, 3.17 GB for 32."""
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "dots3-note-prev.json")) as f:
        cfg = dots3_lm.decode_config(json.load(f), "serve_closed")
    spec = cache_spec(cfg, 32, 16384)
    by = {e.name: e for e in spec}
    assert by["index_1"].shape == (32, 16384, 128)
    assert by["latent_0"].shape == (32, 16384, 576)
    assert by["lring_4"].shape == (32, 513, 1088)
    assert sum(e.nbytes for e in spec) == 32 * 98972416
    geo = cfg.latent_geometry("latent_ring")
    assert (geo.n_head, geo.row, geo.scale) == (64, 1088, 256 ** -0.5)
    assert abs(geo.rho_q - 5 ** 0.5) < 1e-12 > abs(geo.rho_kv - 5 ** 0.5)
    full = cfg.latent_geometry("latent_dsa")
    assert (full.n_head, full.row, full.scale) == (128, 576, 192 ** -0.5)
    assert abs(full.rho_kv - 10 ** 0.5) < 1e-12


def test_server_books_rows_scored_chosen_and_live(pred):
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=4)
    assert srv._index_topk == TOPK and srv._ring_window == WINDOW
    assert srv._stream_rows is None
    counts = srv._step_counts(np.array([3, 0, 30, 0], np.int32), 2)
    assert counts == {
        "active": 2, "attended": 35, "streamed": SLOTS * SEQ,
        "state_bytes": 0, "ring_rows": 4 + WINDOW, "expert_pairs": 0,
        "experts_active": 0, "latent_rows": 35, "latent_row_bytes": ROW * 4,
        "rows_live": 35, "rows_scored": SLOTS * SEQ, "rows_chosen": 4 + TOPK}
    prompts = _prompts([20, 3], seed=11)
    sc = srv._scatter_counts(2, prompts, bucket_rows=2 * 32)
    assert sc["entries"] == 7 and sc["state_slots"] == 0
    assert (sc["prompt_rows"], sc["bucket_rows"], sc["prompts"]) == (
        23, 64, 2)
    assert sc["attn_pairs"] == sc["index_pairs"] == 210 + 6
    # a query at t keeps min(t + 1, 16) rows, and min(t + 1, 9) in a ring
    assert sc["chosen_pairs"] == 136 + 4 * 16 + 6
    assert sc["window_pairs"] == 45 + 11 * 9 + 6
    assert sc["ring_rows"] == WINDOW + 3


def test_a_step_under_the_indexer_streams_live_blocks_where_kernels_run(
        pred, monkeypatch):
    """The view the server asks its block from (`jamba.stream_view`) is
    the one-pass kernel's under an indexer: no slot's scores wait, so
    128 heads on 16,384 positions have a block (1,024 lanes) where the
    two-pass rule has none; on a device with the kernels `rows_scored`
    is then the live blocks' rows, on the CPU every row of every
    slot."""
    from paddle_tpu.models import jamba
    from paddle_tpu.ops import decode_stream, kv_cache, mla

    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "dots3-note-prev.json")) as f:
        cfg = dots3_lm.decode_config(json.load(f), "serve_closed")
    view = jamba.stream_view(cfg, 16384)
    assert (view.seq, view.q_block, view.k_block, view.o_block) == (
        16384, (1, 128, 576), (1, 576, 1), (1, 128, 512))
    assert view.score_rows == 0  # `mla.chosen_view`'s mark
    assert decode_stream.block_positions(view) == 1024
    assert kv_cache.decode_stream_rows(view) is None  # the CPU
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=4)
    lens = np.array([3, 0, 30, 0], np.int32)
    assert srv._step_counts(lens, 2)["rows_scored"] == SLOTS * SEQ
    monkeypatch.setattr(srv, "_stream_rows", 16)  # as a device's block
    counts = srv._step_counts(lens, 2)
    # a slot's blocks up to the row this step appends: 1 + 1 + 2 + 1
    assert counts["rows_scored"] == counts["streamed"] == 5 * 16
    assert counts["rows_live"] == 35 and counts["rows_chosen"] == 4 + TOPK


@pytest.mark.parametrize("kwargs", [
    {"speculative": True}, {"prefix_cache": True}, {"kv_dtype": "int8"}],
    ids=["speculative", "prefix_cache", "int8"])
def test_server_refuses_what_is_not_built_over_a_ring(pred, kwargs):
    with pytest.raises(ValueError, match="ring of 9 rows|kind 'ring'|ring"):
        DecodeServer(pred, slots=2, max_seq=SEQ, **kwargs)


# -- the manifests ---------------------------------------------------------------

NEW_FIELDS = {"latent_ring", "latent_rescale", "index_heads",
              "index_head_dim", "index_topk"}


def test_manifest_round_trip(pred):
    cfg = dots3_lm.decode_config(CFG, "serve")
    d = json.loads(json.dumps(cfg.to_dict()))
    again = DecodeConfig.from_dict(d)
    assert again.to_dict() == cfg.to_dict() == pred.config.to_dict()
    assert again.layer_kinds() == ["latent_dsa"] * 2 + ["latent_ring"] * 3
    assert again.ffn_kinds() == ["dense"] + ["experts"] * 4
    assert again.has_latent and again.has_ring
    assert not (again.has_state or again.is_opt_block)
    assert again.latent_row == ROW and again.held == (0, 8)
    assert NEW_FIELDS <= set(d)
    assert again.latent_ring == {
        "n_head": 2, "q_lora_rank": 24, "kv_lora_rank": 24,
        "qk_nope_dim": 24, "qk_rope_dim": 8, "v_head_dim": 16}
    assert again.rope["index"] == {"theta": 8e7, "rotary_dim": 8}
    assert sorted(n for n in pred._state if ".l1.attention." in n) == sorted(
        "lm.l1.attention." + nm for nm in (
            "q_a.w", "q_norm.w", "q_b.w", "kv_a.w", "kv_norm.w", "kv_b.w",
            "gate.w", "o.w", "index.q.w", "index.k.w", "index.k_norm.w",
            "index.k_norm.b", "index.weights.w"))
    assert sorted(n for n in pred._state if ".l3.attention." in n) == sorted(
        "lm.l3.attention." + nm for nm in (
            "q_a.w", "q_norm.w", "q_b.w", "kv_a.w", "kv_norm.w", "kv_b.w",
            "gate.w", "o.w"))


@pytest.mark.parametrize("name,builder", [
    ("jamba2-3b", jamba_lm), ("laguna-xs.2", laguna_lm),
    ("phi4-mini-flash", phi4flash_lm), ("mistral-small-4", mistral4_lm),
    ("ling-3.0-flash", ling3_lm)])
def test_manifests_written_before_this_model_load_unchanged(name, builder):
    """The fields this model added are written only where set: the
    manifests of the five described models that stand hold none of them
    and come back as they were written."""
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        cfg = builder.decode_config(json.load(f), "serve_closed")
    text = json.dumps(cfg.to_dict(), indent=2, sort_keys=True)
    assert not set(cfg.to_dict()) & NEW_FIELDS
    again = DecodeConfig.from_dict(json.loads(text))
    assert json.dumps(again.to_dict(), indent=2, sort_keys=True) == text
    if again.has_latent:
        geo = again.latent_geometry()
        assert (geo.row, geo.rho_q, geo.rho_kv) == (again.latent_row, 1.0,
                                                    1.0)
