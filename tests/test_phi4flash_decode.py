"""A Phi-4-mini-flash-family LM (SambaY: Mamba-1 and sliding-window
layers, one Mamba layer that hands on its memory, one full-attention
layer whose K and V the cross layers after it read, gated memory units;
differential attention, LayerNorm, biased attention projections, a tied
table) through the normal serving path (`save_decode_model` ->
`DecodePredictor` -> `DecodeServer`) at a tiny size: prefill-then-decode
logits, row for row, against the plain reference
(`benchmark/reference/phi4flash.py`, which imports nothing of the
program and runs every layer on every row); the one-row shortcut of a
prefill against a prefill that runs every layer on every row; the kinds
rule; `cache_spec` (a layer may own no entry); the counts; the
manifests."""
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
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.framework.trace import RngStream, trace_block  # noqa: E402
from paddle_tpu.models import jamba  # noqa: E402
from paddle_tpu.serving.decode import (  # noqa: E402
    DecodeConfig, DecodePredictor, DecodeServer, cache_spec, kv_slab_slots,
    save_decode_model)

from benchmark.lib import shared_kv_cost, weights  # noqa: E402
from benchmark.models import jamba_lm, laguna_lm, phi4flash_lm  # noqa: E402
from benchmark.reference import phi4flash as ref  # noqa: E402

# hidden 64, 8 query heads on 4 key/value heads of 8, 8 layers by the
# rule (Mamba, sliding, Mamba, sliding | memory Mamba, full, GMU,
# cross), window 8, state 4
with open(os.path.join(_ROOT, "benchmark", "tests", "tiny",
                       "phi4flash-tiny.json")) as _f:
    CFG = json.load(_f)
N_LAYER, WINDOW = CFG["num_hidden_layers"], CFG["sliding_window"]
SLOTS, SEQ = 4, 64
K = 2 * WINDOW  # decode steps: every ring wraps again
PROBE_LENS = [5, 21, 40]  # under the window, and wrapping it 2 and 5 times


@pytest.fixture(scope="module")
def seeded():
    specs = phi4flash_lm.parameter_specs(CFG, "serve")
    return weights.seeded_weights(specs, 2 ** 31 + 11,
                                  phi4flash_lm.init_rule)


@pytest.fixture(scope="module")
def pred(tmp_path_factory, seeded):
    d = str(tmp_path_factory.mktemp("phi4flash_model"))
    scope = fluid.Scope()
    for n in seeded:
        scope.set_var(n, seeded[n])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        save_decode_model(d, phi4flash_lm.decode_config(CFG, "serve"), exe,
                          scope=scope)
    return DecodePredictor(d)


def _prompts(lens, seed=3):
    r = np.random.default_rng(seed)
    return [r.integers(1, CFG["vocab_size"], n, dtype=np.int64)
            for n in lens]


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def probes(pred):
    from benchmark.lib import run_serveany

    prompts = _prompts(PROBE_LENS)
    forced = _prompts([K + 1] * len(prompts), seed=4)
    rows, _ = run_serveany._direct_rollout(pred, prompts, K, SLOTS, SEQ,
                                           forced=forced)
    return prompts, forced, [np.stack(r) for r in rows]


def _reference(seeded, text, rows, variant=""):
    """`rows` of the plain reference's logits over `text`, padded to SEQ
    positions: see `tests/test_laguna_decode.py::_reference`."""
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
    """Prompts of 5, 21 and 40 tokens (5 never wraps a ring of 8 rows,
    21 and 40 do), prefilled with the one-row shortcut, then 16 = 2 x
    window teacher-forced steps through slab, rings, states and memory;
    the reference's full forward pass, every layer on every row. Logits
    row for row, 2e-4 relative L2: float32 on the CPU on both sides;
    readings are ~3e-7."""
    prompts, forced, got = probes
    want = _want(seeded, prompts[which], forced[which])
    assert got[which].shape == want.shape == (K + 1, CFG["vocab_size"])
    assert max(_rel(g, w) for g, w in zip(got[which], want)) < 2e-4


@pytest.mark.parametrize("variant", ref.VARIANTS[1:])
def test_a_reference_that_changes_a_part_is_told_apart(probes, seeded,
                                                      variant):
    """Against a reference with lam = 0, without the heads' norm, with
    the memory taken after the gate, or with cross layers one key short,
    the same logits are 0.1% to 100% away, where the program is 3e-7
    from the true reference."""
    prompts, forced, got = probes
    err = _rel(got[2], _want(seeded, prompts[2], forced[2], variant))
    assert err > 5e-4, (variant, err)


def _run_prefill(seeded, cfg, tokens, lens, one_row_tail):
    """(logits, {feed name: entry}) of a prefill program traced as it
    stands, weights from `seeded`."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            t = layers.data(name="tokens", shape=list(tokens.shape),
                            dtype="int64", append_batch_size=False)
            n = layers.data(name="lengths", shape=[len(lens)],
                            dtype="int32", append_batch_size=False)
            logits, caches = jamba.hybrid_lm_prefill(
                t, n, cfg, one_row_tail=one_row_tail)
    env = dict(seeded)
    env.update(tokens=jnp.asarray(tokens), lengths=jnp.asarray(lens))
    trace_block(main.global_block(), env, RngStream(jax.random.PRNGKey(0)))
    ops = [op.type for op in main.global_block().ops]
    return (np.asarray(env[logits.name]),
            {k: np.asarray(env[v.name]) for k, v in caches.items()}, ops)


def test_one_row_shortcut_equals_every_layer_on_every_row(seeded):
    """The layers after the full one own no cache entry, so a prefill
    runs them on each prompt's last row alone: the same logits and the
    same cache entries as a prefill that runs them on all rows."""
    cfg = phi4flash_lm.decode_config(CFG, "serve")
    assert cfg.tail_start == 6 < cfg.n_layer
    prompts = _prompts([7, 30, 19])
    tokens = np.zeros((4, 32), np.int64)
    lens = np.ones((4,), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
        lens[i] = len(p)
    short = _run_prefill(seeded, cfg, tokens, lens, True)
    whole = _run_prefill(seeded, cfg, tokens, lens, False)
    np.testing.assert_allclose(short[0], whole[0], rtol=2e-5, atol=2e-6)
    assert sorted(short[1]) == sorted(whole[1]) == [
        e.name for e in cache_spec(cfg, 4, 32)]
    for name in short[1]:
        np.testing.assert_array_equal(short[1][name], whole[1][name])
    # and it is a shortcut: the tail's MLPs see (4, 1, D), not (4, 32, D)
    assert short[2].count("gmu") == whole[2].count("gmu") == 1
    want = _reference(seeded, prompts[1], [29])
    assert _rel(short[0][1], want[0]) < 2e-4


def test_mamba_mixer_hands_on_the_scan_before_the_gate(seeded):
    """The memory a GMU reads is the scan's output with the D skip,
    before silu(z): the mixer's third result, against the reference's
    `mamba` on the same layer's weights."""
    cfg = phi4flash_lm.decode_config(CFG, "serve")
    u = np.random.default_rng(5).normal(size=(1, 12, 64)).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="u", shape=[1, 12, 64], dtype="float32",
                            append_batch_size=False)
            n = layers.data(name="lengths", shape=[1], dtype="int32",
                            append_batch_size=False)
            out, _entries, memory = jamba._mamba_mixer(
                x, cfg, "lm.l4.mamba", n, None)
    env = dict(seeded)
    env.update(u=jnp.asarray(u), lengths=jnp.asarray([12], jnp.int32))
    trace_block(main.global_block(), env, RngStream(jax.random.PRNGKey(0)))
    p = {k[len("lm.l4.mamba."):]: v for k, v in seeded.items()
         if k.startswith("lm.l4.mamba.")}
    with jax.default_matmul_precision("highest"):
        w_out, w_mem, w_gated = ref.mamba(p, jnp.asarray(u[0]), 4, 4, 4,
                                          "highest")
    assert _rel(np.asarray(env[memory.name])[0], np.asarray(w_mem)) < 1e-5
    assert _rel(np.asarray(env[out.name])[0], np.asarray(w_out)) < 1e-4
    assert _rel(np.asarray(w_mem), np.asarray(w_gated)) > 0.1


@pytest.mark.parametrize("n,want", [
    (32, dict(mamba=9, sliding=8, attention=1, gmu=7, cross=7)),
    (16, dict(mamba=5, sliding=4, attention=1, gmu=3, cross=3)),
])
def test_the_kinds_rule(n, want):
    """8 : 8 : 1 : 1 : 7 : 7 of Mamba : sliding : memory Mamba : full :
    GMU : cross at N = 32, 4 : 4 : 1 : 1 : 3 : 3 at the cut's 16; the
    builder, the reference and the benchmark's byte counts agree."""
    kinds = phi4flash_lm.layer_kinds(n)
    assert {k: kinds.count(k) for k in want} == want
    half = n // 2
    assert kinds[half] == "mamba" and kinds[half + 1] == "attention"
    assert set(kinds[:half]) == {"mamba", "sliding"}
    assert set(kinds[half + 2:]) == {"gmu", "cross"}
    assert kinds[:half:2] == ["mamba"] * (half // 2)
    assert kinds == ref.layer_kinds(n)
    assert kinds == shared_kv_cost.layer_kinds({"num_hidden_layers": n})


def test_cache_spec_gives_gmu_and_cross_layers_no_entry(pred):
    cfg = pred.config
    assert cfg.layer_kinds() == ["mamba", "sliding", "mamba", "sliding",
                                 "mamba", "attention", "gmu", "cross"]
    spec = cache_spec(cfg, SLOTS, SEQ)
    names = [e.name for e in spec]
    assert names == sorted(names) == [
        "conv_0", "conv_2", "conv_4", "kcache_5", "kring_1", "kring_3",
        "ssm_0", "ssm_2", "ssm_4", "vcache_5", "vring_1", "vring_3"]
    by = {e.name: e for e in spec}
    # a position's row is kept flat: 4 key/value heads of 8
    assert by["kcache_5"].shape == (SLOTS, SEQ, 32)
    assert by["kcache_5"].kind == "rows"
    assert by["kring_3"].shape == (SLOTS, WINDOW, 32)
    assert by["kring_3"].kind == "ring" and by["ssm_4"].kind == "state"
    assert jamba.cache_names("gmu", 6) == jamba.cache_names("cross", 7) == []
    assert cfg.has_state and cfg.has_ring and cfg.kv_row == (32,)
    per_slot = sum(e.nbytes for e in cache_spec(cfg, 1, SEQ))
    assert kv_slab_slots(10 * per_slot + 1, cfg, SEQ) == 10


def test_server_counts_one_slab_and_its_readers(pred):
    """`attended` counts the ONE slab's rows once and `slab_readers`
    the layers that read them; the lax path streams the whole slab; an
    admission's scatter says how many rows the tail ran on."""
    srv = DecodeServer(pred, slots=SLOTS, max_seq=SEQ, max_new_tokens=4)
    counts = srv._step_counts(np.array([3, 0, 30, 0], np.int32), 2)
    state = 3 * (3 * 128 + 128 * 4) * 4
    assert counts == {"active": 2, "attended": 35, "streamed": SLOTS * SEQ,
                      "state_bytes": 2 * SLOTS * state,
                      "ring_rows": 4 + 8, "slab_readers": 2}
    prompts = _prompts([20, 3], seed=11)
    sc = srv._scatter_counts(2, prompts, bucket_rows=2 * 32)
    assert sc == {"entries": 12, "state_slots": 2, "ring_rows": 8 + 3,
                  "prompt_rows": 23, "tail_rows": 2,
                  "ssm_tokens": 23, "ssm_pad_tokens": 41}


def _is_greedy(seeded, prompt, generated):
    full = np.concatenate([prompt, generated])
    lg = _reference(seeded, full, np.arange(len(prompt) - 1, len(full) - 1))
    return lg.argmax(-1).tolist() == list(generated)


def test_neighbouring_slots_admitted_at_different_steps(pred, seeded):
    """Two slots, four requests admitted at different steps, prompts
    shorter and longer than the window; each answer is the reference's
    greedy rollout, which knows no slot, no ring, no state and no last
    occupant: a memory row or a slab row that leaks between neighbours
    fails here. `generate` answers the same."""
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
    outs = pred.generate(prompts[:2], max_new_tokens=7)
    assert list(outs[0]) == list(got[0])


@pytest.mark.parametrize("name,builder", [("jamba2-3b", jamba_lm),
                                          ("laguna-xs.2", laguna_lm)])
def test_old_manifests_round_trip_byte_for_byte(name, builder):
    """The fields this model added are written only where set: the
    manifests of the two described models that stand hold none of them
    and come back as they were written."""
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        cfg = builder.decode_config(json.load(f), "serve_closed")
    text = json.dumps(cfg.to_dict(), indent=2, sort_keys=True)
    assert not set(cfg.to_dict()) & {"layer_types", "diff_attn",
                                     "attn_biases", "mamba_norms"}
    again = DecodeConfig.from_dict(json.loads(text))
    assert json.dumps(again.to_dict(), indent=2, sort_keys=True) == text
    assert again.tail_start == again.n_layer and len(again.kv_row) == 2


def test_manifest_round_trip(pred):
    d = pred.config.to_dict()
    assert d["layer_types"][-3:] == ["attention", "gmu", "cross"]
    assert d["diff_attn"] is True and d["attn_biases"] is True
    assert d["mamba_norms"] is False and d["norm"] == "layer_norm"
    again = DecodeConfig.from_dict(json.loads(json.dumps(d)))
    assert again.to_dict() == d and again.layer_kinds() == d["layer_types"]
    opt = DecodeConfig(97, n_layer=2, n_head=4, d_model=32, d_inner=64)
    assert opt.is_opt_block and "layer_types" not in opt.to_dict()


@pytest.mark.parametrize("bad,match", [
    (dict(layer_types=["gmu"]), "reads what a mamba layer"),
    (dict(layer_types=["mamba", "cross"], n_layer=2),
     "reads what a attention layer"),
    (dict(layer_types=["conv"]), "is none of"),
    (dict(layer_types=["sliding"]), "needs a window"),
    (dict(diff_attn=True, n_kv_head=1), "do not pair"),
    (dict(layer_types=["mamba"], n_layer=2), "names 1 layers of 2"),
])
def test_config_refuses_what_does_not_add_up(bad, match):
    kw = dict(n_layer=1, n_head=4, d_model=64)
    kw.update(bad)
    with pytest.raises(ValueError, match=match):
        DecodeConfig(97, **kw)


def test_builders_refuse_what_no_graph_computes():
    base = dict(n_layer=2, n_head=4, d_model=64, norm="layer_norm",
                ffn="gated_silu", positions=False, biases=False)
    jamba._check(DecodeConfig(97, **base))  # LayerNorm is built now
    with pytest.raises(ValueError, match="differential attention alone"):
        jamba._check(DecodeConfig(97, layer_types=["attention", "cross"],
                                  **base))
    with pytest.raises(ValueError, match="without rotary"):
        jamba._check(DecodeConfig(97, diff_attn=True,
                                  rope={"full": {"rotary_dim": 16}}, **base))
    with pytest.raises(ValueError, match="LayerNorm"):
        jamba._check(DecodeConfig(97, **dict(base, norm="batch_norm")))


# -- chip_smoke.py's phase for this family, off the chip -----------------------

def test_chip_smoke_phi4flash_phase_tiny(capsys, monkeypatch, tmp_path):
    """`chip_smoke.phase_phi4flash` tiny on the CPU, the rehearsal that
    precedes a chip run: a prompt of 21 tokens wraps the rings of 8
    rows, its prefill takes the one-row shortcut, then six steps
    through the slab, rings, states and the memory, against the
    full-forward rollout."""
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    tiny = dict(chip_smoke.PHI4FLASH, vocab=97, d_model=64, n_head=8,
                n_kv_head=4, d_inner=96, window=8, seq=64, slots=2,
                prompt=21, new_tokens=6, require_tpu=False)
    assert chip_smoke.phi4flash_config(tiny).layer_kinds() == \
        phi4flash_lm.layer_kinds(8)
    chip_smoke.phase_phi4flash(tiny, fluid.CPUPlace())
    out = capsys.readouterr().out
    assert '"phase": "phi4flash"' in out
    assert '"rollout_tokens_agreeing": 6' in out
    assert '"slab_readers": 2' in out
