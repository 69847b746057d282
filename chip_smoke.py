"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip: kernels, train, serve,
                                    # and small Laguna-, Phi-4-mini-flash-
                                    # and Mistral-Small-4-family blocks
    python chip_smoke.py --chips 4  # four chips: the 2x2-mesh trainer only

One process, no children. Drives the two main paths through the entry
points a user calls — a `Program` on `Executor(TPUPlace())`, and
`save_decode_model` -> `DecodePredictor` -> `DecodeServer` — at the full
width of the LM the repo benchmarks (12 layers, d_model 1024, d_inner
4096, 8 heads of 128, vocab 32768, seq 1024; weights random from a seed),
and checks what comes out against the repo's own references. Every phase
prints one JSON line; the LAST line of stdout is the verdict:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exits non-zero, and never prints that line, when JAX's default device is
not a TPU, when a check fails, or when a phase raises. Numbers printed on
the way (compile seconds, step ms) are for orientation, not metrics.

The phase functions take a size config so tests/test_chip_smoke.py can
call them tiny on the CPU (`require_tpu=False` there; `main` always runs
them with `require_tpu=True`).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import re
import shutil
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
# the exported decode model (0.9 GB of random weights) and nothing else
OUT_DIR = os.path.join(_HERE, ".chip_smoke_out")

# The model, everywhere: the LM of `benchmark/configs/opt-6.7b.json`'s
# family at a width one chip trains 12 layers of.
FULL = dict(vocab=32768, n_layer=12, n_head=8, d_model=1024, d_inner=4096,
            seq=1024, batch=16,
            # serve: 8 seeded prompts of 128-512 tokens, 32 new tokens each
            slots=8, prompt_lo=128, prompt_hi=512, new_tokens=32,
            interpret=False, require_tpu=True)

# A small Laguna-family block (full and sliding-window layers mixed,
# rotary positions, a per-head gate, routed experts with a shared one,
# an untied head) at shapes that enter the kernels the serving cell
# enters: heads of 128, 2 or 3 query heads on each of 8 key/value heads
# (slabs with the decode kernel's free view), the published window of
# 512, a prompt that wraps the ring.
LAGUNA = dict(vocab=4096, d_model=512, head_dim=128, n_kv_head=8,
              heads=[16, 24, 24, 24, 16], d_inner=1024, window=512,
              n_expert=16, top_k=4, d_expert=256, held=[4, 12], seq=2048,
              slots=8, prompt=700, new_tokens=8, require_tpu=True)

# A Phi-4-mini-flash-family block (SambaY: Mamba and sliding layers, a
# Mamba layer that hands on its memory, ONE full layer whose K/V the
# cross layers read, gated memory units; differential attention): 8
# layers by the rule 2 : 2 : 1 : 1 : 1 : 1, 16 query heads on 8 key/value
# heads of 64 (flat rows of 512), the published window and state.
PHI4FLASH = dict(vocab=4096, d_model=1024, n_head=16, n_kv_head=8,
                 d_inner=2048, window=512, n_layer=8, seq=2048, slots=8,
                 prompt=700, new_tokens=8, require_tpu=True)

# A Mistral-Small-4-family block (every layer multi-head latent attention
# over a latent row of 320 floats, then routed experts under a softmax
# router with a shared one) at the published head widths (64 + 64
# query/key, 128 value channels, ranks 256 and 64: the flash kernel at a
# head of 128, a slab row that is no multiple of 128 lanes), YaRN's
# original context SHORTER than the prompt, so the query scale turns.
MISTRAL4 = dict(vocab=4096, d_model=1024, n_head=8, q_rank=256, kv_rank=256,
                nope=64, rope=64, v=128, n_layer=2, n_expert=16, top_k=4,
                d_expert=256, held=[0, 8], original=512, window=0,
                seq=2048, slots=8, prompt=700, new_tokens=8,
                require_tpu=True)

# Kimi Delta Attention at Ling-3.0-flash's published head sizes: 32 heads
# of a 128 x 128 state. Two prompts of 700 and 450 tokens in a bucket of
# 1,024 (padding inside a chunk), then 8 steps; every fourth channel
# decays at the gate's bound.
KDA = dict(heads=32, head_dim=128, batch=2, seq=1024, lengths=[700, 450],
           steps=8, require_tpu=True)

# Tolerances (max abs error over max abs reference, bf16 inputs): one
# bf16 rounding is 2^-8 = 0.4%; the backward accumulates ~T of them.
TOL_ATTN_FWD = 2e-2
TOL_ATTN_GRAD = 5e-2
TOL_DECODE_ATTN = 2e-2
# incremental decode vs full-forward rollout may part only at a near-tie:
# the reference's own logit gap between the two tokens, in logit units
TOL_GREEDY_TIE = 2e-2
# 2x2-mesh vs single-device loss under AMP O2 (bf16 activations)
TOL_PARALLEL_LOSS = 2e-2
# the chunked delta rule (products on the MXU: operands rounded to
# bfloat16) against one exact float32 step after another
TOL_KDA = 2e-2


def _emit(phase, **fields):
    line = json.dumps(dict(phase=phase, **fields), sort_keys=True)
    print(line, flush=True)
    out = os.path.join(_HERE, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "chip_smoke.jsonl"), "a") as f:
        f.write(line + "\n")


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all(), "non-finite kernel output"
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# -- phase 1: kernels -------------------------------------------------------

def phase_kernels(cfg):
    """Pallas BTHD attention (fwd, split bwd, fused bwd) against the XLA
    flash path on the same bf16 inputs; the Pallas decode kernel against
    `decode_attention_reference`."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import attention as A
    from paddle_tpu.ops import kv_cache as KV

    b, t, h = cfg["batch"], cfg["seq"], cfg["n_head"]
    d = cfg["d_model"] // h
    interp = cfg["interpret"]
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(b, t, h, d), jnp.bfloat16)
               for _ in range(3))

    def loss_pallas(q, k, v):
        o = A.pallas_flash_attention_bthd(q, k, v, causal=True,
                                          interpret=interp)
        return jnp.sum(jnp.sin(o.astype(jnp.float32))), o

    def loss_xla(q, k, v):
        o = A.flash_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                              jnp.swapaxes(v, 1, 2), causal=True)
        o = jnp.swapaxes(o, 1, 2)
        return jnp.sum(jnp.sin(o.astype(jnp.float32))), o

    def value_and_grads(fn):
        (_, out), grads = jax.jit(jax.value_and_grad(
            fn, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return out, grads

    ref_out, ref_grads = value_and_grads(loss_xla)
    errs = {}
    assert A._fused_bwd_fits(t, d, 2), "this shape would never run fused"
    budget = A._FUSED_BWD_VMEM_BUDGET
    try:
        # the backward is chosen at trace time from the shape and this
        # constant: "split" gives it a budget nothing fits
        for tag, limit in (("split", 1), ("fused", budget)):
            A._FUSED_BWD_VMEM_BUDGET = limit
            # a fresh lambda is a fresh trace
            out, grads = value_and_grads(lambda q, k, v: loss_pallas(q, k, v))
            errs["fwd"] = _rel_err(out, ref_out)
            assert errs["fwd"] <= TOL_ATTN_FWD, (
                "BTHD fwd vs XLA: %.3g > %.3g" % (errs["fwd"], TOL_ATTN_FWD))
            for name, g, rg in zip("qkv", grads, ref_grads):
                e = errs["%s_d%s" % (tag, name)] = _rel_err(g, rg)
                assert e <= TOL_ATTN_GRAD, (
                    "BTHD %s-backward d%s vs XLA at %s: %.3g > %.3g"
                    % (tag, name, (b, t, h, d), e, TOL_ATTN_GRAD))
    finally:
        A._FUSED_BWD_VMEM_BUDGET = budget

    slots = cfg["slots"]
    lengths = jnp.asarray(
        np.concatenate([[1, t], r.randint(1, t + 1, slots - 2)]), jnp.int32)
    for dt in (jnp.float32, jnp.bfloat16):
        dq = jnp.asarray(r.randn(slots, 1, h, d), dt)
        kc, vc = (jnp.asarray(r.randn(slots, t, h, d), dt) for _ in range(2))
        got = jax.jit(lambda *a: KV.pallas_decode_attention(
            *a, interpret=interp))(dq, kc, vc, lengths)
        want = jax.jit(KV.decode_attention_reference)(dq, kc, vc, lengths)
        e = errs["decode_%s" % jnp.dtype(dt).name] = _rel_err(got, want)
        assert e <= TOL_DECODE_ATTN, (
            "pallas_decode_attention %s vs reference: %.3g > %.3g"
            % (jnp.dtype(dt).name, e, TOL_DECODE_ATTN))
    _emit("kernels", shape=[b, t, h, d], rel_err=errs, ok=True)


def phase_kda(cfg):
    """`ptpu.kda_scan` (the chunked delta rule of a prefill) and
    `ptpu.kda_step` (one update) compiled and run on the device, against
    each other: the scan over a whole text == the scan over its first
    rows handed to the step, then one step after another (outputs and
    the last state); a row's padding leaves its state alone. On a TPU
    the scan is the KERNEL (one `ptpu.kda_scan` call in the compiled
    text, asserted) and so is the step (one `ptpu.kda_step` call, since
    PR 48); elsewhere the lax forms."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import kda

    h, d, b, t = cfg["heads"], cfg["head_dim"], cfg["batch"], cfg["seq"]
    steps = cfg["steps"]
    lens = np.asarray(cfg["lengths"], np.int32)
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(b, t, h, d), jnp.float32)
               for _ in range(3))
    g = -5.0 * r.uniform(size=(b, t, h, d)) ** 3
    g[..., ::4] = -4.999  # whole chunks at the bound on these channels
    g = jnp.asarray(g, jnp.float32)
    beta = jnp.asarray(r.uniform(0.05, 0.95, size=(b, t, h)), jnp.float32)
    scan = jax.jit(lambda *a: kda.kda_scan(*a, lower_bound=-5.0))
    step = jax.jit(kda.kda_step)
    path = "kernel" if kda._use_kernel(t, d, d) else "lax"
    text = scan.lower(q, k, v, g, beta, jnp.asarray(lens)).compile().as_text()
    calls = sum("%ptpu.kda_scan" in ln.split(" = ")[0]
                and "tpu_custom_call" in ln for ln in text.splitlines())
    step_path = ("kernel" if kda._use_step_kernel(h, d, d, jnp.float32)
                 else "lax")
    text = step.lower(*(a[:, :1] for a in (q, k, v, g, beta)), jnp.zeros(
        (b, h, d, d), jnp.float32)).compile().as_text()
    step_calls = sum("%ptpu.kda_step" in ln.split(" = ")[0]
                     and "tpu_custom_call" in ln for ln in text.splitlines())
    if jax.devices()[0].platform == "tpu":
        assert (path, calls, step_path, step_calls) == (
            "kernel", 1, "kernel", 1), (
            "at %r: the scan's path %s, %d kernel calls; the step's %s, %d"
            % ((b, t, h, d), path, calls, step_path, step_calls))
    o_all, s_all = scan(q, k, v, g, beta, jnp.asarray(lens))
    _, state = scan(q, k, v, g, beta, jnp.asarray(lens - steps))
    outs = []
    for j in range(steps):
        at = lens - steps + j
        row = lambda a: jnp.stack([a[i, at[i]] for i in range(b)])[:, None]  # noqa
        o, state = step(row(q), row(k), row(v), row(g), row(beta), state)
        outs.append(np.asarray(o)[:, 0])
    want = np.stack([np.stack([np.asarray(o_all)[i, lens[i] - steps + j]
                               for i in range(b)]) for j in range(steps)])
    errs = {"outputs": _rel_err(np.stack(outs), want),
            "state": _rel_err(state, s_all)}
    assert np.isfinite(np.asarray(o_all)).all()
    for name, e in errs.items():
        assert e <= TOL_KDA, ("kda_scan vs kda_step, %s: %.3g > %.3g"
                              % (name, e, TOL_KDA))
    _emit("kda", shape=[b, t, h, d], lengths=lens.tolist(), steps=steps,
          path=path, kernel_calls=calls, step_path=step_path,
          step_kernel_calls=step_calls, rel_err=errs, ok=True)


# -- phase 2: train ---------------------------------------------------------

def _build_lm(cfg, **lm_kwargs):
    """The training program as examples/train_lm.py builds it;
    `lm_kwargs` go to `transformer_lm` (fused_head=False to serve)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, models, optimizer

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 1
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            shape = [cfg["batch"], cfg["seq"]]
            ids = layers.data(name="ids", shape=shape, dtype="int64",
                              append_batch_size=False)
            labels = layers.data(name="labels", shape=shape, dtype="int64",
                                 append_batch_size=False)
            loss, _ = models.transformer.transformer_lm(
                ids, labels, vocab_size=cfg["vocab"],
                n_layer=cfg["n_layer"], n_head=cfg["n_head"],
                d_model=cfg["d_model"], d_inner=cfg["d_inner"],
                max_len=cfg["seq"], **lm_kwargs)
            optimizer.Adam(learning_rate=1e-4).minimize(loss)
        main_p.enable_mixed_precision(level="O2")
    return main_p, startup, loss


def _fixed_batch(cfg):
    r = np.random.RandomState(0)
    shape = (cfg["batch"], cfg["seq"])
    return {"ids": r.randint(0, cfg["vocab"], shape).astype(np.int64),
            "labels": r.randint(0, cfg["vocab"], shape).astype(np.int64)}


def _executable_texts(exe):
    """Compiled-module text of every executable `exe` holds in memory."""
    return [c.fn.as_text() for c in exe._cache._d.values()]


def phase_train(cfg, place):
    """5 `exe.run` steps on one fixed batch, then one `run_loop` window of
    4. AMP O2; the flash backward is the fused one, which this shape
    gets with nothing set (`ops/attention._fused_bwd_fits`)."""
    import jax

    import paddle_tpu as fluid

    main_p, startup, loss = _build_lm(cfg)
    feed = _fixed_batch(cfg)
    scope = fluid.Scope()
    exe = fluid.Executor(place)
    dev = place.jax_device()
    with fluid.scope_guard(scope):
        exe.run(startup)

        def step():
            return exe.run(main_p, feed=feed, fetch_list=[loss],
                           return_numpy=False)

        t0 = time.perf_counter()
        losses = [float(np.asarray(step()[0]))]
        compile_s = time.perf_counter() - t0
        # the same step fenced two ways, side by side (ROADMAP S1(b))
        ms_ready, ms_host = [], []
        for _ in range(2):
            t0 = time.perf_counter()
            out = step()
            jax.block_until_ready(out)
            ms_ready.append((time.perf_counter() - t0) * 1e3)
            losses.append(float(np.asarray(out[0])))
        for _ in range(2):
            t0 = time.perf_counter()
            val = float(np.asarray(step()[0]))
            ms_host.append((time.perf_counter() - t0) * 1e3)
            losses.append(val)
        t0 = time.perf_counter()
        out = exe.run_loop(main_p, feed=feed, fetch_list=[loss], steps=4)
        loop_first_s = time.perf_counter() - t0
        losses.append(float(np.asarray(out[0])))

        assert np.isfinite(losses).all(), "non-finite loss: %r" % (losses,)
        assert losses[-1] < losses[0] and losses[4] < losses[0], (
            "loss on the fixed batch did not fall: %r" % (losses,))
        pname = next(n for n in scope.local_var_names()
                     if n.endswith(".fc1.w"))
        param = scope.find_var(pname)
        assert isinstance(param, jax.Array), type(param)
        assert param.devices() == {dev}, (param.devices(), dev)
        texts = _executable_texts(exe)
        n_kernels = max(t.count("tpu_custom_call") for t in texts)
        if cfg["require_tpu"]:
            assert dev.platform == "tpu", dev
            assert n_kernels > 0, "no tpu_custom_call in the training step"
        stats = dev.memory_stats() or {}
    _emit("train", losses=losses, compile_plus_first_step_s=compile_s,
          step_ms_block_until_ready=ms_ready, step_ms_host_read=ms_host,
          run_loop_compile_plus_4_steps_s=loop_first_s,
          tpu_custom_calls=n_kernels, param=pname,
          param_device=str(dev), peak_bytes_in_use=stats.get(
              "peak_bytes_in_use"), ok=True)
    exe.close()


# -- phase 3: serve ---------------------------------------------------------

def _full_forward_rollout(pred, prompt, steps):
    """Reference: one full prefill forward per generated token (greedy),
    the recipe of tests/test_decode_serving.py. Returns (tokens, logits)."""
    from paddle_tpu.serving.decode import _pow2_bucket

    s = _pow2_bucket(len(prompt) + steps, floor=16)
    tokens = np.zeros((1, s), np.int64)
    tokens[0, :len(prompt)] = prompt
    lens = np.array([len(prompt)], np.int32)
    pexe, _ = pred.acquire("prefill", 1, s)
    toks, logits = [], []
    for _ in range(steps):
        row = np.asarray(pexe({"tokens": tokens, "lengths": lens},
                              pred._state)[0])[0]
        toks.append(int(row.argmax()))
        logits.append(row)
        tokens[0, lens[0]] = toks[-1]
        lens[0] += 1
    return toks, logits


def phase_serve(cfg, place):
    """save_decode_model -> DecodePredictor.generate -> DecodeServer.submit
    on the same prompts; greedy incremental decode against a full-forward
    rollout; AOT warm start of a second predictor."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.serving import (DecodeConfig, DecodePredictor,
                                    DecodeServer, save_decode_model)

    model_dir = os.path.join(OUT_DIR, "decode_model")
    shutil.rmtree(model_dir, ignore_errors=True)
    os.makedirs(model_dir)
    # parameters from startup: the training program without the fused
    # head names its parameters as the decode graphs expect
    _main, startup, _loss = _build_lm(cfg, fused_head=False)
    scope = fluid.Scope()
    exe = fluid.Executor(place)
    with fluid.scope_guard(scope):
        exe.run(startup)
        save_decode_model(model_dir, DecodeConfig(
            vocab_size=cfg["vocab"], n_layer=cfg["n_layer"],
            n_head=cfg["n_head"], d_model=cfg["d_model"],
            d_inner=cfg["d_inner"], max_len=cfg["seq"]), exe, scope=scope)
    exe.close()
    del scope, exe
    gc.collect()

    r = np.random.RandomState(2)
    slots, new = cfg["slots"], cfg["new_tokens"]
    prompts = [r.randint(1, cfg["vocab"], n).astype(np.int64)
               for n in r.randint(cfg["prompt_lo"], cfg["prompt_hi"] + 1,
                                  slots)]
    pred = DecodePredictor(model_dir, place=place)
    t0 = time.perf_counter()
    gen = pred.generate(prompts, max_new_tokens=new)
    generate_s = time.perf_counter() - t0
    assert all(len(g) == new for g in gen), [len(g) for g in gen]

    # the decode step executable: kernel engaged, feeds donated
    dexe, _ = pred.acquire("decode", slots, cfg["seq"])
    text = dexe.as_text()
    n_kernels = text.count("tpu_custom_call")
    slab = jnp.zeros((slots, cfg["seq"], cfg["n_head"],
                      cfg["d_model"] // cfg["n_head"]), jnp.float32)
    feeds = {"tokens": np.ones((slots, 1), np.int64),
             "positions": np.zeros((slots, 1), np.int64),
             "lengths": np.zeros((slots,), np.int32),
             "seed": np.zeros((1,), np.int64)}
    for i in range(cfg["n_layer"]):
        feeds["kcache_%d" % i] = slab + 0
        feeds["vcache_%d" % i] = slab + 0
    fed = feeds["kcache_0"]
    jax.block_until_ready(dexe(feeds, pred._state))
    donated = fed.is_deleted()
    if cfg["require_tpu"]:
        assert n_kernels > 0, (
            "no tpu_custom_call in the decode step: "
            "decode_attention_reference ran, not the Pallas kernel")
        assert donated and "input_output_alias" in text, (
            "the decode step did not donate its KV slabs")
    del feeds, fed, slab

    # incremental greedy == full-forward rollout, on the shortest prompt
    i_short = int(np.argmin([len(p) for p in prompts]))
    ref_toks, ref_logits = _full_forward_rollout(pred, prompts[i_short], new)
    agree = 0
    for got, want, row in zip(gen[i_short], ref_toks, ref_logits):
        if int(got) != want:
            gap = float(row[want] - row[int(got)])
            assert gap <= TOL_GREEDY_TIE, (
                "incremental decode left the full-forward rollout at token "
                "%d: %d vs %d, reference logit gap %.4g > %.4g"
                % (agree, int(got), want, gap, TOL_GREEDY_TIE))
            break  # a tie broken the other way: the tails are not comparable
        agree += 1

    # the server answers the same prompts with the same tokens; submitted
    # before start() so admission is one burst and the run is repeatable
    srv = DecodeServer(pred, slots=slots, max_seq=cfg["seq"],
                       max_new_tokens=new)
    if cfg["require_tpu"]:
        assert srv._stream_rows, (
            "the server counts whole slabs as streamed: its decode step "
            "does not run the in-place kernel")
    futs = [srv.submit((p,)) for p in prompts]
    t0 = time.perf_counter()
    srv.start()
    try:
        served = [np.asarray(f.result(timeout=600)).reshape(-1)
                  for f in futs]
    finally:
        srv.stop()
    serve_s = time.perf_counter() - t0
    for i, (a, b) in enumerate(zip(served, gen)):
        assert np.array_equal(a, b), (
            "server != generate() on prompt %d: %r vs %r" % (i, a, b))

    # a second predictor over the same directory warm-starts from disk
    pred2 = DecodePredictor(model_dir, place=place)
    gen2 = pred2.generate(prompts, max_new_tokens=new)
    assert pred2.traces == 0, (
        "second DecodePredictor traced %d programs (AOT warm start missed)"
        % pred2.traces)
    for a, b in zip(gen2, gen):
        assert np.array_equal(a, b), "warm-started predictor disagrees"
    _emit("serve", prompt_lens=[len(p) for p in prompts], new_tokens=new,
          generate_s=generate_s, server_s=serve_s,
          rollout_tokens_agreeing=agree, decode_tpu_custom_calls=n_kernels,
          decode_step_donated=donated, decode_stream_rows=srv._stream_rows,
          first_predictor_traces=pred.traces,
          second_predictor_traces=pred2.traces, ok=True)
    shutil.rmtree(model_dir, ignore_errors=True)


def laguna_config(cfg):
    from paddle_tpu.serving import DecodeConfig

    n = len(cfg["heads"])
    half = cfg["head_dim"] // 2
    return DecodeConfig(
        cfg["vocab"], n_layer=n, n_head=cfg["heads"][0],
        d_model=cfg["d_model"], d_inner=cfg["d_inner"], max_len=cfg["seq"],
        tie_embeddings=False, n_kv_head=cfg["n_kv_head"],
        head_dim=cfg["head_dim"], n_head_by_layer=cfg["heads"],
        attn_types=["full", "sliding", "sliding", "sliding", "full"][:n],
        window=cfg["window"], ffn_types=["dense"] + ["experts"] * (n - 1),
        n_expert=cfg["n_expert"], expert_top_k=cfg["top_k"],
        d_expert=cfg["d_expert"], d_shared_expert=cfg["d_expert"],
        experts_held=cfg["held"], router_scale=2.5, attn_gate="per_head",
        rope={"full": {"rotary_dim": half, "theta": 500000.0,
                       "attention_factor": 1.4158883083359672,
                       "yarn": {"factor": 64,
                                "original_max_position": cfg["seq"],
                                "beta_fast": 64, "beta_slow": 1}},
              "sliding": {"rotary_dim": cfg["head_dim"], "theta": 10000.0}},
        norm="rms_norm", norm_eps=1e-6, ffn="gated_silu", positions=False,
        biases=False)


def _serve_described(config, cfg, place, model_dir):
    """A described block with weights from its initializers through
    save_decode_model -> DecodePredictor -> DecodeServer: ONE admission
    whose prompt wraps the ring of a sliding layer, then
    ``new_tokens`` steps, against a full-forward rollout (one prefill a
    token, which knows no cache). -> (pred, srv, tokens agreeing,
    seconds, prompt)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import jamba
    from paddle_tpu.serving import (DecodePredictor, DecodeServer,
                                    save_decode_model)

    shutil.rmtree(model_dir, ignore_errors=True)
    os.makedirs(model_dir)
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 7
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            jamba.hybrid_lm_prefill(
                layers.data(name="tokens", shape=[1, 16], dtype="int64",
                            append_batch_size=False),
                layers.data(name="lengths", shape=[1], dtype="int32",
                            append_batch_size=False), config)
    scope = fluid.Scope()
    exe = fluid.Executor(place)
    with fluid.scope_guard(scope):
        exe.run(startup)
        save_decode_model(model_dir, config, exe, scope=scope)
    exe.close()
    del scope, exe
    gc.collect()

    new = cfg["new_tokens"]
    prompt = np.random.RandomState(3).randint(
        1, cfg["vocab"], cfg["prompt"]).astype(np.int64)
    assert cfg["prompt"] > cfg["window"], "the prompt must wrap the ring"
    assert cfg["prompt"] > cfg.get("original", 0), (
        "the prompt must pass YaRN's original context")
    pred = DecodePredictor(model_dir, place=place)
    srv = DecodeServer(pred, slots=cfg["slots"], max_seq=cfg["seq"],
                       max_new_tokens=new)
    fut = srv.submit((prompt,))
    t0 = time.perf_counter()
    srv.start()
    try:
        served = np.asarray(fut.result(timeout=600)).reshape(-1)
    finally:
        srv.stop()
    serve_s = time.perf_counter() - t0
    assert len(served) == new, served
    ref_toks, ref_logits = _full_forward_rollout(pred, prompt, new)
    agree = 0
    for got, want, row in zip(served, ref_toks, ref_logits):
        if int(got) != want:
            gap = float(row[want] - row[int(got)])
            assert gap <= TOL_GREEDY_TIE, (
                "decode through slabs and rings left the full-forward "
                "rollout at token %d: %d vs %d, reference logit gap %.4g > "
                "%.4g" % (agree, int(got), want, gap, TOL_GREEDY_TIE))
            break
        agree += 1
    return pred, srv, agree, serve_s, prompt


def phase_laguna(cfg, place):
    """A Laguna-family block: the prompt wraps the ring of a sliding
    layer, then eight steps through slabs and rings, and the experts'
    loads booked. A hang in the expert gather, the ring's slices or the
    window kernel shows here, in seconds."""
    model_dir = os.path.join(OUT_DIR, "laguna_model")
    new = cfg["new_tokens"]
    pred, srv, agree, serve_s, prompt = _serve_described(
        laguna_config(cfg), cfg, place, model_dir)
    pairs = int(srv.moe_load_total.sum())
    assert pairs > 0, "no pair was booked on a held expert"
    if cfg["require_tpu"]:
        text = pred.acquire("prefill", 1, 1024)[0].as_text()
        kernels = re.findall(r"%(ptpu\.[a-z_]+)[.\d]* = ", text)
        assert (kernels.count("ptpu.attn_window") == 3
                and kernels.count("ptpu.flash_fwd") == 2), (
            "the prefill does not run one attention kernel a layer: %r"
            % kernels)
        text = pred.acquire("decode", cfg["slots"], cfg["seq"])[0].as_text()
        kernels = re.findall(r"%(ptpu\.[a-z_]+)[.\d]* = ", text)
        assert (kernels.count("ptpu.decode_attn_grouped") == 2
                and srv._stream_rows), (
            "the decode step does not attend its slabs through the "
            "in-place kernel: %r, stream rows %r"
            % (kernels, srv._stream_rows))
    _emit("laguna", prompt_len=len(prompt), new_tokens=new,
          server_s=serve_s, rollout_tokens_agreeing=agree,
          expert_pairs=pairs,
          load_max_over_mean=float(
              (srv.moe_load_total.max(axis=1)
               / np.maximum(srv.moe_load_total.mean(axis=1), 1e-9)).max()),
          ok=True)
    shutil.rmtree(model_dir, ignore_errors=True)


def phi4flash_config(cfg):
    from paddle_tpu.serving import DecodeConfig

    n = cfg["n_layer"]
    half = n // 2
    kinds = [("mamba" if i % 2 == 0 else "sliding") if i < half
             else "mamba" if i == half else "attention" if i == half + 1
             else "gmu" if i % 2 == 0 else "cross" for i in range(n)]
    return DecodeConfig(
        cfg["vocab"], n_layer=n, n_head=cfg["n_head"],
        d_model=cfg["d_model"], d_inner=cfg["d_inner"], max_len=cfg["seq"],
        tie_embeddings=True, n_kv_head=cfg["n_kv_head"], layer_types=kinds,
        window=cfg["window"], diff_attn=True, attn_biases=True,
        mamba_norms=False, norm="layer_norm", norm_eps=1e-5,
        ffn="gated_silu", positions=False, biases=False)


def phase_phi4flash(cfg, place):
    """A Phi-4-mini-flash-family block: a prefill with the one-row
    shortcut whose prompt wraps the rings, then eight steps through the
    ONE slab (read by the full layer and the cross layers), rings,
    states and the memory. A slab that is copied for its append, or a
    hang in the flat rows' slices, shows here, in seconds. The hybrid
    phase: its prefill program holds ONE call of the selective scan's
    kernel a state-space layer (13 in the Jamba cell's period of 14,
    as many as this block has here) and no `while`."""
    model_dir = os.path.join(OUT_DIR, "phi4flash_model")
    config = phi4flash_config(cfg)
    pred, srv, agree, serve_s, prompt = _serve_described(
        config, cfg, place, model_dir)
    readers = srv._step_counts(np.zeros((cfg["slots"],), np.int32), 0)[
        "slab_readers"]
    assert readers == 1 + config.layer_kinds().count("cross")
    if cfg["require_tpu"]:
        text = pred.acquire("prefill", 1, 1024)[0].as_text()
        kernels = re.findall(r"%(ptpu\.[a-z_]+)[.\d]* = ", text)
        sliding = config.layer_kinds().count("sliding")
        assert (kernels.count("ptpu.attn_window") == sliding
                and kernels.count("ptpu.flash_fwd") == 1), (
            "the prefill does not run one attention kernel a layer that "
            "owns keys: %r" % kernels)
        scans = config.layer_kinds().count("mamba")
        assert (kernels.count("ptpu.ssm_scan") == scans
                and " while(" not in text), (
            "the prefill does not run one selective-scan kernel a "
            "state-space layer (%d): %r" % (scans, kernels))
        text = pred.acquire("decode", cfg["slots"], cfg["seq"])[0].as_text()
        entry = text[text.index("ENTRY"):]
        slab = "f32[%d,%d,%d]" % (cfg["slots"], cfg["seq"],
                                  config.kv_row[0])
        copies = [ln for ln in entry.splitlines()
                  if " copy(" in ln and "= " + slab in ln]
        assert not copies, ("the decode step copies its slab: %s"
                            % copies[0][:200])
        kernels = re.findall(r"%(ptpu\.[a-z_]+)[.\d]* = ", text)
        assert (kernels.count("ptpu.diff_attn_rows") == readers
                and srv._stream_rows), (
            "the slab's readers do not attend it through the kernel over "
            "flat rows: %r, stream rows %r" % (kernels, srv._stream_rows))
    _emit("phi4flash", prompt_len=len(prompt), new_tokens=cfg["new_tokens"],
          server_s=serve_s, rollout_tokens_agreeing=agree,
          slab_readers=readers, ok=True)
    shutil.rmtree(model_dir, ignore_errors=True)


def mistral4_config(cfg):
    from paddle_tpu.ops.mla import softmax_scale
    from paddle_tpu.serving import DecodeConfig

    n = cfg["n_layer"]
    return DecodeConfig(
        cfg["vocab"], n_layer=n, n_head=cfg["n_head"],
        d_model=cfg["d_model"], d_inner=cfg["d_model"], max_len=cfg["seq"],
        tie_embeddings=False, layer_types=["latent"] * n,
        ffn_types=["experts"] * n, q_lora_rank=cfg["q_rank"],
        kv_lora_rank=cfg["kv_rank"], qk_nope_dim=cfg["nope"],
        qk_rope_dim=cfg["rope"], v_head_dim=cfg["v"],
        softmax_scale=softmax_scale(cfg["nope"] + cfg["rope"], 128.0, 1.0),
        rope={"latent": {"theta": 10000.0, "interleave": True,
                         "scale_beta": 0.1,
                         "yarn": {"factor": 128,
                                  "original_max_position": cfg["original"],
                                  "beta_fast": 32, "beta_slow": 1}}},
        n_expert=cfg["n_expert"], expert_top_k=cfg["top_k"],
        d_expert=cfg["d_expert"], d_shared_expert=cfg["d_expert"],
        experts_held=cfg["held"], router_score="softmax", router_scale=1.0,
        norm="rms_norm", norm_eps=1e-6, ffn="gated_silu", positions=False,
        biases=False)


def phase_mistral4(cfg, place):
    """A Mistral-Small-4-family block: ONE prefill by the expanded path
    (the flash kernel at a head of 64 + 64), whose prompt passes YaRN's
    original context, then eight steps by the absorbed path (the kernel
    over the slab's transposed view, one call a layer) through the
    latent slab, against the full-forward rollout (a prefill a token,
    which knows no cache and no absorption). A slab that is copied or
    relaid for its append shows here, in seconds."""
    model_dir = os.path.join(OUT_DIR, "mistral4_model")
    config = mistral4_config(cfg)
    pred, srv, agree, serve_s, prompt = _serve_described(
        config, cfg, place, model_dir)
    counts = srv._step_counts(np.zeros((cfg["slots"],), np.int32), 0)
    assert counts["latent_row_bytes"] == 4 * config.latent_row
    pairs = int(srv.moe_load_total.sum())
    assert pairs > 0, "no pair was booked on a held expert"
    if cfg["require_tpu"]:
        text = pred.acquire("prefill", 1, 1024)[0].as_text()
        kernels = re.findall(r"%(ptpu\.[a-z_]+)[.\d]* = ", text)
        assert kernels.count("ptpu.flash_fwd") == cfg["n_layer"], (
            "the prefill does not run one flash forward a layer: %r"
            % kernels)
        text = pred.acquire("decode", cfg["slots"], cfg["seq"])[0].as_text()
        entry = text[text.index("ENTRY"):]
        slab = "f32[%d,%d,%d]" % (cfg["slots"], cfg["seq"],
                                  config.latent_row)
        view = "f32[%d,%d,%d]" % (cfg["slots"], config.latent_row,
                                  cfg["seq"])  # what the kernel is handed
        copies = [ln for ln in entry.splitlines() if " copy(" in ln
                  and ("= " + slab in ln or "= " + view in ln)]
        assert not copies, ("the decode step copies its latent slab: %s"
                            % copies[0][:200])
        kernels = re.findall(r"%(ptpu\.[a-z_]+)[.\d]* = ", entry)
        assert kernels.count("ptpu.mla_latent_attn") == cfg["n_layer"], (
            "the decode step does not run the absorbed attention's kernel "
            "once a layer: %r" % kernels)
        heads = "f32[%d,%d,%d," % (cfg["slots"], cfg["seq"], cfg["n_head"])
        assert heads not in text, (
            "the decode step builds keys or values of every head")
    _emit("mistral4", prompt_len=len(prompt), new_tokens=cfg["new_tokens"],
          server_s=serve_s, rollout_tokens_agreeing=agree,
          expert_pairs=pairs, latent_row=config.latent_row, ok=True)
    shutil.rmtree(model_dir, ignore_errors=True)


# -- --chips 4: the 2x2-mesh trainer ----------------------------------------

def _parallel_step_text(pexe, feed):
    """Compiled text of the mesh step `pexe` just ran (its jitted step is
    lowered again from avals; jax's compile cache makes that cheap)."""
    import jax

    from paddle_tpu.framework import trace as trace_mod

    (compiled,) = pexe._cache.values()
    sds = jax.ShapeDtypeStruct
    state = {n: pexe._scope.find_var(n) for n in compiled.state_in_names}
    args = ({n: sds(a.shape, a.dtype)
             for n, a in pexe._assemble_feed(feed, None).items()},
            {n: sds(a.shape, a.dtype) for n, a in state.items()},
            jax.eval_shape(lambda: jax.random.PRNGKey(0)),
            sds((), np.uint32))
    with trace_mod.mesh_context(pexe._mesh, pexe._plan):
        return compiled.fn.lower(*args).compile().as_text()


def phase_parallel(cfg, place, steps=3):
    """The training program under ParallelExecutor on a 2x2 (dp, mp) mesh
    with the megatron plan, against the same seeded program on a
    single-device Executor in this process."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.parallel import (ParallelExecutor, make_mesh,
                                     megatron_transformer_plan)

    assert jax.device_count() >= 4, (
        "--chips 4 needs 4 devices, JAX sees %d" % jax.device_count())
    feed = _fixed_batch(cfg)

    def run(parallel):
        main_p, startup, loss = _build_lm(cfg)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor(place).run(startup)
            if not parallel:
                exe = fluid.Executor(place)
                return [float(exe.run(main_p, feed=feed,
                                      fetch_list=[loss])[0])
                        for _ in range(steps)], None
            mesh = make_mesh([2, 2], ("dp", "mp"),
                             devices=jax.devices()[:4])
            pexe = ParallelExecutor(
                loss_name=loss.name, main_program=main_p, scope=scope,
                mesh=mesh, plan=megatron_transformer_plan(mesh))
            losses = [float(pexe.run(feed=feed, fetch_list=[loss])[0])
                      for _ in range(steps)]
            pname = next(n for n in scope.local_var_names()
                         if n.endswith(".fc1.w"))
            w = scope.find_var(pname)
            shards = w.addressable_shards
            info = dict(param=pname, global_shape=list(w.shape),
                        shard_shapes=[list(s.data.shape) for s in shards],
                        shard_devices=[str(s.device) for s in shards])
            assert len({s.device for s in shards}) == 4, info
            # columns over mp; rows over dp, whose one rank owns the update
            assert all(s.data.shape == (w.shape[0] // 2, w.shape[1] // 2)
                       for s in shards), info
            text = _parallel_step_text(pexe, feed)
            info["tpu_custom_calls"] = text.count("tpu_custom_call")
            info["all_reduces"] = len(re.findall(r"all-reduce(?:-start)?\(",
                                                 text))
            assert info["all_reduces"] > 0, "no all-reduce in the mesh step"
            if cfg["require_tpu"]:
                assert info["tpu_custom_calls"] > 0, (
                    "no tpu_custom_call in the mesh step")
            return losses, info

    single, _ = run(parallel=False)
    gc.collect()
    par, info = run(parallel=True)
    assert np.isfinite(single + par).all(), (single, par)
    worst = max(abs(a - b) / abs(a) for a, b in zip(single, par))
    assert worst <= TOL_PARALLEL_LOSS, (
        "2x2 mesh vs single device: losses %r vs %r (rel %.3g > %.3g)"
        % (par, single, worst, TOL_PARALLEL_LOSS))
    _emit("parallel", single_device_losses=single, mesh_losses=par,
          worst_rel_diff=worst, device_count=jax.device_count(), ok=True,
          **info)


# -- driver -----------------------------------------------------------------

def _count_jax_cache_events():
    """Hit/miss counters of jax's persistent compilation cache."""
    import jax

    counts = {"hits": 0, "misses": 0}

    def listen(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    import paddle_tpu as fluid
    from paddle_tpu.runtime import aot_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("chip_smoke: JAX's default device is %r, not a TPU; refusing "
              "to run" % (dev,), file=sys.stderr)
        return 2
    cache_dir = aot_cache.enable_compile_cache()
    cache_events = _count_jax_cache_events()
    _emit("start", chips=args.chips, jax=jax.__version__,
          device_kind=dev.device_kind, device_count=len(jax.devices()),
          compile_cache_dir=cache_dir,
          aot_cache_dir=aot_cache.default_cache_dir())
    place = fluid.TPUPlace()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_parallel(FULL, place)
    else:
        phase_kernels(FULL)
        phase_kda(KDA)
        phase_train(FULL, place)
        gc.collect()
        phase_serve(FULL, place)
        gc.collect()
        phase_laguna(LAGUNA, place)
        gc.collect()
        phase_phi4flash(PHI4FLASH, place)
        gc.collect()
        phase_mistral4(MISTRAL4, place)
    _emit("done", seconds=time.perf_counter() - t0,
          jax_cache_hits=cache_events["hits"],
          jax_cache_misses=cache_events["misses"])
    count = len(jax.devices())
    assert count == args.chips, (
        "asked for %d chip(s), JAX sees %d" % (args.chips, count))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
