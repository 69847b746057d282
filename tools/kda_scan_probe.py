#!/usr/bin/env python
"""Chip probe: the chunked delta rule alone (`ops/kda.py`), the lax form
against the Pallas kernel, at Ling-3.0-flash's head sizes (32 heads of a
128 x 128 state, the gate's bound -5) over the admissions of
`agent-closed-2x-any` (8 x 512 .. 8 x 2,048, 4 x 4,096, 2 x 8,192,
1 x 16,384), lengths drawn as the mix draws them (log-normal, median
2,048, sigma 0.9, clipped to 512-12,288; a batch whose longest prompt
falls in the bucket). Prints us a token a layer for each, per BUCKET
token and per LIVE token, how far the two forms are apart and how far
each is from the lax form at `highest` precision; writes
`chiprun_out/kda_scan_probe.json`. `--guarded 1` probes the GUARDED
form of both (a gate with no bound: the Solar-Open2-250B cell's, with
`--heads 64 --admissions 8x512,8x1024,8x2048,4x4096`): g as Kimi
Linear's softplus gate makes it, a seventh of the channels at -40 to
-100 a token, beta in (0, 2); `--lax-up-to` bucket tokens the composed
lax form is run at all (a chunk at a time, it takes seconds at 16,384).

    chiprun -- python tools/kda_scan_probe.py [--blocks 256,512]

Refuses to run where no TPU is visible: a time from the CPU's backend
or the interpreter says nothing about the chip."""
import argparse
import faulthandler
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops import kda as K  # noqa: E402

H, DK, BOUND = 32, 128, -5.0
ADMISSIONS = "8x512,8x1024,8x2048,4x4096,2x8192,1x16384"

# scans a jitted call chains (each reads a v that depends on the last
# one's o): the host's dispatch of a call, ~0.4 ms on the chip's machine,
# is paid once for them and not read as the scan's time
CHAIN = 4


def mix_lengths(rng, bsz, t):
    """A batch of the mix's prompt lengths whose longest lies in the
    bucket (t / 2, t]."""
    def draw(lo):
        while True:
            n = int(np.clip(np.exp(rng.normal(np.log(2048), 0.9)), 512,
                            12288))
            if lo < n <= t:
                return n
    # the longest decides the bucket; the others are whatever was queued
    # beside it and fits
    return np.array([draw(t // 2)] + [draw(0) for _ in range(bsz - 1)],
                    np.int32)


def operands(rng, bsz, t, guarded=False):
    """q, k, v as a SiLU's outputs, g as the gate's with `dt_bias`
    N(-4.6, 1.3), some channels at the bound, beta in (0, 1): flat
    (B, T, H * d) as the projections leave them. ``guarded``: g as the
    unbounded gate's (a head's rate in [1, 16], a channel's step
    log-uniform in [1e-3, 0.1] times e^N(0, 1)), a seventh of the
    channels at -40 to -100 a token, beta in (0, 2)."""
    def silu(x):
        return x / (1.0 + np.exp(-x))
    q, k, v = (silu(rng.normal(size=(bsz, t, H * DK))).astype(np.float32)
               for _ in range(3))
    if guarded:
        rate = np.repeat(rng.uniform(1.0, 16.0, size=H), DK)
        step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), size=H * DK)
                      + rng.normal(size=(bsz, t, H * DK)))
        g = (-rate * step).astype(np.float32)
        deep = g[..., ::7]
        g[..., ::7] = rng.uniform(-100.0, -40.0, size=deep.shape)
        beta = rng.uniform(0.02, 1.98, size=(bsz, t, H)).astype(np.float32)
        return tuple(jnp.asarray(a) for a in (q, k, v, g, beta))
    x = rng.normal(-4.6, 1.3, size=(1, 1, H * DK)) + rng.normal(
        size=(bsz, t, H * DK))
    g = (BOUND / (1.0 + np.exp(-x))).astype(np.float32)
    g[..., ::17] = BOUND * 0.9999      # whole chunks at the bound
    beta = rng.uniform(0.02, 0.98, size=(bsz, t, H)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, k, v, g, beta))


def chained(scan, chain=CHAIN):
    """A jitted function that runs ``scan`` ``chain`` times, one after
    the other, and returns the last (o, state)."""
    def run(q, k, v, g, beta, lens):
        shape = q.shape[:2] + (H, DK)
        q, k, v, g = (a.reshape(shape) for a in (q, k, v, g))
        for _ in range(chain):
            o, st = scan(q, k, v, g, beta, lens)
            v = v + 0.0 * o
        return o, st
    return jax.jit(run)


def timed(f, args, n, chain=CHAIN):
    """Seconds a scan: ``n`` calls of a chained function."""
    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (n * chain)


def apart(got, want, mask):
    """(norm of the difference over the norm, largest difference over
    the largest number), over the live positions."""
    d = np.where(mask, np.asarray(got) - np.asarray(want), 0.0)
    w = np.where(mask, np.asarray(want), 0.0)
    return (float(np.linalg.norm(d) / np.linalg.norm(w)),
            float(np.abs(d).max() / np.abs(w).max()))


def main():
    global H
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default=str(K._KERNEL_BLOCK_T))
    ap.add_argument("--state-passes", default="1")
    ap.add_argument("--admissions", default=ADMISSIONS)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--heads", type=int, default=H)
    ap.add_argument("--guarded", type=int, choices=(0, 1), default=0)
    ap.add_argument("--lax-up-to", type=int, default=1 << 30)
    a = ap.parse_args()
    H = a.heads
    guarded = bool(a.guarded)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if dev.platform != "tpu":
        print("no TPU here: the probe measures the chip", flush=True)
        return 2
    faulthandler.dump_traceback_later(1200, exit=True)
    rng = np.random.default_rng(a.seed)
    out = {"device": [dev.platform, dev.device_kind], "rows": []}

    def lax_form(*ops):
        return K._kda_scan_lax(*ops, guarded, True)

    lax_f = chained(lax_form)
    truth_f = chained(lax_form, 1)
    for adm in a.admissions.split(","):
        bsz, t = (int(v) for v in adm.split("x"))
        lens = mix_lengths(rng, bsz, t)
        args = operands(rng, bsz, t, guarded) + (jnp.asarray(lens),)
        live, bucket = int(lens.sum()), bsz * t
        row = {"batch": bsz, "bucket": t, "live": live, "heads": H,
               "guarded": guarded, "lengths": lens.tolist()}
        n = max(2, min(10, int(0.5 / (CHAIN * bucket * 2e-6))))
        with_lax = bucket <= a.lax_up_to
        mask = (np.arange(t)[None, :] < lens[:, None])[..., None, None]
        if with_lax:
            sec = timed(lax_f, args, n)
            row["lax_us_per_bucket_tok"] = sec / bucket * 1e6
            row["lax_us_per_live_tok"] = sec / live * 1e6
            want_o, want_s = lax_f(*args)
            with jax.default_matmul_precision("highest"):
                true_o, true_s = truth_f(*args)
            row["lax_o_from_highest"] = apart(want_o, true_o, mask)
            row["lax_state_from_highest"] = apart(want_s, true_s, True)
        for bt in (int(v) for v in a.blocks.split(",")):
            for sp in (int(v) for v in a.state_passes.split(",")):
                tag = "k%d_s%d" % (bt, sp)
                if t % bt:
                    continue
                f = chained(lambda *o, bt=bt, sp=sp: K.pallas_kda_scan(
                    *o, block_t=bt, state=sp, guarded=guarded))
                try:
                    sec = timed(f, args, 2 * n)
                    got_o, got_s = f(*args)
                except Exception as e:  # a block the compiler refuses
                    row[tag + "_err"] = str(e)[-600:]
                    continue
                row[tag + "_us_per_bucket_tok"] = sec / bucket * 1e6
                row[tag + "_us_per_live_tok"] = sec / live * 1e6
                row[tag + "_finite"] = bool(jnp.all(jnp.isfinite(got_o)))
                if not with_lax:
                    continue
                row[tag + "_o_from_lax"] = apart(got_o, want_o, mask)
                row[tag + "_state_from_lax"] = apart(got_s, want_s, True)
                row[tag + "_o_from_highest"] = apart(got_o, true_o, mask)
                row[tag + "_state_from_highest"] = apart(got_s, true_s, True)
        out["rows"].append(row)
        print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_scan_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
