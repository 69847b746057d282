#!/usr/bin/env python
"""Chip probe: the selective scan alone (`ops/ssm.py`), the lax form
against the Pallas kernel, at the hybrid cells' widths (`d_inner` 5,120,
`d_state` 16) over the prefill buckets (B, T) in {1, 2, 4, 8} x {256,
512, 1024, 2048}, lengths drawn as `chat-closed-2x-any` draws them
(log-normal, median 384, sigma 0.8, clipped to 64-1,536; a batch whose
longest prompt falls in the bucket). Prints us a position a row for
each, per BUCKET position and per LIVE position, and how far the two
forms are apart; writes `chiprun_out/ssm_scan_probe.json`.

    chiprun -- python tools/ssm_scan_probe.py [--blocks 128x512,256x1024]

Refuses to run where no TPU is visible: a time from the CPU's backend
or the interpreter says nothing about the chip."""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops import ssm as S  # noqa: E402

DI, N = 5120, 16


def mix_lengths(rng, bsz, t):
    """A batch of the mix's prompt lengths whose longest lies in the
    bucket (t / 2, t]."""
    while True:
        lens = np.clip(np.exp(rng.normal(np.log(384), 0.8, bsz)), 64,
                       1536).astype(np.int32)
        if t // 2 < lens.max() <= t:
            return lens


# scans a jitted call chains (each reads a D that depends on the last
# one's y): the host's dispatch of a call, ~0.4 ms on the chip's machine,
# is paid once for them and not read as the scan's time
CHAIN = 8


def chained(scan):
    """A jitted function that runs ``scan`` CHAIN times, one after the
    other, and returns the last (y, state (B, Di, N))."""
    def run(x, dt, am, b, c, d, lens):
        for _ in range(CHAIN):
            y, st = scan(x, dt, am, b, c, d, lens)
            d = d + 0.0 * y[0, 0]
        return y, jnp.swapaxes(st, 1, 2)
    return jax.jit(run)


def timed(f, args, n):
    """Seconds a scan: ``n`` calls of a chained function."""
    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (n * CHAIN)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="%dx%d" % (S._KERNEL_BLOCK_T,
                                                    S._KERNEL_BLOCK_D))
    ap.add_argument("--batches", default="1,2,4,8")
    ap.add_argument("--buckets", default="256,512,1024,2048")
    ap.add_argument("--seed", type=int, default=41)
    a = ap.parse_args()
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if dev.platform != "tpu":
        print("no TPU here: the probe measures the chip", flush=True)
        return 2
    blocks = [tuple(int(v) for v in b.split("x"))
              for b in a.blocks.split(",")]
    rng = np.random.default_rng(a.seed)
    out = {"device": [dev.platform, dev.device_kind], "rows": []}

    lax_f = chained(S._ssm_scan_lax)
    for t in (int(v) for v in a.buckets.split(",")):
        for bsz in (int(v) for v in a.batches.split(",")):
            lens = mix_lengths(rng, bsz, t)
            x = jnp.asarray(rng.normal(size=(bsz, t, DI)), jnp.float32)
            dt = jnp.asarray(np.abs(rng.normal(size=(bsz, t, DI))) * 0.05
                             + 0.001, jnp.float32)
            am = jnp.asarray(-np.exp(rng.normal(size=(DI, N)) * 0.5),
                             jnp.float32)
            b = jnp.asarray(rng.normal(size=(bsz, t, N)), jnp.float32)
            c = jnp.asarray(rng.normal(size=(bsz, t, N)), jnp.float32)
            d = jnp.asarray(rng.normal(size=(DI,)), jnp.float32)
            args = (x, dt, am, b, c, d, jnp.asarray(lens))
            live, bucket = int(lens.sum()), bsz * t
            row = {"batch": bsz, "bucket": t, "live": live,
                   "lengths": lens.tolist()}
            n = max(2, min(20, int(0.5 / (CHAIN * bucket * 1.6e-6))))
            sec = timed(lax_f, args, n)
            row["lax_us_per_bucket_pos"] = sec / bucket * 1e6
            row["lax_us_per_live_pos"] = sec / live * 1e6
            want_y, want_s = lax_f(*args)
            mask = (np.arange(t)[None, :] < lens[:, None])[..., None]
            for bt, bd in blocks:
                tag = "k%dx%d" % (bt, bd)
                if t % bt:
                    continue
                f = chained(lambda *o, bt=bt, bd=bd: S.pallas_ssm_scan(
                    *o, block_t=bt, block_d=bd))
                try:
                    sec = timed(f, args, 4 * n)
                    got_y, got_s = f(*args)
                except Exception as e:  # a block the compiler refuses
                    row[tag + "_err"] = str(e)[-400:]
                    continue
                row[tag + "_us_per_bucket_pos"] = sec / bucket * 1e6
                row[tag + "_us_per_live_pos"] = sec / live * 1e6
                dy = np.where(mask, np.asarray(got_y - want_y), 0.0)
                row[tag + "_y_rel"] = float(
                    np.linalg.norm(dy)
                    / np.linalg.norm(np.where(mask, want_y, 0.0)))
                row[tag + "_state_rel"] = float(
                    jnp.linalg.norm(got_s - want_s)
                    / jnp.linalg.norm(want_s))
                row[tag + "_finite"] = bool(jnp.all(jnp.isfinite(got_y)))
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssm_scan_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
