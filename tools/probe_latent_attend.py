#!/usr/bin/env python
"""Chip probe: the absorbed latent attention's kernel alone (`ops/mla.py`
`pallas_latent_attend` -> `ops/decode_stream.py` `stream_attend` under
`latent_view`), at the two cells' shapes that run it: Mistral-Small-4's
step (32 slots of 16,384 rows of 320 floats, 256 of them summed, 32
heads; prompts log-normal median 3,072, sigma 0.8, 512-15,360) and
Ling-3.0-flash's (64 slots of 16,384 rows of 576, 512 summed, 32 heads;
median 2,048, sigma 0.9, 512-12,288), each slot some way into its reply.
Two bodies on the same slab, lengths and queries: `twice`, the body that
fetches each live block a pass (the parent's: `_KEPT_VMEM_CAP` set to 0
for the trace, so the rule refuses to keep anything), and `once`, the
body that keeps a block's summed rows in VMEM between its passes (PR
55). FOUR calls are chained in one jitted function, as a decode step
holds its latent layers: a lone call's dispatch, ~0.4 ms on the chip's
machine, would read as the kernel (`PERF.md` 6, PR 41). Prints ms a
call, GB/s of the live rows counted ONCE (the roofline's bytes: 819 GB/s
is the chip's), the two bodies' largest difference and each one's
distance from the exact lax form. Writes
`chiprun_out/probe_latent_attend.json`.

    chiprun -- python tools/probe_latent_attend.py [--lanes 1024,2048]

`--cpu 1` rehearses it here at a tiny size in interpret mode (no times
are printed under the device's names). Without it the probe refuses to
run where no TPU is visible: a time from the CPU's backend or the
interpreter says nothing about the chip."""
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

from paddle_tpu.ops import decode_stream as DS  # noqa: E402
from paddle_tpu.ops import mla  # noqa: E402

LAYERS = 4
HBM_GB_PER_S = 819.0

# name -> slots, positions, heads, row, rank, the prompts' log-normal
# (median, sigma, min, max) and the most tokens a reply has grown by
SHAPES = {
    "mistral": (32, 16384, 32, 320, 256, (3072, 0.8, 512, 15360), 512),
    "ling": (64, 16384, 32, 576, 512, (2048, 0.9, 512, 12288), 2048),
    "tiny": (4, 512, 8, 40, 32, (96, 0.8, 16, 480), 16),
}


def lengths(rng, b, s, prompts, grown):
    """Live rows a slot: a prompt of the cell's mix and a reply some way
    in."""
    median, sigma, lo, hi = prompts
    n = np.clip(rng.lognormal(np.log(median), sigma, size=b), lo, hi)
    n = n + rng.integers(0, grown + 1, size=b)
    return np.minimum(n.astype(np.int32), s)


def chained(rank, lanes, interpret):
    """A jitted function of LAYERS calls over one slab, each with a
    query of its own -> (LAYERS, B, H, rank)."""
    def run(q_row, slab, lens):
        return jnp.stack([
            mla.pallas_latent_attend(q_row * (1.0 + 0.125 * i), slab, lens,
                                     rank, block_s=lanes, interpret=interpret)
            for i in range(LAYERS)])
    return jax.jit(run)


def timed(f, args, n):
    """(seconds a call, the outputs)."""
    for _ in range(3):
        out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (n * LAYERS), out


def apart(got, want):
    """The distance over the reference's norm."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mistral,ling")
    ap.add_argument("--lanes", default="1024")
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--seed", type=int, default=19)
    ap.add_argument("--cpu", type=int, default=0)
    a = ap.parse_args()
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if dev.platform != "tpu" and not a.cpu:
        print("no TPU here: the probe measures the chip", flush=True)
        return 2
    faulthandler.dump_traceback_later(800, exit=True)
    out = {"device": [dev.platform, dev.device_kind], "layers": LAYERS,
           "rows": []}
    cap = DS._KEPT_VMEM_CAP
    for name in a.shapes.split(","):
        b, s, h, row, rank, prompts, grown = SHAPES[name]
        rng = np.random.default_rng(a.seed)
        lens = lengths(rng, b, s, prompts, grown)
        key_q, key_c = jax.random.split(jax.random.PRNGKey(a.seed))
        slab = jax.random.normal(key_c, (b, s, row), jnp.float32)
        q_row = 0.05 * jax.random.normal(key_q, (b, h, row), jnp.float32)
        args = (q_row, slab, jnp.asarray(lens))
        want = np.stack([np.asarray(mla._latent_attend_lax(
            q_row * (1.0 + 0.125 * i), slab, args[2], rank))
            for i in range(LAYERS)])
        live = int(lens.sum())
        for lanes in (int(v) for v in a.lanes.split(",")):
            fetched = int((-(-lens // lanes) * lanes).sum())
            got = {}
            for body in ("twice", "once"):
                row_out = {"shape": name, "slab": [b, s, row], "rank": rank,
                           "heads": h, "lanes": lanes, "body": body,
                           "live_rows": live, "rows_in_live_blocks": fetched}
                DS._KEPT_VMEM_CAP = 0 if body == "twice" else cap
                try:
                    sec, o = timed(chained(rank, lanes, bool(a.cpu)), args,
                                   2 if a.cpu else a.calls)
                except Exception as e:  # a block the compiler refuses
                    row_out["err"] = str(e)[-600:]
                else:
                    got[body] = np.asarray(o)
                    if not a.cpu:
                        row_out.update(
                            ms_a_call=sec * 1e3,
                            live_gb_per_s=live * row * 4 / sec / 1e9,
                            roofline_pct=100.0 * live * row * 4
                            / (HBM_GB_PER_S * 1e9) / sec)
                    row_out.update(from_lax=apart(got[body], want),
                                   finite=bool(np.isfinite(got[body]).all()))
                    if len(got) == 2:
                        row_out["largest_difference_from_twice"] = float(
                            np.abs(got["once"] - got["twice"]).max())
                finally:
                    DS._KEPT_VMEM_CAP = cap
                out["rows"].append(row_out)
                print(json.dumps(row_out), flush=True)
        del slab, args
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_latent_attend.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
