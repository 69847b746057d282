#!/usr/bin/env python
"""Chip probe: the delta rule's one-token step alone (`ops/kda.py`), the
lax form (`_update`) against the Pallas kernel (`pallas_kda_step`), at
the Ling-3.0-flash cell's step: 64 slots, 32 heads of a 128 x 128
float32 state, the gate's bound -5. FIVE updates, of five states, are
chained in one jitted call with the states donated, as a decode step
holds them (each layer's v waits for the last one's o): a lone call's
dispatch, ~0.4 ms on the chip's machine, would read as the kernel
(`PERF.md` 6, PR 41). Prints ms a layer for the lax form and for the
kernel by heads a grid cell, GB/s of the state's one read and one write,
and the two forms' largest difference; beside them the STREAM alone, a
kernel of the same blocks that scales the states in place and does
nothing else: what the chip allows a call that reads and writes them
once. Writes `chiprun_out/kda_step_probe.json`.

    chiprun -- python tools/kda_step_probe.py [--heads 8,16,32]

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
from jax.experimental import pallas as pl  # noqa: E402

from paddle_tpu.ops import attention as A  # noqa: E402
from paddle_tpu.ops import kda as K  # noqa: E402

B, H, DK, DV, BOUND = 64, 32, 128, 128, -5.0
LAYERS = 5


def operands(rng, bsz, h, dk, dv):
    """q, k, v as a SiLU's outputs (q and k then as the recurrence takes
    them), g as the gate's with `dt_bias` N(-4.6, 1.3) and some channels
    at the bound, beta in (0, 1); states as a prefill's scan leaves
    them, of the order of 1."""
    def silu(x):
        return x / (1.0 + np.exp(-x))
    q, k = (silu(rng.normal(size=(bsz, h, dk))).astype(np.float32)
            for _ in range(2))
    v = silu(rng.normal(size=(bsz, h, dv))).astype(np.float32)
    x = rng.normal(-4.6, 1.3, size=(1, h, dk)) + rng.normal(
        size=(bsz, h, dk))
    g = (BOUND / (1.0 + np.exp(-x))).astype(np.float32)
    g[..., ::17] = BOUND * 0.9999
    beta = rng.uniform(0.02, 0.98, size=(bsz, h)).astype(np.float32)
    q, k = K._prepare(jnp.asarray(q), jnp.asarray(k), True)
    return q, k, jnp.asarray(v), jnp.asarray(g), jnp.asarray(beta)


def states(seed, bsz, h, dk, dv):
    """The layers' states, made on the device (670 MB at the cell's)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), LAYERS)
    return tuple(0.3 * jax.random.normal(key, (bsz, h, dk, dv), jnp.float32)
                 for key in keys)


def stream(state, q, k, v, g, beta, heads):
    """The states scaled in place, in the step kernel's blocks: the
    stream's own time. -> (v, the states scaled)."""
    def kernel(s_ref, so_ref):
        so_ref[...] = s_ref[...] * 0.999

    spec = pl.BlockSpec((1, heads) + state.shape[2:],
                        lambda bi, hi: (bi, hi, 0, 0))
    return v, pl.pallas_call(
        kernel, grid=(state.shape[0], state.shape[1] // heads),
        in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        input_output_aliases={0: 0},
        **A._tpu_params("parallel", "parallel"))(state)


def chained(update):
    """A jitted function that runs ``update`` on each of the states, one
    after the other, the states donated -> (the last o, the new
    states)."""
    def run(sts, q, k, v, g, beta):
        out = []
        for s in sts:
            o, s = update(s, q, k, v, g, beta)
            v = v + 0.0 * o
            out.append(s)
        return o, tuple(out)
    return jax.jit(run, donate_argnums=(0,))


def timed(f, sts, args, n):
    """(seconds a layer, the last o, the states after ``n + 2`` calls)."""
    for _ in range(2):
        o, sts = f(sts, *args)
    jax.block_until_ready(sts)
    t0 = time.perf_counter()
    for _ in range(n):
        o, sts = f(sts, *args)
    jax.block_until_ready((o, sts))
    return (time.perf_counter() - t0) / (n * LAYERS), o, sts


def apart(got, want):
    """The largest difference over the largest number."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", default="8,16,32")
    ap.add_argument("--shape", default="%d,%d,%d,%d" % (B, H, DK, DV))
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--seed", type=int, default=42)
    a = ap.parse_args()
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if dev.platform != "tpu":
        print("no TPU here: the probe measures the chip", flush=True)
        return 2
    faulthandler.dump_traceback_later(800, exit=True)
    bsz, h, dk, dv = (int(v) for v in a.shape.split(","))
    args = operands(np.random.default_rng(a.seed), bsz, h, dk, dv)
    nbytes = 2 * bsz * h * dk * dv * 4
    out = {"device": [dev.platform, dev.device_kind],
           "shape": [bsz, h, dk, dv], "layers": LAYERS, "rows": []}

    sec, want_o, want_s = timed(chained(K._update),
                                states(a.seed, bsz, h, dk, dv), args,
                                a.calls)
    want_s = [np.asarray(s) for s in want_s]
    row = {"form": "lax", "ms_a_layer": sec * 1e3,
           "state_gb_per_s": nbytes / sec / 1e9}
    out["rows"].append(row)
    print(json.dumps(row), flush=True)
    for hb in (int(v) for v in a.heads.split(",")):
        for form, update in (
                ("stream", lambda *o, hb=hb: stream(*o, heads=hb)),
                ("kernel", lambda *o, hb=hb: K.pallas_kda_step(
                    *o, heads=hb))):
            row = {"form": form, "heads": hb}
            try:
                sec, o, sts = timed(chained(update),
                                    states(a.seed, bsz, h, dk, dv), args,
                                    a.calls)
            except Exception as e:  # a block the compiler refuses
                row["err"] = str(e)[-600:]
            else:
                row.update(ms_a_layer=sec * 1e3,
                           state_gb_per_s=nbytes / sec / 1e9)
                if form == "kernel":
                    row.update(
                        o_from_lax=apart(o, want_o),
                        state_from_lax=max(apart(s, w)
                                           for s, w in zip(sts, want_s)),
                        finite=bool(jnp.all(jnp.isfinite(o))))
                del sts
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_step_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
