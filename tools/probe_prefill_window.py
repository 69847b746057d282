#!/usr/bin/env python
"""Chip probe: a serving prefill's attention call alone (`ops/attention.py`
`prefill_attention`), the PARENT's form against the one PR 57 gave it, at
the shapes of the cells that run it with a window, a sink or fewer
key/value heads than query heads:

- `mimo-swa-16k`, `mimo-swa-2k`: MiMo-V2-Flash's sliding layer, (1, 16,384)
  and (1, 2,048) x 64 query heads on 8 key/value heads of 192 / 128 under
  a window of 128 with a learned sink a head;
- `mimo-full-16k`: its full layer, 64 on 4, no window, no sink;
- `laguna-swa`: Laguna-XS.2's sliding layer, (2, 2,048) x 48 on 8 of 128
  under a window of 512.

`parent` is what `prefill_attention` did before PR 57, rebuilt here from
the package's own pieces: K and V repeated to the query heads in HBM
(`jnp.repeat`), blocks of 512 / 512 whatever the window, and the sink as
a pass of XLA's over the kernel's output and a transposed `lse`
(`parent-nosink`, on a shape with a sink: the same without that pass, so
that the pass is the difference of the two). `change` is the entry as it
is: K and V at their own head count under the index map `hi // group`,
the blocks `prefill_blocks` gives the window, the sink in the kernel's
last write. Under a window two more families, the same call at blocks
given by hand: `band-<bq>`, a q-block of `bq` rows against the ONE key
block it sees, in one pass (each `--block-q`; the rule's choice is one
of them), and `walk-<bq>x<bk>` (`--walk`), the running softmax over key
blocks of `bk`, which is what ISSUE 57 asked for first (256 x 128) and
what the probe read no faster than the parent's 512 x 512. LAYERS calls are chained in one jitted function, each on its own
q, as a prefill holds its layers: a lone call's dispatch, ~0.4 ms on the
chip's machine, would read as the kernel at the 2,048 bucket. Prints ms
a call and the change's largest difference from the parent's output;
with `--trace 1` (the default) one more run of each form under the
profiler, the device's operations by name in ms a call (the kernel apart
from the passes beside it); writes
`chiprun_out/probe_prefill_window.json`.

    chiprun -- python tools/probe_prefill_window.py [--block-q 128,256,512]

`--cpu 1 --shapes tiny` rehearses it here in interpret mode (no times are
printed under the device's names). Without it the probe refuses to run
where no TPU is visible."""
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

from paddle_tpu.ops import attention as A  # noqa: E402

LAYERS = 4

# name -> batch, rows, (query heads, key/value heads), (dq, dv), window,
# sink?, live rows of each row of the batch
SHAPES = {
    "mimo-swa-16k": (1, 16384, (64, 8), (192, 128), 128, True, [15000]),
    "mimo-swa-2k": (1, 2048, (64, 8), (192, 128), 128, True, [1900]),
    "mimo-full-16k": (1, 16384, (64, 4), (192, 128), 0, False, [15000]),
    "laguna-swa": (2, 2048, (48, 8), (128, 128), 512, False, [2048, 1500]),
    "tiny": (2, 512, (4, 2), (24, 16), 128, True, [512, 300]),
}


def parent_form(q, k, v, lengths, window, sink, interpret):
    """`prefill_attention` as it was before PR 57 (its kernel path)."""
    b, t, h, dq = q.shape
    group = h // k.shape[2]
    block = A._fit_block(t, A._FLASH_BLOCK)
    name = A.ATTN_WINDOW if window else A.FLASH_FWD

    def repeated(x):
        x = jnp.repeat(A.flash_operand(x).reshape(b, t, x.shape[2], -1),
                       group, axis=2)
        return x.reshape(b, t, -1)

    out, lse = A._mha_fwd_call_bthd(
        A.flash_operand(q * jnp.asarray(1.0 / np.sqrt(dq), q.dtype)),
        repeated(k), repeated(v), h, True, block, block, interpret,
        window=window, name=name, lengths=lengths, out_dtype=q.dtype)
    out = out.reshape(b, t, h, -1)[..., :v.shape[-1]]
    if sink is None:
        return out
    share = A.sink_share(jnp.swapaxes(lse.reshape(b, h, t), 1, 2), sink)
    return (out * share[..., None]).astype(out.dtype)


def by_hand(q, k, v, lengths, window, sink, block_q, block_k, band,
            interpret):
    """`prefill_attention`'s kernel path at blocks given by hand."""
    b, t, h, dq = q.shape
    out, _ = A._mha_fwd_call_bthd(
        A.flash_operand(q * jnp.asarray(1.0 / np.sqrt(dq), q.dtype)),
        A.flash_operand(k), A.flash_operand(v), h, True, block_q, block_k,
        interpret, window=window, name=A.ATTN_WINDOW, lengths=lengths,
        out_dtype=q.dtype, group=h // k.shape[2], sink=sink, band=band)
    return out.reshape(b, t, h, -1)[..., :v.shape[-1]]


def chained(form, window, interpret):
    """A jitted function of LAYERS calls, each on its own q ->
    (LAYERS, B, T, H, dv)."""
    def run(q, k, v, lengths, sink):
        outs = []
        for i in range(LAYERS):
            qi = q * (1.0 + 0.125 * i)
            if form.startswith("parent"):
                outs.append(parent_form(
                    qi, k, v, lengths, window,
                    None if form == "parent-nosink" else sink, interpret))
            elif form == "change":
                outs.append(A.prefill_attention(
                    qi, k, v, lengths, window=window, sink=sink,
                    interpret=interpret))
            else:
                outs.append(by_hand(qi, k, v, lengths, window, sink,
                                    *blocks_of(form, window), interpret))
        return jnp.stack(outs)
    return jax.jit(run)


def blocks_of(form, window):
    """(block_q, block_k, band) of `band-<bq>` / `walk-<bq>x<bk>`."""
    kind, blocks = form.split("-")
    if kind == "walk":
        bq, bk = (int(x) for x in blocks.split("x"))
        return bq, bk, False
    return int(blocks), int(blocks) + A.band_back(window), True


def timed(f, args, n):
    """(seconds a call, the last outputs)."""
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (n * LAYERS), out


def device_ops(f, args, logdir):
    """{operation (its number dropped): ms a call} of one traced run of
    ``f``, the eight largest: which of a form's time is the kernel and
    which the passes of XLA's beside it."""
    import re
    import shutil

    from benchmark.lib.trace_reduce import load_xplane

    shutil.rmtree(logdir, ignore_errors=True)
    with jax.profiler.trace(logdir):
        jax.block_until_ready(f(*args))
    ms = {}
    for events in load_xplane(logdir)["devices"].values():
        for name, _, dur, _ in events:
            name = re.sub(r"\.\d+$", "", name)
            ms[name] = ms.get(name, 0.0) + dur / 1e6 / LAYERS
    return dict(sorted(ms.items(), key=lambda kv: -kv[1])[:8])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mimo-swa-16k,mimo-swa-2k,"
                    "mimo-full-16k,laguna-swa")
    ap.add_argument("--block-q", default="128,256,512")
    ap.add_argument("--walk", default="256x128")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=57)
    ap.add_argument("--cpu", type=int, default=0)
    ap.add_argument("--trace", type=int, default=1,
                    help="one more run of each form under the profiler")
    a = ap.parse_args()
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if dev.platform != "tpu" and not a.cpu:
        print("no TPU here: the probe measures the chip", flush=True)
        return 2
    faulthandler.dump_traceback_later(800, exit=True)
    out = {"device": [dev.platform, dev.device_kind], "layers": LAYERS,
           "rows": []}
    for name in a.shapes.split(","):
        b, t, (h, hkv), (dq, dv), window, sinks, live = SHAPES[name]
        keys = jax.random.split(jax.random.PRNGKey(a.seed), 4)
        q, k, v = (jax.random.normal(key, shape, jnp.float32)
                   for key, shape in zip(keys, ((b, t, h, dq),
                                                (b, t, hkv, dq),
                                                (b, t, hkv, dv))))
        sink = (1.0 + jax.random.normal(keys[3], (h,), jnp.float32)
                if sinks else None)
        lengths = jnp.asarray(live, jnp.int32)
        args = (q, k, v, lengths, sink)
        forms = ["parent"] + (["parent-nosink"] if sinks else []) + [
            "change"]
        if window:
            forms += ["band-" + bq for bq in a.block_q.split(",") if bq]
            forms += ["walk-" + w for w in a.walk.split(",") if w]
        want = None
        for form in forms:
            row = {"shape": name, "q": [b, t, h, dq],
                   "kv_heads": hkv, "dv": dv, "window": window,
                   "sink": sinks, "live": live, "form": form}
            row["blocks"] = list(
                A.prefill_blocks(window, t) if form == "change"
                else blocks_of(form, window) if form[:5] in ("band-", "walk-")
                else (A._fit_block(t, A._FLASH_BLOCK),) * 2 + (False,))
            try:
                f = chained(form, window, bool(a.cpu))
                sec, got = timed(f, args, 1 if a.cpu else a.calls)
            except Exception as e:  # a block the compiler refuses
                row["err"] = str(e)[-600:]
            else:
                if not a.cpu:
                    row["ms_a_call"] = sec * 1e3
                    if a.trace:
                        row["device_ms_a_call"] = device_ops(
                            f, args, "chiprun_out/probe_prefill_window_trace")
                # a row's live positions only: what is past them is
                # whatever a form left there, and no one reads it
                got = [got[:, i, :n] for i, n in enumerate(live)]
                row["finite"] = bool(all(jnp.isfinite(g).all() for g in got))
                if form == "parent":
                    want = got
                elif want is not None:
                    row["largest_difference_from_parent"] = float(max(
                        jnp.abs(g - w).max() for g, w in zip(got, want)))
                    row["parent_largest"] = float(max(
                        jnp.abs(w).max() for w in want))
                del got
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
        del q, k, v, args, want
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_prefill_window.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
