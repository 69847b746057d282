"""sha256 of the LOWERED text (StableHLO, no locations) of every serving
cell's decode step and one prefill, and of one training step of the LM
(``tests/test_tpu_compile.py``'s: chip_smoke.py's model at one layer,
AMP O2, the differentiable flash kernels; the backward is the one its
shape gets with nothing set, the fused kernel, which is what both OPT
training cells run: since PR 56 this line is of the program a cell
hands the chip, where before it it was of the split pair unless the
caller set the environment switch that PR removed), lowered for a
described ``v5e:2x2`` with the Pallas paths steered on (``tests/
test_tpu_compile_cells.py::_cell_predictor``): no chip, nothing
compiled. Two trees whose tables agree hand the chip the same programs, Mosaic kernels included, so a
refactoring of a kernel is checked here before a chip minute is spent.

A Mosaic call carries its kernel as serialized MLIR, and that carries
the LOCATIONS of the Python that traced it (the tree's path, file names,
function names, lines), which move with any edit and mean nothing to the
chip. Each body is therefore parsed and written back without them before
the text is hashed; what is left of a kernel is its operations, its
block shapes and index maps, and the module's name (the kernel
function's; ``--no-kernel-names`` writes every one as ``kernel``, to see
past a kernel function renamed).

    python tools/lowered_hashes.py [TREE] [--json OUT] [--text DIR]
                                   [--no-kernel-names]

``TREE`` is a checkout of this repo (default: the one this file is in);
``--text`` keeps each program's text in ``DIR`` to diff two trees by.
"""
from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import sys
import types

# (benchmark/models/<model>, benchmark/configs/<config>, programs): each
# cell's decode step (or round) at its own (slots, positions) and one
# prefill
CELLS = [
    ("opt_lm", "opt-6.7b.json", [("decode", 8, 2048), ("prefill", 2, 512)]),
    ("jamba_lm", "jamba2-3b.json",
     [("decode", 64, 2048), ("prefill", 8, 512)]),
    ("laguna_lm", "laguna-xs.2.json",
     [("decode", 64, 4096), ("prefill", 4, 1024)]),
    ("phi4flash_lm", "phi4-mini-flash.json",
     [("decode", 64, 4096), ("prefill", 8, 256)]),
    ("mistral4_lm", "mistral-small-4.json",
     [("decode", 32, 16384), ("prefill", 2, 2048)]),
    ("ling3_lm", "ling-3.0-flash.json",
     [("decode", 64, 16384), ("prefill", 2, 2048)]),
    ("dots3_lm", "dots3-note-prev.json",
     [("decode", 32, 16384), ("prefill", 4, 4096)]),
    ("evabyte_lm", "evabyte.json",
     [("decode", 16, 16384), ("prefill", 2, 8192)]),
    ("solar_open2_lm", "solar-open2-250b.json",
     [("decode", 32, 8192), ("prefill", 4, 1024)]),
    ("mimo_v2_lm", "mimo-v2-flash.json",
     [("decode", 16, 16384), ("prefill", 4, 1024)]),
    # its one step program is the ROUND of two positions a slot
    ("glm5_lm", "glm-5.json", [("round", 16, 16384), ("prefill", 2, 4096)]),
]


# what ``_cell_predictor`` asks of pytest's ``monkeypatch``, for the life
# of this process
_STEER = types.SimpleNamespace(setenv=os.environ.__setitem__, setattr=setattr)


_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')
_KERNEL_NAME = re.compile(r'(\bmodule @|\bkernel_name = ")\w+')


def without_locations(text, names=True):
    """``text`` with every Mosaic call's serialized kernel replaced by
    its MLIR assembly, locations dropped; without ``names`` the kernel
    functions' names too."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True  # the serialized dialect's name

    def asm(match):
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return "body:\n" + module.operation.get_asm(
                enable_debug_info=False)

    text = _BODY.sub(asm, text)
    return text if names else _KERNEL_NAME.sub(r"\1kernel", text)


def main(argv=None):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("tree", nargs="?", default=here)
    ap.add_argument("--json", help="write the table here as well")
    ap.add_argument("--text", help="keep each program's text in this "
                    "directory")
    ap.add_argument("--no-kernel-names", action="store_true",
                    help="write every kernel function's name as 'kernel'")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.tree)
    out_json = args.json and os.path.abspath(args.json)
    out_text = args.text and os.path.abspath(args.text)
    os.chdir(root)
    sys.path[:0] = [root, os.path.join(root, "tests")]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu
    from test_tpu_compile_cells import _cell_predictor
    from tpu_compile_lib import _serving_step

    if os.path.dirname(os.path.dirname(paddle_tpu.__file__)) != root:
        sys.exit("imported %s, not the tree %s" % (paddle_tpu.__file__, root))
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    if out_text:
        os.makedirs(out_text, exist_ok=True)
    table = {}

    def keep(name, lowered):
        text = without_locations(lowered, names=not args.no_kernel_names)
        table[name] = [hashlib.sha256(text.encode()).hexdigest()[:16],
                       len(text)]
        if out_text:
            with open(os.path.join(
                    out_text, name.replace(" ", "_") + ".txt"), "w") as f:
                f.write(text)
        print("%-38s %s %9d" % (name, *table[name]), flush=True)

    for model, config, programs in CELLS:
        if not os.path.exists(os.path.join(root, "benchmark", "configs",
                                           config)):
            print("%-38s absent from this tree" % config[:-5], flush=True)
            continue
        for kind, batch, seq in programs:
            name = "%s %s %dx%d" % (config[:-5], kind, batch, seq)
            pred = _cell_predictor(model, config, _STEER)
            fn, feeds, state, _ = _serving_step(pred, kind, batch, seq, chip)
            keep(name, jax.jit(fn, donate_argnums=(0,)).lower(
                feeds, state).as_text())
    os.environ["PADDLE_TPU_FORCE_PALLAS"] = "1"
    from test_tpu_compile import _lm_programs, _train_step_avals

    def on_chip(aval, batch=False):
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=chip)

    stepfn, avals = _train_step_avals(
        *_lm_programs(1), lambda n, a: on_chip(a), on_chip)
    keep("lm train 1 layer", jax.jit(stepfn, donate_argnums=(1,)).lower(
        *avals).as_text())
    if out_json:
        with open(out_json, "w") as f:
            json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
