"""What the `test_tpu_compile*.py` files share: a DESCRIBED TPU v5e (the
chip's own compiler, no chip attached) and the helpers that compile a
function for it and read the compiled text.

The topology is described inside a module-scoped fixture, never at
import: describing it loads the TPU library, and every xdist worker
imports every test file. The compile cases are split over four files by
subject (kernels alone; training steps; OPT and hybrid serving steps;
the Laguna and Phi-4-mini-flash cells' serving steps) so that no one
worker holds them all (`--dist loadfile` gives a file to a worker;
together they are 450 s of all-core compiles); each worker that runs one
of the files loads the library for itself, which the library allows
where `ALLOW_MULTIPLE_LIBTPU_LOAD` is set. The driver's command sets it;
`topo` sets it where nobody has, so a developer's `-n` run behaves as
the driver's.

A test file takes the fixtures by name:
`from tpu_compile_lib import one_chip, topo  # noqa: F401`.
"""
from __future__ import annotations

import os
import re
import sys

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402  (the model and sizes under test are its)

_FULL = chip_smoke.FULL
B, T, D_MODEL, D_INNER, VOCAB = (_FULL["batch"], _FULL["seq"],
                                 _FULL["d_model"], _FULL["d_inner"],
                                 _FULL["vocab"])
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described chip is written to jax's persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, *avals, mosaic=True, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*avals).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == mosaic, (
        "Mosaic kernel in the compiled text? wanted %s" % mosaic)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    return compiled


def _compile(fn, *avals, **jit_kw):
    return _compiled(fn, *avals, **jit_kw).as_text()


# shape of a whole-slab instruction in compiled text, any view of it:
# `%name = f32[8,1024,8,128]{3,2,1,0:T(8,128)} opcode(%operands...)`
_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\](\{\S*)? ([\w-]+)\((.*)$")


def _whole_slab_ops(text, slab_shape):
    """[(opcode, name, changes layout?)] of the top-level instructions
    whose result holds a whole slab's elements, whatever its view."""
    n = int(np.prod(slab_shape))
    entry = text[text.index("ENTRY "):]
    shapes, out = {}, []
    for line in entry.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        name, dims, layout, opcode, operands = m.groups()
        # tiling and dimension order; `S(n)` is a memory space, not a layout
        shape = (dims, re.sub(r"S\(\d+\)", "", (layout or "").split("}")[0]))
        shapes[name] = shape
        if int(np.prod([int(d) for d in dims.split(",")])) != n:
            continue
        src = re.match(r"%(\S+?)[,)]", operands)
        changed = bool(src) and shapes.get(src.group(1), shape) != shape
        out.append((opcode, name, changed))
    return out


def _serving_step(pred, kind, batch, seq, one_chip, **kw):
    """(step function, feed shapes, state shapes, how many cache entries
    it is fed) of the program a graph-builder-only DecodePredictor
    builds for (kind, batch, seq), placed on the described chip. The
    function is `DecodePredictor._step`'s: the very one `_acquire` jits,
    its outputs in the order it traces them."""
    from paddle_tpu.executor import analyze_state
    from paddle_tpu.framework.dtypes import as_numpy_dtype

    pred.traces = 0
    step = pred._step(kind, batch, seq, "greedy", **kw)
    sds = jax.ShapeDtypeStruct
    feeds = {n: sds(a.shape, a.dtype, sharding=one_chip)
             for n, a in pred._feed_structs(step.program,
                                            step.feed_names).items()}
    gb = step.program.global_block()
    state = {}
    for n in analyze_state(step.program, set(step.feed_names))[0]:
        var = gb._find_var_recursive(n)
        # in the type it is HELD in (float32, but for a manifest whose
        # matrices are bfloat16)
        state[n] = sds(tuple(var.shape), np.dtype(as_numpy_dtype(var.dtype)),
                       sharding=one_chip)
    return step.fn, feeds, state, step.n_cache
