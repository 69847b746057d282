"""The GLM-5 cell's own programs compiled at full width for a DESCRIBED
TPU v5e, no chip attached (`tpu_compile_lib.py` says what such a compile
can and cannot say): the ROUND of two positions a slot at (16, 16,384),
the model's ONE step program (its plain greedy step is that executable,
`serving/decode.py: _StepOfRound`), and the largest admissions, one
prompt of the 16,384 bucket and four of the 4,096 one, with the
prediction layer's walk of them. A file of its own: the other four are
at the ceiling of ROADMAP D1."""
from __future__ import annotations

import re

import jax
import numpy as np
import pytest

from tpu_compile_lib import HBM_BYTES, _serving_step, _whole_slab_ops
from tpu_compile_lib import one_chip, topo  # noqa: F401  (fixtures)

_GLM5_CASES = [
    # id, kind, batch, seq (benchmark/configs/glm-5.json: 5 layers and
    # the prediction layer at published widths, 8 of 256 experts held,
    # bfloat16 matrices, 16 slots of 16,384 positions)
    ("round-16x16384", "round", 16, 16384),
    ("prefill-1x16384", "prefill", 1, 16384),
    ("prefill-4x4096", "prefill", 4, 4096),
]

# what the prediction layer is in a compiled text: its two norms, its
# projection of [embedding ; hidden], a decoder layer of its own (index
# keys, the choice, the latent row, the attention, the experts) and its
# last norm, each under its parameter's scope
_MTP_SCOPES = (
    "fl.rms_norm:lm.mtp.enorm.w", "fl.rms_norm:lm.mtp.hnorm.w",
    "fl.matmul:lm.mtp.eh_proj.w",
    "fl.dsa_index_keys:lm.mtp.l5.attention.index.k.w",
    "fl.dsa_mask:lm.mtp.l5.attention.index.q.w",
    "fl.mla_kv:lm.mtp.l5.attention.kv_a.w",
    "fl.mla_decode:lm.mtp.l5.attention.kv_b.w",
    "fl.moe_experts:lm.mtp.l5.moe.experts.gate.w",
    "fl.moe_shared:lm.mtp.l5.moe.shared.gate.w",
    "fl.rms_norm:lm.mtp.norm.w")


@pytest.mark.parametrize("kind,batch,seq", [c[1:] for c in _GLM5_CASES],
                         ids=[c[0] for c in _GLM5_CASES])
def test_glm5_serving_step_compiles(one_chip, monkeypatch, kind, batch, seq):
    """They compile for a v5e and fit it beside each other: 6.60 GB of
    weights (3.29 B parameters, the matrices bfloat16), 4.43 GB of
    entries (six layers' latent slab and index keys a slot), an
    admission's temporaries. A round holds ONE call of each step kernel
    a layer (six: the prediction layer's too), on a window of two query
    rows, donates its twelve entries and holds no copy of a slab of
    16,384 positions, no scores of every row and no float32 copy of a
    bfloat16 matrix."""
    from test_tpu_compile_cells import _cell_predictor

    pred = _cell_predictor("glm5_lm", "glm-5.json", monkeypatch)
    step_fn, feeds, state, n_cache = _serving_step(pred, kind, batch, seq,
                                                   one_chip)
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        feeds, state).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, "does not fit one chip: %r" % (mem,)
    weights = sum(int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
                  for s in state.values())
    assert 6.59e9 < weights < 6.61e9, weights
    held = {str(np.dtype(s.dtype)) for s in state.values()}
    assert held == {"bfloat16", "float32"}
    spec = pred.cache_spec(16, 16384)
    slabs = sum(e.nbytes for e in spec)
    assert len(spec) == 12 and round(slabs / 1e9, 2) == 4.43
    text = compiled.as_text()
    calls = re.findall(r"%([\w.-]+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    kernels = [c for c in calls if c.startswith("ptpu.")]
    if kind == "prefill":
        assert kernels.count("ptpu.dsa_attend") == 6, calls
        assert "fl.matmul:lm.mtp.eh_proj.w" in text
        assert weights + slabs + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes) < 15.5 * 2**30, mem
        assert mem.temp_size_in_bytes < 3.5 * 2**30, mem.temp_size_in_bytes
        return
    assert kernels == ["ptpu.dsa_index_step", "ptpu.dsa_attend_step"] * 6, (
        calls)
    # no float32 copy of a whole bfloat16 matrix (the head, W_o)
    assert "f32[6144,19360]" not in text and "f32[16384,6144]" not in text
    assert n_cache == len(spec)
    assert mem.alias_size_in_bytes >= slabs
    # (a slab of index keys has as many elements as W_qb, 2,048 x
    # 16,384, which the plain step's compile lays out anew for its 16
    # rows: told apart by the type)
    for shape in ((16, 16384, 576), (16, 16384, 128)):
        moved = [name for op, name, changed in _whole_slab_ops(text, shape)
                 if op == "copy"
                 and re.search(r"%%%s = f32\[" % re.escape(name), text)]
        assert not moved, (shape, moved)
    # the draft is IN the round: the prediction layer's scopes, and two
    # query rows a slot through the model and through that layer (both
    # positions' logits and the prediction layer's leave the program)
    assert not [n for n in _MTP_SCOPES if n not in text]
    out = text[text.index("ENTRY"):].split("\n", 1)[0]
    assert out.count("f32[16,2,19360]") == 2, out[-2000:]
    assert "f32[16,128,16384]" not in text  # no scores of every row
    assert mem.temp_size_in_bytes < 600 * 2**20, mem.temp_size_in_bytes
