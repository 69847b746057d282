"""paddle_tpu.quant — post-training int8 quantization tier.

The calibrate -> transpile -> serve flow (ROADMAP item 2; reference
lineage: the InferenceTranspiler's deploy-time rewrites, extended with
integer-arithmetic-only inference in the Jacob et al. CVPR'18 mold):

1. **Calibrate** (``calibrate.py``): stream a recordio/DataLoader
   sample through the inference program, collecting per-tensor
   activation amax for every quantizable op input and per-channel
   weight amax from the scope, into a serializable
   :class:`CalibrationTable`.
2. **Transpile** (``transpiler/passes/quantize.py``): a level-3 pass on
   the PR-11 manager rewrites ``mul``/``matmul``/``fused_fc``/
   ``conv2d`` into ``quantized_matmul``/``quantized_conv2d`` (int8
   weights materialized as persistable params, scales riding as attrs,
   int32 accumulation, fused dequant/bias/act epilogue).
3. **Serve**: ``save_inference_model(..., quantize=table)`` exports the
   quantized program; it serves through the same Predictor / AOT cache
   (distinct content fingerprint = distinct executable keys, so bf16
   and int8 coexist) — and ``DecodeServer(kv_dtype="int8")`` opts the
   KV slabs into int8 with per-(slot, position) scales (2x sequences
   per slab budget).
4. **Verify** (``parity.py``): quantized-vs-float logits tolerance and
   task-metric delta: same feeds through both arms, one report.
"""
from .calibrate import (  # noqa: F401
    CalibrationTable, activation_targets, calibrate, quantizable_targets,
)
from .parity import parity_report  # noqa: F401

__all__ = [
    "CalibrationTable", "activation_targets", "calibrate",
    "quantizable_targets", "parity_report",
]
