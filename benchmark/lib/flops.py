"""Operations and bytes the ALGORITHM needs, computed from shapes.

Model FLOPs count the forward and backward passes once (backward = 2 x
forward); recomputation and padding are not counted. Causal attention is
counted at what a causal kernel has to do: half the T x T score matrix
(bench.py's `_train_flops_per_step` counted the full matrix, which
overstates MFU for a causal model).
"""
from __future__ import annotations

# ResNet-50 forward at 224x224: 4.089 G multiply-adds per image (He et
# al. 2015, Table 1 gives 3.8 G for the 7x7 stem variant without the
# shortcut projections; 4.089 G counts them, as bench.py does).
RN50_FWD_FLOPS_PER_IMG = 2 * 4.089e9


def lm_matmul_params(cfg: dict, n_layer: int) -> int:
    """Parameters that sit in a matmul a token passes through: q, k, v,
    out (4 d^2), the FFN (2 d f) per layer, and the vocabulary head."""
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    return n_layer * (4 * d * d + 2 * d * f) + v * d


def lm_train_flops_per_token(cfg: dict, n_layer: int, seq: int) -> float:
    """Forward + backward FLOPs per trained token of the decoder LM."""
    d = cfg["hidden_size"]
    fwd = 2.0 * lm_matmul_params(cfg, n_layer)
    # scores + context: 2 matmuls x 2 FLOPs x T x d per token per layer
    # for the full matrix, halved for the causal triangle
    fwd += n_layer * (4.0 * seq * d) / 2.0
    return 3.0 * fwd


def resnet50_train_flops_per_image() -> float:
    return 3.0 * RN50_FWD_FLOPS_PER_IMG


def flash_attention_cost(batch: int, seq: int, n_head: int, d_head: int,
                         itemsize: int = 2) -> dict:
    """Causal flash attention over (B, T, H, Dh), forward and backward,
    per call of each: FLOPs and the HBM bytes the algorithm must move.

    forward: S = QK^T and O = PV over the causal triangle: 2 matmuls.
    backward: recomputes S, then dV, dP, dQ, dK: 5 matmuls.
    bytes: forward reads Q, K, V and writes O (+ the f32 log-sum-exp);
    backward reads Q, K, V, O, dO, lse and writes dQ, dK, dV.
    """
    tri = batch * n_head * seq * seq * d_head  # one causal matmul x2/2
    elems = batch * seq * n_head * d_head
    lse = batch * n_head * seq * 4
    return {
        "fwd_flops": 2.0 * tri,
        "bwd_flops": 5.0 * tri,
        "fwd_bytes": 4.0 * elems * itemsize + lse,
        "bwd_bytes": 8.0 * elems * itemsize + 2 * lse,
    }


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds the chip could take, which bound it is)."""
    t_flops = flops / peaks["flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
