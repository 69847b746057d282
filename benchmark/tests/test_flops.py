"""`flops.py` against counts made by hand for both configurations."""
import json
import os

import pytest

from benchmark.lib import flops, harness, peaks


def _cfg(name):
    with open(os.path.join(harness.BENCH_DIR, "configs", name)) as f:
        return json.load(f)


def test_opt_6_7b_per_token_by_hand():
    cfg = _cfg("opt-6.7b.json")
    # per layer: q, k, v, out 4 x 4096^2 = 67,108,864; FFN 2 x 4096 x 16384
    # = 134,217,728; head 50272 x 4096 = 205,914,112
    assert flops.lm_matmul_params(cfg, 2) == 2 * (67108864 + 134217728) \
        + 205914112
    assert flops.lm_matmul_params(cfg, 32) == 32 * 201326592 + 205914112
    # forward: 2 FLOPs a parameter + causal attention 2 x 2048 x 4096 a layer
    fwd = 2 * 608567296 + 2 * (2 * 2048 * 4096)
    assert flops.lm_train_flops_per_token(cfg, 2, 2048) == pytest.approx(
        3 * fwd)
    assert 3.7e9 < 3 * fwd < 3.8e9


def test_causal_attention_is_half_of_bench_py():
    cfg = {"hidden_size": 1024, "ffn_dim": 4096, "vocab_size": 32768}
    t, n = 1024, 12
    ours = flops.lm_train_flops_per_token(cfg, n, t)
    p = n * (4 * 1024 ** 2 + 2 * 1024 * 4096) + 32768 * 1024
    bench_py = 3.0 * (2.0 * p + n * 4.0 * t * 1024)  # the non-causal count
    assert bench_py - ours == pytest.approx(3.0 * n * 2.0 * t * 1024)


def test_resnet50_by_hand():
    assert flops.resnet50_train_flops_per_image() == pytest.approx(
        3 * 2 * 4.089e9)


def test_flash_attention_cost_by_hand():
    c = flops.flash_attention_cost(8, 2048, 32, 128, itemsize=2)
    tri = 8 * 32 * 2048 * 2048 * 128
    assert c["fwd_flops"] == 2 * tri and c["bwd_flops"] == 5 * tri
    elems = 8 * 2048 * 32 * 128
    assert c["fwd_bytes"] == 4 * elems * 2 + 8 * 32 * 2048 * 4
    least, bound = flops.roofline_seconds(
        c["fwd_flops"], c["fwd_bytes"], peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(2 * tri / 197e12)


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
