"""layers.nn (reference: python/paddle/fluid/layers/nn.py).

All layers build IR ops into the default main program; kernels live in
paddle_tpu/ops/*. Sequence layers follow the dense (batch, time, ...) +
Lengths convention (see ops/sequence.py) instead of the reference's LoD.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..framework.core import Variable
from ..framework.dtypes import convert_dtype
from ..layer_helper import LayerHelper

__all__ = [
    "fc",
    "embedding",
    "dynamic_lstm",
    "dynamic_lstmp",
    "dynamic_gru",
    "gru_unit",
    "lstm_unit",
    "cos_sim",
    "dropout",
    "cross_entropy",
    "square_error_cost",
    "softmax",
    "conv2d",
    "conv3d",
    "pool2d",
    "pool3d",
    "batch_norm",
    "layer_norm",
    "conv2d_transpose",
    "conv3d_transpose",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_min",
    "reduce_prod",
    "split",
    "l2_normalize",
    "matmul",
    "topk",
    "transpose",
    "im2sequence",
    "row_conv",
    "multiplex",
    "softmax_with_cross_entropy",
    "smooth_l1",
    "one_hot",
    "autoincreased_step_counter",
    "reshape",
    "squeeze",
    "unsqueeze",
    "lrn",
    "pad",
    "pad_constant_like",
    "label_smooth",
    "roi_pool",
    "dice_loss",
    "image_resize",
    "resize_bilinear",
    "gather",
    "scatter",
    "random_crop",
    "mean_iou",
    "relu",
    "log",
    "crop",
    "rank_loss",
    "prelu",
    "flatten",
    "stack",
    "unstack",
    "sequence_mask",
    "sequence_conv",
    "sequence_pool",
    "sequence_softmax",
    "sequence_first_step",
    "sequence_last_step",
    "sequence_expand",
    "sequence_reshape",
    "sequence_pad",
    "lod_reset",
    "image_resize_short",
    "shape",
    "mean",
    "mul",
    "maxout",
    "conv_shift",
    "bilinear_tensor_product",
    "elementwise_add",
    "sum",
    "linear_chain_crf",
    "crf_decoding",
    "chunk_eval",
    "edit_distance",
    "ctc_greedy_decoder",
    "warpctc",
    "nce",
    "hsigmoid",
    "beam_search",
    "beam_search_decode",
    "fused_attention",
    "ring_attention",
    "moe_ffn",
    "fused_lm_head_loss",
    "decode_attention",
    "decode_attention_quant",
    "decode_attention_window",
    "cache_append",
    "cache_append_quant",
    "cache_append_window",
    "cache_gather",
    "spec_accept",
    "spec_pick",
    "mtp_next_tokens",
    "greedy_sample",
    "top_k_sample",
    "top_p_sample",
    "rms_norm",
    "ssm_scan",
    "ssm_step",
    "causal_conv1d",
    "causal_conv1d_step",
    "rope",
    "moe_route",
    "moe_experts",
    "moe_shared",
    "prefill_attention",
    "ring_append",
    "ring_pack",
    "decode_attn_ring",
    "decode_attention_uneven",
    "diff_attention",
    "diff_decode_attention",
    "attn_cross",
    "gmu",
    "mla_q",
    "mla_kv",
    "mla_expand",
    "mla_decode",
    "mla_append",
    "mla_attend",
    "kda_gate",
    "kda_scan",
    "kda_step",
    "latent_prefill",
    "dsa_index_keys",
    "dsa_mask",
    "eva_summaries",
    "eva_prefill",
    "eva_pack",
    "eva_append",
    "eva_decode",
]

from .ops import elementwise_add  # re-export for parity

# default KV block of fused_attention and decode_attention
_DEFAULT_ATTN_BLOCK = 512


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


# ---------------------------------------------------------------------------
# dense / embedding
# ---------------------------------------------------------------------------


def fc(
    input,
    size,
    num_flatten_dims=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    is_test=False,
    name=None,
):
    """Fully connected (reference nn.py:fc). One `mul` per input + sum +
    bias + act; XLA fuses the epilogue into the MXU matmul."""
    helper = LayerHelper("fc", **locals())
    dtype = helper.input_dtype()
    inputs = helper.multiple_input()
    param_attrs = param_attr if isinstance(param_attr, (list, tuple)) else [param_attr] * len(inputs)

    mul_results = []
    for inp, attr in zip(inputs, param_attrs):
        input_shape = inp.shape
        in_features = _prod(input_shape[num_flatten_dims:])
        w = helper.create_parameter(
            attr=attr, shape=[in_features, size], dtype=dtype, is_bias=False
        )
        out_shape = tuple(input_shape[:num_flatten_dims]) + (size,)
        tmp = helper.create_variable_for_type_inference(dtype, shape=out_shape)
        helper.append_op(
            type="mul",
            inputs={"X": [inp], "Y": [w]},
            outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_results.append(tmp)

    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype, shape=mul_results[0].shape)
        helper.append_op(type="sum", inputs={"X": mul_results}, outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(
    input,
    size,
    is_sparse=False,
    is_distributed=False,
    padding_idx=None,
    param_attr=None,
    dtype="float32",
):
    """reference nn.py:embedding / lookup_table_op.cc. is_sparse is accepted
    for parity; on TPU the grad is a dense scatter-add either way."""
    helper = LayerHelper("embedding", **locals())
    w = helper.create_parameter(attr=helper.param_attr, shape=size, dtype=dtype, is_bias=False)
    in_shape = input.shape
    if in_shape and in_shape[-1] == 1:
        out_shape = tuple(in_shape[:-1]) + (size[1],)
    else:
        out_shape = tuple(in_shape) + (size[1],)
    tmp = helper.create_variable_for_type_inference(dtype, shape=out_shape)
    padding_idx = (
        -1 if padding_idx is None else padding_idx if padding_idx >= 0 else size[0] + padding_idx
    )
    helper.append_op(
        type="lookup_table",
        inputs={"Ids": [input], "W": [w]},
        outputs={"Out": [tmp]},
        attrs={"is_sparse": is_sparse, "padding_idx": padding_idx},
    )
    return tmp


# ---------------------------------------------------------------------------
# recurrent
# ---------------------------------------------------------------------------


def dynamic_lstm(
    input,
    size,
    h_0=None,
    c_0=None,
    param_attr=None,
    bias_attr=None,
    use_peepholes=True,
    is_reverse=False,
    gate_activation="sigmoid",
    cell_activation="tanh",
    candidate_activation="tanh",
    dtype="float32",
    name=None,
    sequence_length=None,
):
    """reference nn.py:dynamic_lstm (lstm_op.cc). Input is the dense
    pre-projected gates (batch, time, 4*hidden); size = 4*hidden.
    `sequence_length` replaces LoD for ragged batches."""
    helper = LayerHelper("lstm", **locals())
    hidden = size // 4
    w = helper.create_parameter(attr=param_attr, shape=[hidden, 4 * hidden], dtype=dtype)
    bias_size = [1, 7 * hidden] if use_peepholes else [1, 4 * hidden]
    b = helper.create_parameter(attr=bias_attr, shape=bias_size, dtype=dtype, is_bias=True)

    batch, time = input.shape[0], input.shape[1]
    hidden_out = helper.create_variable_for_type_inference(dtype, shape=(batch, time, hidden))
    cell_out = helper.create_variable_for_type_inference(dtype, shape=(batch, time, hidden))
    last_h = helper.create_variable_for_type_inference(dtype, shape=(batch, hidden))
    last_c = helper.create_variable_for_type_inference(dtype, shape=(batch, hidden))

    inputs = {"Input": [input], "Weight": [w], "Bias": [b]}
    if h_0 is not None:
        inputs["H0"] = [h_0]
    if c_0 is not None:
        inputs["C0"] = [c_0]
    if sequence_length is not None:
        inputs["Lengths"] = [sequence_length]
    helper.append_op(
        type="lstm",
        inputs=inputs,
        outputs={
            "Hidden": [hidden_out],
            "Cell": [cell_out],
            "LastHidden": [last_h],
            "LastCell": [last_c],
        },
        attrs={
            "use_peepholes": use_peepholes,
            "is_reverse": is_reverse,
            "gate_activation": gate_activation,
            "cell_activation": cell_activation,
            "candidate_activation": candidate_activation,
        },
    )
    return hidden_out, cell_out


def dynamic_lstmp(
    input,
    size,
    proj_size,
    param_attr=None,
    bias_attr=None,
    use_peepholes=True,
    is_reverse=False,
    gate_activation="sigmoid",
    cell_activation="tanh",
    candidate_activation="tanh",
    proj_activation="tanh",
    dtype="float32",
    name=None,
    sequence_length=None,
):
    """LSTM with a recurrent projection layer: h_proj = act(h @ W_proj).
    Composed from the lstm kernel + a projection fc applied stepwise; for
    TPU efficiency we run the plain LSTM at `hidden` then project the whole
    sequence in one batched matmul (mathematically equivalent because the
    projection feeds back only through the recurrent weight, which here is
    sized (proj, 4*hidden))."""
    # Full fidelity of in-loop projection requires a custom scan; provided via
    # the lstmp op below.
    helper = LayerHelper("lstmp", **locals())
    hidden = size // 4
    w = helper.create_parameter(attr=param_attr, shape=[proj_size, 4 * hidden], dtype=dtype)
    w_proj = helper.create_parameter(attr=param_attr, shape=[hidden, proj_size], dtype=dtype)
    bias_size = [1, 7 * hidden] if use_peepholes else [1, 4 * hidden]
    b = helper.create_parameter(attr=bias_attr, shape=bias_size, dtype=dtype, is_bias=True)
    batch, time = input.shape[0], input.shape[1]
    proj_out = helper.create_variable_for_type_inference(dtype, shape=(batch, time, proj_size))
    cell_out = helper.create_variable_for_type_inference(dtype, shape=(batch, time, hidden))
    inputs = {"Input": [input], "Weight": [w], "ProjWeight": [w_proj], "Bias": [b]}
    if sequence_length is not None:
        inputs["Lengths"] = [sequence_length]
    helper.append_op(
        type="lstmp",
        inputs=inputs,
        outputs={"Projection": [proj_out], "Cell": [cell_out]},
        attrs={
            "use_peepholes": use_peepholes,
            "is_reverse": is_reverse,
            "gate_activation": gate_activation,
            "cell_activation": cell_activation,
            "candidate_activation": candidate_activation,
            "proj_activation": proj_activation,
        },
    )
    return proj_out, cell_out


def dynamic_gru(
    input,
    size,
    param_attr=None,
    bias_attr=None,
    is_reverse=False,
    gate_activation="sigmoid",
    candidate_activation="tanh",
    h_0=None,
    sequence_length=None,
):
    """reference nn.py:dynamic_gru (gru_op.cc). Input: (batch, time, 3*size)."""
    helper = LayerHelper("gru", **locals())
    dtype = input.dtype
    w = helper.create_parameter(attr=param_attr, shape=[size, 3 * size], dtype=dtype)
    b = helper.create_parameter(attr=bias_attr, shape=[1, 3 * size], dtype=dtype, is_bias=True)
    batch, time = input.shape[0], input.shape[1]
    hidden_out = helper.create_variable_for_type_inference(dtype, shape=(batch, time, size))
    last_h = helper.create_variable_for_type_inference(dtype, shape=(batch, size))
    inputs = {"Input": [input], "Weight": [w], "Bias": [b]}
    if h_0 is not None:
        inputs["H0"] = [h_0]
    if sequence_length is not None:
        inputs["Lengths"] = [sequence_length]
    helper.append_op(
        type="gru",
        inputs=inputs,
        outputs={"Hidden": [hidden_out], "LastHidden": [last_h]},
        attrs={
            "is_reverse": is_reverse,
            "gate_activation": gate_activation,
            "activation": candidate_activation,
        },
    )
    return hidden_out


def gru_unit(
    input,
    hidden,
    size,
    param_attr=None,
    bias_attr=None,
    activation="tanh",
    gate_activation="sigmoid",
):
    """reference nn.py:gru_unit. size = 3 * hidden_dim."""
    helper = LayerHelper("gru_unit", **locals())
    dtype = input.dtype
    hidden_dim = size // 3
    w = helper.create_parameter(attr=param_attr, shape=[hidden_dim, 3 * hidden_dim], dtype=dtype)
    b = helper.create_parameter(
        attr=bias_attr, shape=[1, 3 * hidden_dim], dtype=dtype, is_bias=True
    )
    batch = input.shape[0]
    gate = helper.create_variable_for_type_inference(dtype, shape=(batch, 3 * hidden_dim))
    reset_hidden_pre = helper.create_variable_for_type_inference(dtype, shape=(batch, hidden_dim))
    updated_hidden = helper.create_variable_for_type_inference(dtype, shape=(batch, hidden_dim))
    helper.append_op(
        type="gru_unit",
        inputs={"Input": [input], "HiddenPrev": [hidden], "Weight": [w], "Bias": [b]},
        outputs={
            "Hidden": [updated_hidden],
            "Gate": [gate],
            "ResetHiddenPrev": [reset_hidden_pre],
        },
        attrs={"activation": activation, "gate_activation": gate_activation},
    )
    return updated_hidden, reset_hidden_pre, gate


def lstm_unit(
    x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0, param_attr=None, bias_attr=None, name=None
):
    """reference nn.py:lstm_unit: fc([x, h]) -> lstm_unit op."""
    helper = LayerHelper("lstm_unit_layer", name=name)
    size = cell_t_prev.shape[1]
    from .tensor import concat

    concat_in = concat([x_t, hidden_t_prev], axis=1)
    fc_out = fc(concat_in, 4 * size, param_attr=param_attr, bias_attr=bias_attr)
    batch = x_t.shape[0]
    new_c = helper.create_variable_for_type_inference(x_t.dtype, shape=(batch, size))
    new_h = helper.create_variable_for_type_inference(x_t.dtype, shape=(batch, size))
    helper.append_op(
        type="lstm_unit",
        inputs={"X": [fc_out], "C_prev": [cell_t_prev]},
        outputs={"C": [new_c], "H": [new_h]},
        attrs={"forget_bias": forget_bias},
    )
    return new_h, new_c


# ---------------------------------------------------------------------------
# convolution / pooling / norm
# ---------------------------------------------------------------------------


def _conv_out_size(in_size, k, pad, stride, dilation=1):
    if in_size < 0:
        return -1
    return (in_size + 2 * pad - (dilation * (k - 1) + 1)) // stride + 1


def _to_list(v, n):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v] * n


def conv2d_default_std(filter_hw, c_in) -> float:
    """MSRA/He std used for conv filters when no initializer is given —
    shared so alternate stems (e.g. the ResNet space-to-depth stem)
    initialize exactly like layers.conv2d."""
    return (2.0 / (filter_hw[0] * filter_hw[1] * c_in)) ** 0.5


def conv2d(
    input,
    num_filters,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups=None,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    use_mkldnn=False,
    act=None,
    name=None,
    data_format="NCHW",
):
    """reference nn.py:conv2d (conv_op.cc). Filter is OIHW in either
    data_format ("NCHW"/"NHWC", matching the reference attr); `use_cudnn`
    and `use_mkldnn` are accepted and ignored (XLA picks the TPU conv).
    NHWC keeps channels lane-minor on TPU — see the conv2d kernel note."""
    helper = LayerHelper("conv2d", **locals())
    dtype = input.dtype
    groups = groups or 1
    if data_format == "NHWC":
        n, h, w_dim, c = input.shape
    else:
        n, c, h, w_dim = input.shape
    fs = _to_list(filter_size, 2)
    st = _to_list(stride, 2)
    pd = _to_list(padding, 2)
    dl = _to_list(dilation, 2)
    filter_shape = [num_filters, c // groups, fs[0], fs[1]]

    std = conv2d_default_std(fs, c)
    from ..initializer import NormalInitializer

    w = helper.create_parameter(
        attr=param_attr,
        shape=filter_shape,
        dtype=dtype,
        default_initializer=NormalInitializer(0.0, std),
    )
    out_h = _conv_out_size(h, fs[0], pd[0], st[0], dl[0])
    out_w = _conv_out_size(w_dim, fs[1], pd[1], st[1], dl[1])
    out_shape = ((n, out_h, out_w, num_filters) if data_format == "NHWC"
                 else (n, num_filters, out_h, out_w))
    pre_bias = helper.create_variable_for_type_inference(dtype, shape=out_shape)
    helper.append_op(
        type="conv2d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={"strides": st, "paddings": pd, "dilations": dl,
               "groups": groups, "data_format": data_format},
    )
    cdim = 3 if data_format == "NHWC" else 1
    pre_act = helper.append_bias_op(pre_bias, dim_start=cdim, dim_end=cdim + 1)
    return helper.append_activation(pre_act)


def conv3d(
    input,
    num_filters,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups=None,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    helper = LayerHelper("conv3d", **locals())
    dtype = input.dtype
    groups = groups or 1
    n, c, d, h, w_dim = input.shape
    fs = _to_list(filter_size, 3)
    st = _to_list(stride, 3)
    pd = _to_list(padding, 3)
    dl = _to_list(dilation, 3)
    filter_shape = [num_filters, c // groups] + fs
    from ..initializer import NormalInitializer

    std = (2.0 / (fs[0] * fs[1] * fs[2] * c)) ** 0.5
    w = helper.create_parameter(
        attr=param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=NormalInitializer(0.0, std),
    )
    out_dims = [
        _conv_out_size(s, fs[i], pd[i], st[i], dl[i]) for i, s in enumerate([d, h, w_dim])
    ]
    pre_bias = helper.create_variable_for_type_inference(
        dtype, shape=tuple([n, num_filters] + out_dims)
    )
    helper.append_op(
        type="conv3d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={"strides": st, "paddings": pd, "dilations": dl, "groups": groups},
    )
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv2d_transpose(
    input,
    num_filters,
    output_size=None,
    filter_size=None,
    padding=0,
    stride=1,
    dilation=1,
    groups=None,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    helper = LayerHelper("conv2d_transpose", **locals())
    dtype = input.dtype
    n, c, h, w_dim = input.shape
    st = _to_list(stride, 2)
    pd = _to_list(padding, 2)
    dl = _to_list(dilation, 2)
    if filter_size is None:
        if output_size is None:
            raise ValueError("either filter_size or output_size is required")
        os = _to_list(output_size, 2)
        fs = [
            (os[i] - (in_s - 1) * st[i] + 2 * pd[i] - 1) // dl[i] + 1
            for i, in_s in enumerate([h, w_dim])
        ]
    else:
        fs = _to_list(filter_size, 2)
    groups = groups or 1
    if num_filters % groups or c % groups:
        raise ValueError(
            "conv2d_transpose: groups=%d must divide both the input "
            "channels (%d) and num_filters (%d)" % (groups, c, num_filters))
    # reference weight layout: (C_in, num_filters // groups, kh, kw)
    filter_shape = [c, num_filters // groups] + fs
    w = helper.create_parameter(attr=param_attr, shape=filter_shape, dtype=dtype)
    out_h = (h - 1) * st[0] - 2 * pd[0] + dl[0] * (fs[0] - 1) + 1
    out_w = (w_dim - 1) * st[1] - 2 * pd[1] + dl[1] * (fs[1] - 1) + 1
    pre_bias = helper.create_variable_for_type_inference(
        dtype, shape=(n, num_filters, out_h, out_w)
    )
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={"strides": st, "paddings": pd, "dilations": dl,
               "groups": groups},
    )
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv3d_transpose(
    input,
    num_filters,
    output_size=None,
    filter_size=None,
    padding=0,
    stride=1,
    dilation=1,
    groups=None,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    helper = LayerHelper("conv3d_transpose", **locals())
    dtype = input.dtype
    n, c, d, h, w_dim = input.shape
    st = _to_list(stride, 3)
    pd = _to_list(padding, 3)
    dl = _to_list(dilation, 3)
    fs = _to_list(filter_size, 3)
    filter_shape = [c, num_filters] + fs
    w = helper.create_parameter(attr=param_attr, shape=filter_shape, dtype=dtype)
    outs = [
        (s - 1) * st[i] - 2 * pd[i] + dl[i] * (fs[i] - 1) + 1
        for i, s in enumerate([d, h, w_dim])
    ]
    pre_bias = helper.create_variable_for_type_inference(
        dtype, shape=tuple([n, num_filters] + outs)
    )
    helper.append_op(
        type="conv3d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={"strides": st, "paddings": pd, "dilations": dl},
    )
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(
    input,
    pool_size=-1,
    pool_type="max",
    pool_stride=1,
    pool_padding=0,
    global_pooling=False,
    use_cudnn=True,
    ceil_mode=False,
    use_mkldnn=False,
    name=None,
    exclusive=True,
    data_format="NCHW",
):
    helper = LayerHelper("pool2d", **locals())
    if data_format == "NHWC":
        n, h, w_dim, c = input.shape
    else:
        n, c, h, w_dim = input.shape
    ks = _to_list(pool_size, 2)
    st = _to_list(pool_stride, 2)
    pd = _to_list(pool_padding, 2)
    if global_pooling:
        out_h = out_w = 1
    else:
        def _psize(in_s, k, p, s):
            if in_s < 0:
                return -1
            if ceil_mode:
                return (in_s - k + 2 * p + s - 1) // s + 1
            return (in_s - k + 2 * p) // s + 1

        out_h = _psize(h, ks[0], pd[0], st[0])
        out_w = _psize(w_dim, ks[1], pd[1], st[1])
    out_shape = ((n, out_h, out_w, c) if data_format == "NHWC"
                 else (n, c, out_h, out_w))
    out = helper.create_variable_for_type_inference(input.dtype, shape=out_shape)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": ks,
            "strides": st,
            "paddings": pd,
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
            "exclusive": exclusive,
            "data_format": data_format,
        },
    )
    return out


def pool3d(
    input,
    pool_size=-1,
    pool_type="max",
    pool_stride=1,
    pool_padding=0,
    global_pooling=False,
    use_cudnn=True,
    ceil_mode=False,
    name=None,
):
    helper = LayerHelper("pool3d", **locals())
    n, c, d, h, w_dim = input.shape
    ks = _to_list(pool_size, 3)
    st = _to_list(pool_stride, 3)
    pd = _to_list(pool_padding, 3)
    if global_pooling:
        outs = [1, 1, 1]
    else:
        outs = [
            ((s - ks[i] + 2 * pd[i] + (st[i] - 1 if ceil_mode else 0)) // st[i]) + 1
            for i, s in enumerate([d, h, w_dim])
        ]
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=tuple([n, c] + outs)
    )
    helper.append_op(
        type="pool3d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": ks,
            "strides": st,
            "paddings": pd,
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
        },
    )
    return out


def batch_norm(
    input,
    act=None,
    is_test=False,
    momentum=0.9,
    epsilon=1e-05,
    param_attr=None,
    bias_attr=None,
    data_layout="NCHW",
    in_place=False,
    use_mkldnn=False,
    name=None,
    moving_mean_name=None,
    moving_variance_name=None,
    do_model_average_for_mean_and_var=False,
    fuse_with_relu=False,
):
    """reference nn.py:batch_norm (batch_norm_op.cc). Running stats are
    persistable non-trainable parameters updated by the traced step."""
    helper = LayerHelper("batch_norm", **locals())
    dtype = input.dtype
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    param_shape = [c]

    from ..initializer import ConstantInitializer
    from ..param_attr import ParamAttr

    scale = helper.create_parameter(
        attr=helper.param_attr,
        shape=param_shape,
        dtype=dtype,
        default_initializer=ConstantInitializer(1.0),
    )
    bias = helper.create_parameter(
        attr=helper.bias_attr, shape=param_shape, dtype=dtype, is_bias=True
    )
    mean = helper.create_parameter(
        attr=ParamAttr(
            name=moving_mean_name, initializer=ConstantInitializer(0.0), trainable=False
        ),
        shape=param_shape,
        dtype=dtype,
    )
    variance = helper.create_parameter(
        attr=ParamAttr(
            name=moving_variance_name, initializer=ConstantInitializer(1.0), trainable=False
        ),
        shape=param_shape,
        dtype=dtype,
    )
    mean.stop_gradient = True
    variance.stop_gradient = True

    saved_mean = helper.create_variable_for_type_inference(dtype, shape=(c,), stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(dtype, shape=(c,), stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype, shape=input.shape)

    helper.append_op(
        type="batch_norm",
        inputs={
            "X": [input],
            "Scale": [scale],
            "Bias": [bias],
            "Mean": [mean],
            "Variance": [variance],
        },
        outputs={
            "Y": [out],
            "MeanOut": [mean],
            "VarianceOut": [variance],
            "SavedMean": [saved_mean],
            "SavedVariance": [saved_var],
        },
        attrs={
            "momentum": momentum,
            "epsilon": epsilon,
            "is_test": is_test,
            "data_layout": data_layout,
        },
    )
    return helper.append_activation(out)


def layer_norm(
    input,
    scale=True,
    shift=True,
    begin_norm_axis=1,
    epsilon=1e-05,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    helper = LayerHelper("layer_norm", **locals())
    dtype = input.dtype
    param_shape = [_prod(input.shape[begin_norm_axis:])]
    inputs = {"X": [input]}
    if scale:
        from ..initializer import ConstantInitializer

        s = helper.create_parameter(
            attr=helper.param_attr,
            shape=param_shape,
            dtype=dtype,
            default_initializer=ConstantInitializer(1.0),
        )
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(
            attr=helper.bias_attr, shape=param_shape, dtype=dtype, is_bias=True
        )
        inputs["Bias"] = [b]
    mean_out = helper.create_variable_for_type_inference(
        dtype, shape=input.shape[:begin_norm_axis], stop_gradient=True
    )
    var_out = helper.create_variable_for_type_inference(
        dtype, shape=input.shape[:begin_norm_axis], stop_gradient=True
    )
    out = helper.create_variable_for_type_inference(dtype, shape=input.shape)
    helper.append_op(
        type="layer_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean_out], "Variance": [var_out]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    return helper.append_activation(out)


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", **locals())
    out = helper.create_variable_for_type_inference(input.dtype, shape=input.shape)
    mid = helper.create_variable_for_type_inference(
        input.dtype, shape=input.shape, stop_gradient=True
    )
    helper.append_op(
        type="lrn",
        inputs={"X": [input]},
        outputs={"Out": [out], "MidOut": [mid]},
        attrs={"n": n, "k": k, "alpha": alpha, "beta": beta},
    )
    return out


# ---------------------------------------------------------------------------
# losses / probability
# ---------------------------------------------------------------------------


def softmax(input, use_cudnn=True, name=None):
    helper = LayerHelper("softmax", **locals())
    out = helper.create_variable_for_type_inference(input.dtype, shape=input.shape)
    helper.append_op(type="softmax", inputs={"X": [input]}, outputs={"Out": [out]})
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out_shape = tuple(input.shape[:-1]) + (1,)
    out = helper.create_variable_for_type_inference(input.dtype, shape=out_shape)
    helper.append_op(
        type="cross_entropy",
        inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("softmax_with_cross_entropy")
    loss_shape = tuple(logits.shape[:-1]) + (1,)
    softmax_out = helper.create_variable_for_type_inference(logits.dtype, shape=logits.shape)
    loss = helper.create_variable_for_type_inference(logits.dtype, shape=loss_shape)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [softmax_out], "Loss": [loss]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return loss


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(input.dtype, shape=input.shape)
    helper.append_op(
        type="square_error_cost",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out]},
    )
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss")
    diff = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    loss = helper.create_variable_for_type_inference(x.dtype, shape=(x.shape[0], 1))
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op(
        type="smooth_l1_loss",
        inputs=inputs,
        outputs={"Diff": [diff], "Out": [loss]},
        attrs={"sigma": sigma if sigma is not None else 1.0},
    )
    return loss


def rank_loss(label, left, right, name=None):
    helper = LayerHelper("rank_loss", name=name)
    out = helper.create_variable_for_type_inference(left.dtype, shape=left.shape)
    helper.append_op(
        type="rank_loss",
        inputs={"Label": [label], "Left": [left], "Right": [right]},
        outputs={"Out": [out]},
    )
    return out


def dice_loss(input, label, epsilon=1e-05):
    helper = LayerHelper("dice_loss")
    out = helper.create_variable_for_type_inference(input.dtype, shape=())
    helper.append_op(
        type="dice_loss",
        inputs={"X": [input], "Label": [label]},
        outputs={"Out": [out]},
        attrs={"epsilon": epsilon},
    )
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(convert_dtype(dtype), shape=label.shape)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(
        type="label_smooth",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={"epsilon": float(epsilon)},
    )
    return out


def one_hot(input, depth):
    helper = LayerHelper("one_hot")
    # drop only a trailing label dim of 1 (paddle's (N, 1) int labels)
    shape = tuple(input.shape[:-1]) if input.shape and input.shape[-1] == 1 else tuple(input.shape)
    out = helper.create_variable_for_type_inference("float32", shape=shape + (depth,))
    helper.append_op(
        type="one_hot", inputs={"X": [input]}, outputs={"Out": [out]}, attrs={"depth": depth}
    )
    return out


def nce(
    input, label, num_total_classes, sample_weight=None, param_attr=None,
    bias_attr=None, num_neg_samples=None, name=None,
):
    """Noise-contrastive estimation (reference nn.py:nce). TPU-native: the
    negative sampling happens inside the traced step via the op's rng."""
    helper = LayerHelper("nce", **locals())
    dim = input.shape[1]
    w = helper.create_parameter(attr=param_attr, shape=[num_total_classes, dim], dtype=input.dtype)
    b = helper.create_parameter(
        attr=bias_attr, shape=[num_total_classes, 1], dtype=input.dtype, is_bias=True
    )
    num_neg_samples = 10 if num_neg_samples is None else num_neg_samples
    cost = helper.create_variable_for_type_inference(input.dtype, shape=(input.shape[0], 1))
    inputs = {"Input": [input], "Label": [label], "Weight": [w], "Bias": [b]}
    if sample_weight is not None:
        inputs["SampleWeight"] = [sample_weight]
    helper.append_op(
        type="nce",
        inputs=inputs,
        outputs={"Cost": [cost]},
        attrs={"num_total_classes": num_total_classes, "num_neg_samples": num_neg_samples},
    )
    return cost


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None, name=None):
    """Hierarchical sigmoid over a complete binary tree (reference
    nn.py:hsigmoid / hierarchical_sigmoid_op.cc)."""
    helper = LayerHelper("hierarchical_sigmoid", **locals())
    dim = input.shape[1]
    w = helper.create_parameter(attr=param_attr, shape=[num_classes - 1, dim], dtype=input.dtype)
    b = helper.create_parameter(
        attr=bias_attr, shape=[num_classes - 1, 1], dtype=input.dtype, is_bias=True
    )
    out = helper.create_variable_for_type_inference(input.dtype, shape=(input.shape[0], 1))
    helper.append_op(
        type="hierarchical_sigmoid",
        inputs={"X": [input], "Label": [label], "W": [w], "Bias": [b]},
        outputs={"Out": [out]},
        attrs={"num_classes": num_classes},
    )
    return out


# ---------------------------------------------------------------------------
# reductions / linalg / shape
# ---------------------------------------------------------------------------


def _reduce_layer(op_type, input, dim, keep_dim, name):
    helper = LayerHelper(op_type, name=name)
    if dim is None:
        out_shape = ()
        attrs = {"reduce_all": True, "keep_dim": keep_dim}
    else:
        dims = dim if isinstance(dim, (list, tuple)) else [dim]
        nd = len(input.shape)
        axes = sorted(d % nd for d in dims)
        shape = list(input.shape)
        if keep_dim:
            for a in axes:
                shape[a] = 1
        else:
            for a in reversed(axes):
                del shape[a]
        out_shape = tuple(shape)
        attrs = {"dim": list(dims), "keep_dim": keep_dim, "reduce_all": False}
    out = helper.create_variable_for_type_inference(input.dtype, shape=out_shape)
    helper.append_op(type=op_type, inputs={"X": [input]}, outputs={"Out": [out]}, attrs=attrs)
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_prod", input, dim, keep_dim, name)


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=())
    helper.append_op(type="mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out_shape = tuple(x.shape[:x_num_col_dims]) + tuple(y.shape[y_num_col_dims:])
    out = helper.create_variable_for_type_inference(x.dtype, shape=out_shape)
    helper.append_op(
        type="mul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={"x_num_col_dims": x_num_col_dims, "y_num_col_dims": y_num_col_dims},
    )
    return out


def sum(x):
    from .tensor import sums

    return sums(x if isinstance(x, (list, tuple)) else [x])


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None,
           precision=None):
    """``precision`` ("highest": float32 products whatever the device's
    default) is written to the op only where given."""
    helper = LayerHelper("matmul", name=name)
    xs = list(x.shape)
    ys = list(y.shape)
    if transpose_x and len(xs) > 1:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if transpose_y and len(ys) > 1:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    batch = xs[:-2] if len(xs) > 2 else (ys[:-2] if len(ys) > 2 else [])
    out_shape = tuple(batch) + ((xs[-2],) if len(xs) > 1 else ()) + ((ys[-1],) if len(ys) > 1 else ())
    out = helper.create_variable_for_type_inference(x.dtype, shape=out_shape)
    attrs = {"transpose_X": transpose_x, "transpose_Y": transpose_y, "alpha": float(alpha)}
    if precision:
        attrs["precision"] = str(precision)
    helper.append_op(
        type="matmul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs=attrs,
    )
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    shape = tuple(input.shape[:-1]) + (k,)
    values = helper.create_variable_for_type_inference(input.dtype, shape=shape)
    indices = helper.create_variable_for_type_inference("int64", shape=shape)
    helper.append_op(
        type="top_k",
        inputs={"X": [input]},
        outputs={"Out": [values], "Indices": [indices]},
        attrs={"k": k},
    )
    return values, indices


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose", name=name)
    out_shape = tuple(x.shape[p] for p in perm)
    out = helper.create_variable_for_type_inference(x.dtype, shape=out_shape)
    helper.append_op(
        type="transpose", inputs={"X": [x]}, outputs={"Out": [out]}, attrs={"axis": list(perm)}
    )
    return out


def reshape(x, shape, actual_shape=None, act=None, inplace=True, name=None):
    helper = LayerHelper("reshape", name=name, act=act)
    out_shape = list(shape)
    in_count = _prod([s for s in x.shape if s >= 0])
    for i, s in enumerate(out_shape):
        if s == 0:
            out_shape[i] = x.shape[i]
    if -1 in out_shape and all(s >= 0 for s in x.shape):
        known = _prod([s for s in out_shape if s > 0])
        out_shape[out_shape.index(-1)] = in_count // known
    out = helper.create_variable_for_type_inference(x.dtype, shape=tuple(out_shape))
    helper.append_op(
        type="reshape", inputs={"X": [x]}, outputs={"Out": [out]}, attrs={"shape": list(shape)}
    )
    return helper.append_activation(out)


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze", name=name)
    shape = [s for i, s in enumerate(input.shape) if i not in [a % len(input.shape) for a in axes]]
    out = helper.create_variable_for_type_inference(input.dtype, shape=tuple(shape))
    helper.append_op(
        type="squeeze", inputs={"X": [input]}, outputs={"Out": [out]}, attrs={"axes": list(axes)}
    )
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze", name=name)
    shape = list(input.shape)
    for a in sorted(axes):
        shape.insert(a, 1)
    out = helper.create_variable_for_type_inference(input.dtype, shape=tuple(shape))
    helper.append_op(
        type="unsqueeze", inputs={"X": [input]}, outputs={"Out": [out]}, attrs={"axes": list(axes)}
    )
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    nd = len(input.shape)
    axis = dim % nd
    in_size = input.shape[axis]
    if isinstance(num_or_sections, int):
        sections = [in_size // num_or_sections] * num_or_sections
        attrs = {"num": num_or_sections, "axis": axis}
    else:
        sections = list(num_or_sections)
        attrs = {"sections": sections, "axis": axis}
    outs = []
    for s in sections:
        shape = list(input.shape)
        shape[axis] = s
        outs.append(helper.create_variable_for_type_inference(input.dtype, shape=tuple(shape)))
    helper.append_op(type="split", inputs={"X": [input]}, outputs={"Out": outs}, attrs=attrs)
    return outs


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    norm_shape = list(x.shape)
    norm_shape[axis % len(norm_shape)] = 1
    norm = helper.create_variable_for_type_inference(x.dtype, shape=tuple(norm_shape))
    helper.append_op(
        type="l2_normalize",
        inputs={"X": [x]},
        outputs={"Out": [out], "Norm": [norm]},
        attrs={"axis": axis, "epsilon": epsilon},
    )
    return out


def stack(x, axis=0):
    helper = LayerHelper("stack")
    xs = x if isinstance(x, (list, tuple)) else [x]
    shape = list(xs[0].shape)
    shape.insert(axis % (len(shape) + 1), len(xs))
    out = helper.create_variable_for_type_inference(xs[0].dtype, shape=tuple(shape))
    helper.append_op(
        type="stack", inputs={"X": list(xs)}, outputs={"Y": [out]}, attrs={"axis": axis}
    )
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    nd = len(x.shape)
    ax = axis % nd
    if num is None:
        num = x.shape[ax]
    shape = [s for i, s in enumerate(x.shape) if i != ax]
    outs = [
        helper.create_variable_for_type_inference(x.dtype, shape=tuple(shape)) for _ in range(num)
    ]
    helper.append_op(type="unstack", inputs={"X": [x]}, outputs={"Y": outs}, attrs={"axis": axis})
    return outs


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten", name=name)
    lead = _prod(x.shape[:axis]) if all(s >= 0 for s in x.shape[:axis]) else -1
    tail = _prod(x.shape[axis:])
    out = helper.create_variable_for_type_inference(x.dtype, shape=(lead, tail))
    helper.append_op(
        type="flatten", inputs={"X": [x]}, outputs={"Out": [out]}, attrs={"axis": axis}
    )
    return out


def shape(input):
    helper = LayerHelper("shape")
    out = helper.create_variable_for_type_inference("int32", shape=(len(input.shape),))
    helper.append_op(type="shape", inputs={"Input": [input]}, outputs={"Out": [out]})
    return out


# ---------------------------------------------------------------------------
# indexing / misc
# ---------------------------------------------------------------------------


def gather(input, index):
    helper = LayerHelper("gather")
    out_shape = (index.shape[0],) + tuple(input.shape[1:])
    out = helper.create_variable_for_type_inference(input.dtype, shape=out_shape)
    helper.append_op(
        type="gather", inputs={"X": [input], "Index": [index]}, outputs={"Out": [out]}
    )
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, shape=input.shape)
    helper.append_op(
        type="scatter",
        inputs={"X": [input], "Ids": [index], "Updates": [updates]},
        outputs={"Out": [out]},
        attrs={"overwrite": overwrite},
    )
    return out


def random_crop(x, shape, seed=None):
    helper = LayerHelper("random_crop")
    lead = tuple(x.shape[: len(x.shape) - len(shape)])
    out = helper.create_variable_for_type_inference(x.dtype, shape=lead + tuple(shape))
    helper.append_op(
        type="random_crop",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"shape": list(shape), "seed": seed if seed is not None else 0},
    )
    return out


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper("crop", name=name)
    if isinstance(shape, Variable):
        shape = list(shape.shape)
    offsets = offsets or [0] * len(x.shape)
    out = helper.create_variable_for_type_inference(x.dtype, shape=tuple(shape))
    helper.append_op(
        type="crop",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"shape": list(shape), "offsets": list(offsets)},
    )
    return out


def multiplex(inputs, index):
    helper = LayerHelper("multiplex")
    out = helper.create_variable_for_type_inference(inputs[0].dtype, shape=inputs[0].shape)
    helper.append_op(
        type="multiplex",
        inputs={"X": list(inputs), "Ids": [index]},
        outputs={"Out": [out]},
    )
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    shape = [
        s + paddings[2 * i] + paddings[2 * i + 1] if s >= 0 else -1
        for i, s in enumerate(x.shape)
    ]
    out = helper.create_variable_for_type_inference(x.dtype, shape=tuple(shape))
    helper.append_op(
        type="pad",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"paddings": list(paddings), "pad_value": float(pad_value)},
    )
    return out


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper("pad_constant_like", name=name)
    out = helper.create_variable_for_type_inference(y.dtype, shape=x.shape)
    helper.append_op(
        type="pad_constant_like",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={"pad_value": float(pad_value)},
    )
    return out


def relu(x, name=None):
    helper = LayerHelper("relu", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type="relu", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def log(x, name=None):
    helper = LayerHelper("log", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type="log", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = list(x.shape[1:])
    from ..initializer import ConstantInitializer

    alpha = helper.create_parameter(
        attr=helper.param_attr,
        shape=alpha_shape,
        dtype=x.dtype,
        default_initializer=ConstantInitializer(0.25),
    )
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(
        type="prelu",
        inputs={"X": [x], "Alpha": [alpha]},
        outputs={"Out": [out]},
        attrs={"mode": mode},
    )
    return out


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim")
    out = helper.create_variable_for_type_inference(X.dtype, shape=(X.shape[0], 1))
    xnorm = helper.create_variable_for_type_inference(X.dtype, shape=(X.shape[0], 1))
    ynorm = helper.create_variable_for_type_inference(X.dtype, shape=(Y.shape[0], 1))
    helper.append_op(
        type="cos_sim",
        inputs={"X": [X], "Y": [Y]},
        outputs={"Out": [out], "XNorm": [xnorm], "YNorm": [ynorm]},
    )
    return out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    mask = helper.create_variable_for_type_inference(x.dtype, shape=x.shape, stop_gradient=True)
    helper.append_op(
        type="dropout",
        inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "seed": seed if seed is not None else 0,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """Persistable int64 step counter incremented once per run (reference
    nn.py:autoincreased_step_counter)."""
    helper = LayerHelper("global_step_counter")
    name = counter_name or "@STEP_COUNTER@"
    counter = helper.create_global_variable(
        name=name, dtype="int64", shape=(1,), persistable=True
    )
    from ..initializer import ConstantInitializer

    helper.set_variable_initializer(counter, ConstantInitializer(begin - 1))
    helper.append_op(
        type="increment",
        inputs={"X": [counter]},
        outputs={"Out": [counter]},
        attrs={"step": float(step)},
    )
    counter.stop_gradient = True
    return counter


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper("row_conv", **locals())
    d = input.shape[-1]
    w = helper.create_parameter(
        attr=param_attr, shape=[future_context_size + 1, d], dtype=input.dtype
    )
    out = helper.create_variable_for_type_inference(input.dtype, shape=input.shape)
    helper.append_op(
        type="row_conv",
        inputs={"X": [input], "Filter": [w]},
        outputs={"Out": [out]},
    )
    return helper.append_activation(out)


def conv_shift(x, y, name=None):
    helper = LayerHelper("conv_shift", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(
        type="conv_shift", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]}
    )
    return out


def bilinear_tensor_product(x, y, size, act=None, name=None, param_attr=None, bias_attr=None):
    helper = LayerHelper("bilinear_tensor_product", **locals())
    w = helper.create_parameter(
        attr=param_attr, shape=[size, x.shape[1], y.shape[1]], dtype=x.dtype
    )
    out = helper.create_variable_for_type_inference(x.dtype, shape=(x.shape[0], size))
    inputs = {"X": [x], "Y": [y], "Weight": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr, shape=[1, size], dtype=x.dtype, is_bias=True)
        inputs["Bias"] = [b]
    helper.append_op(
        type="bilinear_tensor_product", inputs=inputs, outputs={"Out": [out]}
    )
    return helper.append_activation(out)


def maxout(x, groups, name=None):
    from .ops import maxout as _maxout

    return _maxout(x, groups, name)


# ---------------------------------------------------------------------------
# image
# ---------------------------------------------------------------------------


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """reference nn.py:image_resize_short — resize so the SHORT edge equals
    out_short_len, keeping aspect ratio."""
    in_shape = input.shape
    if len(in_shape) != 4:
        raise ValueError(
            "image_resize_short expects NCHW input, got rank %d"
            % len(in_shape))
    hw = list(in_shape[2:4])
    short_idx = hw.index(min(hw))
    long_idx = 1 - short_idx
    out_shape = list(hw)
    out_shape[short_idx] = out_short_len
    out_shape[long_idx] = int(
        float(out_shape[long_idx])
        * (float(out_short_len) / float(hw[short_idx])) + 0.5)
    return image_resize(input=input, out_shape=out_shape, resample=resample)


def image_resize(input, out_shape=None, scale=None, name=None, resample="BILINEAR"):
    helper = LayerHelper("bilinear_interp", name=name)
    n, c, h, w = input.shape
    if out_shape is None:
        out_h, out_w = int(h * scale), int(w * scale)
    else:
        out_h, out_w = out_shape
    op_type = "bilinear_interp" if resample == "BILINEAR" else "nearest_interp"
    out = helper.create_variable_for_type_inference(input.dtype, shape=(n, c, out_h, out_w))
    helper.append_op(
        type=op_type,
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"out_h": out_h, "out_w": out_w},
    )
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, name, "BILINEAR")


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    n, c, h, w = input.shape
    short = min(h, w)
    out_h = h * out_short_len // short
    out_w = w * out_short_len // short
    return image_resize(input, (out_h, out_w), None, None, resample)


def roi_pool(input, rois, pooled_height=1, pooled_width=1, spatial_scale=1.0):
    helper = LayerHelper("roi_pool")
    num_rois = rois.shape[0]
    c = input.shape[1]
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=(num_rois, c, pooled_height, pooled_width)
    )
    helper.append_op(
        type="roi_pool",
        inputs={"X": [input], "ROIs": [rois]},
        outputs={"Out": [out]},
        attrs={
            "pooled_height": pooled_height,
            "pooled_width": pooled_width,
            "spatial_scale": spatial_scale,
        },
    )
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    helper = LayerHelper("im2sequence", name=name)
    fs = _to_list(filter_size, 2)
    st = _to_list(stride, 2)
    pd = _to_list(padding, 4) if isinstance(padding, (list, tuple)) and len(padding) == 4 else _to_list(padding, 2) * 2
    n, c, h, w = input.shape
    out_h = (h + pd[0] + pd[2] - fs[0]) // st[0] + 1 if h > 0 else -1
    out_w = (w + pd[1] + pd[3] - fs[1]) // st[1] + 1 if w > 0 else -1
    rows = n * out_h * out_w if n > 0 and out_h > 0 and out_w > 0 else -1
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=(rows, c * fs[0] * fs[1])
    )
    helper.append_op(
        type="im2sequence",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"kernels": fs, "strides": st, "paddings": list(pd)},
    )
    return out


def mean_iou(input, label, num_classes):
    helper = LayerHelper("mean_iou")
    out_mean_iou = helper.create_variable_for_type_inference("float32", shape=())
    out_wrong = helper.create_variable_for_type_inference("int32", shape=(num_classes,))
    out_correct = helper.create_variable_for_type_inference("int32", shape=(num_classes,))
    helper.append_op(
        type="mean_iou",
        inputs={"Predictions": [input], "Labels": [label]},
        outputs={
            "OutMeanIou": [out_mean_iou],
            "OutWrong": [out_wrong],
            "OutCorrect": [out_correct],
        },
        attrs={"num_classes": num_classes},
    )
    return out_mean_iou, out_wrong, out_correct


# ---------------------------------------------------------------------------
# sequence layers (dense + lengths)
# ---------------------------------------------------------------------------


def _seq_inputs(input, sequence_length):
    inputs = {"X": [input]}
    if sequence_length is not None:
        inputs["Lengths"] = [sequence_length]
    return inputs


def sequence_pool(input, pool_type, sequence_length=None):
    helper = LayerHelper("sequence_pool")
    out_shape = (input.shape[0],) + tuple(input.shape[2:])
    out = helper.create_variable_for_type_inference(input.dtype, shape=out_shape)
    helper.append_op(
        type="sequence_pool",
        inputs=_seq_inputs(input, sequence_length),
        outputs={"Out": [out]},
        attrs={"pooltype": pool_type.upper()},
    )
    return out


def sequence_pad(x, pad_value=None, maxlen=None, sequence_length=None,
                 name=None):
    """reference nn.py:sequence_pad (sequence_pad_op.cc). Under the dense +
    lengths convention the data is already a padded block; this re-pads:
    positions past each row's length become `pad_value` (a scalar Variable,
    like the reference) and the time axis is sliced/extended to the static
    `maxlen`. Returns (out, length) like the reference."""
    helper = LayerHelper("sequence_pad", name=name)
    t = maxlen if maxlen and maxlen > 0 else (
        x.shape[1] if len(x.shape) > 1 else -1)
    out_shape = (x.shape[0], t) + tuple(x.shape[2:])
    out = helper.create_variable_for_type_inference(x.dtype, shape=out_shape)
    length = helper.create_variable_for_type_inference(
        "int64", shape=(x.shape[0],))
    inputs = _seq_inputs(x, sequence_length)
    if pad_value is not None:
        inputs["PadValue"] = [pad_value]
    helper.append_op(
        type="sequence_pad",
        inputs=inputs,
        outputs={"Out": [out], "Length": [length]},
        attrs={"padded_length": int(maxlen) if maxlen else -1},
    )
    return out, length


def lod_reset(x, y=None, target_lod=None, name=None):
    """reference nn.py:lod_reset (lod_reset_op.cc). Dense analog: the data
    passes through and the Lengths companion is replaced by `y` (a lengths
    Variable) or the static `target_lod` list. Returns (out, out_lengths)."""
    helper = LayerHelper("lod_reset", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    out_len = helper.create_variable_for_type_inference(
        "int32", shape=(x.shape[0],))
    inputs = {"X": [x]}
    attrs = {}
    if y is not None:
        inputs["Y"] = [y]
    elif target_lod is not None:
        attrs["target_lod"] = list(target_lod)
    else:
        raise ValueError("lod_reset: provide y or target_lod")
    helper.append_op(
        type="lod_reset", inputs=inputs,
        outputs={"Out": [out], "OutLengths": [out_len]}, attrs=attrs,
    )
    return out, out_len


def sequence_first_step(input, sequence_length=None):
    return sequence_pool(input, "first", sequence_length)


def sequence_last_step(input, sequence_length=None):
    return sequence_pool(input, "last", sequence_length)


def sequence_softmax(input, param_attr=None, bias_attr=None, use_cudnn=True,
                     sequence_length=None):
    helper = LayerHelper("sequence_softmax")
    out = helper.create_variable_for_type_inference(input.dtype, shape=input.shape)
    helper.append_op(
        type="sequence_softmax",
        inputs=_seq_inputs(input, sequence_length),
        outputs={"Out": [out]},
    )
    return out


def sequence_conv(
    input,
    num_filters,
    filter_size=3,
    filter_stride=1,
    padding=None,
    bias_attr=None,
    param_attr=None,
    act=None,
    sequence_length=None,
):
    helper = LayerHelper("sequence_conv", **locals())
    d = input.shape[-1]
    w = helper.create_parameter(
        attr=param_attr, shape=[filter_size * d, num_filters], dtype=input.dtype
    )
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=tuple(input.shape[:-1]) + (num_filters,)
    )
    inputs = _seq_inputs(input, sequence_length)
    inputs["Filter"] = [w]
    helper.append_op(
        type="sequence_conv",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={
            "contextLength": filter_size,
            "contextStart": -int((filter_size - 1) // 2),
            "contextStride": filter_stride,
        },
    )
    pre_act = helper.append_bias_op(out, dim_start=2)
    return helper.append_activation(pre_act)


def sequence_expand(x, y, ref_level=-1, name=None):
    helper = LayerHelper("sequence_expand", name=name)
    t = y.shape[1]
    if len(x.shape) == 2:
        out_shape = (x.shape[0], t, x.shape[1])
    else:
        out_shape = (x.shape[0], t) + tuple(x.shape[2:])
    out = helper.create_variable_for_type_inference(x.dtype, shape=out_shape)
    helper.append_op(
        type="sequence_expand", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]}
    )
    return out


def sequence_reshape(input, new_dim):
    helper = LayerHelper("sequence_reshape")
    b, t, d = input.shape
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=(b, t * d // new_dim if t > 0 else -1, new_dim)
    )
    helper.append_op(
        type="sequence_reshape",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"new_dim": new_dim},
    )
    return out


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    helper = LayerHelper("sequence_mask", name=name)
    if maxlen is None:
        raise ValueError("sequence_mask on TPU requires a static maxlen")
    out = helper.create_variable_for_type_inference(
        convert_dtype(dtype), shape=(x.shape[0] if x.shape else -1, maxlen)
    )
    helper.append_op(
        type="sequence_mask",
        inputs={"X": [x]},
        outputs={"Y": [out]},
        attrs={"maxlen": maxlen, "out_dtype": convert_dtype(dtype)},
    )
    return out


# ---------------------------------------------------------------------------
# structured prediction / decoding (kernels: ops/decode.py)
# ---------------------------------------------------------------------------


def linear_chain_crf(input, label, param_attr=None, sequence_length=None):
    """reference nn.py:linear_chain_crf — CRF negative log-likelihood.
    `input` is dense (B, T, num_tags) emissions (the reference takes LoD'd
    (sum_len, num_tags)); `sequence_length` masks padding. The transition
    parameter has shape [num_tags + 2, num_tags] (rows 0/1 = start/end)."""
    helper = LayerHelper("linear_chain_crf", **locals())
    size = input.shape[-1]
    transition = helper.create_parameter(
        attr=helper.param_attr, shape=[size + 2, size], dtype=helper.input_dtype()
    )
    b, t = input.shape[0], input.shape[1]
    alpha = helper.create_variable_for_type_inference(
        dtype=helper.input_dtype(), shape=(b, t, size))
    log_likelihood = helper.create_variable_for_type_inference(
        dtype=helper.input_dtype(), shape=(b, 1))
    inputs = {"Emission": [input], "Transition": [transition], "Label": [label]}
    if sequence_length is not None:
        inputs["Lengths"] = [sequence_length]
    helper.append_op(
        type="linear_chain_crf",
        inputs=inputs,
        outputs={"Alpha": [alpha], "LogLikelihood": [log_likelihood]},
    )
    return log_likelihood


def crf_decoding(input, param_attr, label=None, sequence_length=None):
    """reference nn.py:crf_decoding — Viterbi decode with the transition
    parameter learned by linear_chain_crf (pass the same ParamAttr name).
    With `label`, emits per-token 0/1 correctness for chunk_eval."""
    helper = LayerHelper("crf_decoding", **locals())
    size = input.shape[-1]
    transition = helper.create_parameter(
        attr=helper.param_attr, shape=[size + 2, size], dtype=helper.input_dtype()
    )
    viterbi_path = helper.create_variable_for_type_inference(
        dtype="int32", shape=(input.shape[0], input.shape[1]))
    inputs = {"Emission": [input], "Transition": [transition]}
    if label is not None:
        inputs["Label"] = [label]
    if sequence_length is not None:
        inputs["Lengths"] = [sequence_length]
    helper.append_op(
        type="crf_decoding", inputs=inputs,
        outputs={"ViterbiPath": [viterbi_path]},
    )
    return viterbi_path


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, sequence_length=None):
    """reference nn.py:chunk_eval — precision/recall/F1 of chunk detection
    (IOB/IOE/IOBES/plain). Returns (precision, recall, f1, num_infer,
    num_label, num_correct)."""
    helper = LayerHelper("chunk_eval", **locals())
    precision = helper.create_variable_for_type_inference("float32", shape=())
    recall = helper.create_variable_for_type_inference("float32", shape=())
    f1_score = helper.create_variable_for_type_inference("float32", shape=())
    num_infer = helper.create_variable_for_type_inference("int64", shape=())
    num_label = helper.create_variable_for_type_inference("int64", shape=())
    num_correct = helper.create_variable_for_type_inference("int64", shape=())
    inputs = {"Inference": [input], "Label": [label]}
    if sequence_length is not None:
        inputs["Lengths"] = [sequence_length]
    helper.append_op(
        type="chunk_eval",
        inputs=inputs,
        outputs={
            "Precision": [precision], "Recall": [recall],
            "F1-Score": [f1_score], "NumInferChunks": [num_infer],
            "NumLabelChunks": [num_label], "NumCorrectChunks": [num_correct],
        },
        attrs={
            "chunk_scheme": chunk_scheme,
            "num_chunk_types": num_chunk_types,
            "excluded_chunk_types": excluded_chunk_types or [],
        },
    )
    return precision, recall, f1_score, num_infer, num_label, num_correct


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  input_length=None, label_length=None):
    """reference nn.py:edit_distance — batch Levenshtein distance between
    dense (B, L) hyp/ref token tensors. Returns (distance (B,1), seq_num)."""
    helper = LayerHelper("edit_distance", **locals())
    out = helper.create_variable_for_type_inference(
        "float32", shape=(input.shape[0], 1))
    seq_num = helper.create_variable_for_type_inference("int64", shape=())
    inputs = {"Hyps": [input], "Refs": [label]}
    if input_length is not None:
        inputs["HypsLengths"] = [input_length]
    if label_length is not None:
        inputs["RefsLengths"] = [label_length]
    helper.append_op(
        type="edit_distance",
        inputs=inputs,
        outputs={"Out": [out], "SequenceNum": [seq_num]},
        attrs={"normalized": normalized,
               "ignored_tokens": list(ignored_tokens or [])},
    )
    return out, seq_num


def ctc_greedy_decoder(input, blank, input_length=None, name=None):
    """reference nn.py:ctc_greedy_decoder — argmax, merge repeats, drop
    blanks. Returns (decoded (B, T) zero-padded, decoded_lengths (B,))."""
    helper = LayerHelper("ctc_greedy_decoder", name=name)
    out = helper.create_variable_for_type_inference(
        "int32", shape=(input.shape[0], input.shape[1]))
    out_len = helper.create_variable_for_type_inference(
        "int32", shape=(input.shape[0],))
    inputs = {"Input": [input]}
    if input_length is not None:
        inputs["Lengths"] = [input_length]
    helper.append_op(
        type="ctc_greedy_decoder",
        inputs=inputs,
        outputs={"Out": [out], "OutLengths": [out_len]},
        attrs={"blank": blank},
    )
    return out, out_len


def warpctc(input, label, blank=0, norm_by_times=False, input_length=None,
            label_length=None):
    """reference nn.py:warpctc — CTC loss on (B, T, C) unnormalized logits
    and dense (B, L) labels; differentiable (lax.scan alpha recursion
    replaces the warp-ctc CUDA kernel)."""
    helper = LayerHelper("warpctc", **locals())
    loss = helper.create_variable_for_type_inference(
        helper.input_dtype(), shape=(input.shape[0], 1))
    inputs = {"Logits": [input], "Label": [label]}
    if input_length is not None:
        inputs["LogitsLengths"] = [input_length]
    if label_length is not None:
        inputs["LabelLengths"] = [label_length]
    helper.append_op(
        type="warpctc",
        inputs=inputs,
        outputs={"Loss": [loss]},
        attrs={"blank": blank, "norm_by_times": norm_by_times},
    )
    return loss


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=None):
    """reference nn.py:nce — noise-contrastive estimation loss with a
    uniform negative sampler."""
    helper = LayerHelper("nce", **locals())
    dim = input.shape[-1]
    weight = helper.create_parameter(
        attr=helper.param_attr, shape=[num_total_classes, dim],
        dtype=input.dtype)
    bias = helper.create_parameter(
        attr=bias_attr, shape=[num_total_classes], dtype=input.dtype,
        is_bias=True)
    cost = helper.create_variable_for_type_inference(
        input.dtype, shape=(input.shape[0], 1))
    inputs = {"Input": [input], "Label": [label], "Weight": [weight]}
    if bias is not None:
        inputs["Bias"] = [bias]
    if sample_weight is not None:
        inputs["SampleWeight"] = [sample_weight]
    helper.append_op(
        type="nce",
        inputs=inputs,
        outputs={"Cost": [cost]},
        attrs={"num_total_classes": num_total_classes,
               "num_neg_samples": num_neg_samples or 10},
    )
    return cost


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None):
    """reference nn.py:hsigmoid — hierarchical sigmoid over a complete
    binary tree of classes."""
    helper = LayerHelper("hsigmoid", **locals())
    dim = input.shape[-1]
    weights = helper.create_parameter(
        attr=helper.param_attr, shape=[num_classes - 1, dim],
        dtype=input.dtype)
    bias = helper.create_parameter(
        attr=bias_attr, shape=[num_classes - 1], dtype=input.dtype,
        is_bias=True)
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=(input.shape[0], 1))
    inputs = {"X": [input], "W": [weights], "Label": [label]}
    if bias is not None:
        inputs["Bias"] = [bias]
    helper.append_op(
        type="hierarchical_sigmoid",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={"num_classes": num_classes},
    )
    return out


def beam_search(pre_ids, pre_scores, ids, scores, beam_size, end_id, level=0,
                name=None):
    """reference nn.py:beam_search — one decode step over dense (B, K)
    beams. `scores` are ACCUMULATED log-probs (B, K, V); finished beams
    (pre_id == end_id) only propose end_id with their score unchanged.
    Returns (selected_ids, selected_scores, parent_idx), each (B, beam_size).
    `level` is accepted for source compatibility (LoD levels do not exist
    in the dense layout)."""
    helper = LayerHelper("beam_search", name=name)
    b = pre_ids.shape[0]
    sel_ids = helper.create_variable_for_type_inference(
        "int32", shape=(b, beam_size))
    sel_scores = helper.create_variable_for_type_inference(
        scores.dtype, shape=(b, beam_size))
    parent_idx = helper.create_variable_for_type_inference(
        "int32", shape=(b, beam_size))
    inputs = {"pre_ids": [pre_ids], "pre_scores": [pre_scores],
              "scores": [scores]}
    if ids is not None:
        inputs["ids"] = [ids]
    helper.append_op(
        type="beam_search",
        inputs=inputs,
        outputs={"selected_ids": [sel_ids], "selected_scores": [sel_scores],
                 "parent_idx": [parent_idx]},
        attrs={"beam_size": beam_size, "end_id": end_id},
    )
    return sel_ids, sel_scores, parent_idx


def beam_search_decode(ids, scores, beam_size=None, end_id=0, parent_idx=None,
                       name=None):
    """reference nn.py:beam_search_decode — backtrack the stacked per-step
    beam selections. `ids`/`scores` are (steps, B, K) stacks of the
    per-step beam_search outputs (the reference's LoD TensorArrays) and
    `parent_idx` the matching (steps, B, K) parent pointers. Returns
    (sentence_ids (B, K, steps), sentence_scores (B, K)); with scores=None
    returns (sentence_ids, sentence_lengths (B, K) int32) instead."""
    if parent_idx is None:
        raise ValueError(
            "beam_search_decode needs the stacked parent_idx produced by "
            "beam_search (dense backtracking replaces LoD lineage)")
    helper = LayerHelper("beam_search_decode", name=name)
    s, b, k = ids.shape
    sent_ids = helper.create_variable_for_type_inference(
        "int32", shape=(b, k, s))
    sent_lens = helper.create_variable_for_type_inference(
        "int32", shape=(b, k))
    outputs = {"SentenceIds": [sent_ids], "SentenceLengths": [sent_lens]}
    inputs = {"Ids": [ids], "ParentIdx": [parent_idx]}
    if scores is not None:
        sent_scores = helper.create_variable_for_type_inference(
            scores.dtype, shape=(b, k))
        inputs["Scores"] = [scores]
        outputs["SentenceScores"] = [sent_scores]
    helper.append_op(
        type="beam_search_decode", inputs=inputs, outputs=outputs,
        attrs={"end_id": end_id},
    )
    if scores is not None:
        return sent_ids, sent_scores
    return sent_ids, sent_lens


def fused_attention(q, k, v, causal=False, scale=None, sequence_length=None,
                    dropout_rate=0.0, block_k=None, layout="bhtd",
                    name=None):
    """Flash attention over (B, H, T, Dh) tensors — one fused op instead of
    the matmul/softmax/dropout/matmul chain (kernel: ops/attention.py).
    Exact attention, O(T) memory; `sequence_length` masks padded KV
    positions; TPU-native (no reference twin — the reference materializes
    the (T, T) scores). layout="bthd" instead takes (B, T, H, Dh) — the
    head-split projection's natural shape — and runs with zero head
    transposes on the Pallas path (needs Dh %% 128 == 0; falls back to an
    internal transpose otherwise, numerics identical)."""
    helper = LayerHelper("fused_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if sequence_length is not None:
        inputs["Lengths"] = [sequence_length]
    helper.append_op(
        type="fused_attention",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={"causal": causal, "scale": scale,
               "dropout_rate": dropout_rate,
               "block_k": block_k or _DEFAULT_ATTN_BLOCK,
               "layout": layout},
    )
    return out


def ring_attention(q, k, v, causal=False, scale=None, sp_axis="sp",
                   lengths=None, dropout_rate=0.0, chunk=None, name=None):
    """Sequence-parallel exact attention over (B, H, T, Dh) tensors: under
    a ParallelExecutor whose mesh has `sp_axis`, K/V blocks rotate on the
    ICI ring (lax.ppermute) so each chip keeps an O(T/N) sequence shard —
    the long-context path (kernel: ops/attention.py ring_attention; math:
    parallel/ring_attention.py). Falls back to exact full attention on a
    single device, so the Program is portable. `lengths` (B,) masks
    padded KV positions; `dropout_rate` applies attention-probability
    dropout with a sharding-independent mask (ring == single-device
    exactly, matching the reference attention's dropout_rate at
    /root/reference/python/paddle/fluid/nets.py:332)."""
    helper = LayerHelper("ring_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if lengths is not None:
        inputs["Lengths"] = [lengths]
    helper.append_op(
        type="ring_attention",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={"causal": causal, "scale": scale, "sp_axis": sp_axis,
               "dropout_rate": dropout_rate, "chunk": chunk},
    )
    return out


def decode_attention(q, k_cache, v_cache, lengths, scale=None, block_s=None,
                     name=None):
    """Single-query attention against a preallocated KV slab (kernel:
    ops/kv_cache.py — Pallas on TPU, exact lax fallback elsewhere). The
    incremental-decode twin of ``fused_attention``: q (B, 1, H, Dh)
    attends k/v slabs (B, S, H, Dh) up to ``lengths`` (B,) valid rows
    per slot. S is static; serving buckets it to powers of two."""
    helper = LayerHelper("decode_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    helper.append_op(
        type="decode_attention",
        inputs={"Q": [q], "KCache": [k_cache], "VCache": [v_cache],
                "Lengths": [lengths]},
        outputs={"Out": [out]},
        attrs={"scale": scale, "block_s": block_s or _DEFAULT_ATTN_BLOCK},
    )
    return out


def cache_append(cache, new, pos, name=None):
    """Append one row per sequence into a KV slab: ``new`` (B, 1, ...)
    lands at row ``pos[b]`` of ``cache`` (B, S, ...). Functional update;
    the decode step threads the slab through feeds/fetches and XLA
    aliases it in place under donation (kernel: ops/kv_cache.py)."""
    helper = LayerHelper("cache_append", name=name)
    out = helper.create_variable_for_type_inference(
        cache.dtype, shape=cache.shape)
    helper.append_op(
        type="cache_append",
        inputs={"Cache": [cache], "New": [new], "Pos": [pos]},
        outputs={"Out": [out]},
        attrs={},
    )
    return out


def cache_append_quant(cache, scales, new, pos, name=None):
    """Quantized slab append: the float row ``new`` (B, 1, ...) lands in
    the int8 slab ``cache`` (B, S, ...) at row ``pos[b]``, quantized
    against a fresh per-row scale stored in ``scales`` (B, S) at the
    same position. Returns (new_cache, new_scales); kernel:
    ops/quant.py (the int8 KV-slab opt-in — PADDLE_TPU_QUANT)."""
    helper = LayerHelper("cache_append_quant", name=name)
    out = helper.create_variable_for_type_inference(
        cache.dtype, shape=cache.shape)
    out_scales = helper.create_variable_for_type_inference(
        scales.dtype, shape=scales.shape)
    helper.append_op(
        type="cache_append_quant",
        inputs={"Cache": [cache], "Scales": [scales], "New": [new],
                "Pos": [pos]},
        outputs={"Out": [out], "OutScales": [out_scales]},
        attrs={},
    )
    return out, out_scales


def decode_attention_quant(q, k_cache, k_scales, v_cache, v_scales,
                           lengths, scale=None, block_s=None, name=None):
    """``decode_attention`` over int8 K/V slabs with per-(slot,
    position) scales: rows dequantize on read, then the regular decode
    dispatch runs (Pallas on TPU, exact lax fallback elsewhere; kernel:
    ops/quant.py)."""
    helper = LayerHelper("decode_attention_quant", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    helper.append_op(
        type="decode_attention_quant",
        inputs={"Q": [q], "KCache": [k_cache], "KScales": [k_scales],
                "VCache": [v_cache], "VScales": [v_scales],
                "Lengths": [lengths]},
        outputs={"Out": [out]},
        attrs={"scale": scale, "block_s": block_s or _DEFAULT_ATTN_BLOCK},
    )
    return out


def cache_gather(cache, index, name=None):
    """Reorder KV-slab slot rows: out[i] = cache[index[i]] — beam-search
    parent reordering and continuous-batching slot compaction (kernel:
    ops/kv_cache.py)."""
    helper = LayerHelper("cache_gather", name=name)
    # the kernel FLATTENS Index, so the declared row count is the
    # product of all its dims (None if any is unknown) — matching the
    # infer rule, or the declared-vs-inferred drift lint fires
    if index.shape is None:
        n = None
    else:
        n = 1
        for d in tuple(index.shape):
            if d is None or d < 0:
                n = None
                break
            n *= d
    out = helper.create_variable_for_type_inference(
        cache.dtype, shape=(n,) + tuple(cache.shape)[1:])
    helper.append_op(
        type="cache_gather",
        inputs={"Cache": [cache], "Index": [index]},
        outputs={"Out": [out]},
        attrs={},
    )
    return out


def cache_append_window(cache, new, pos, name=None):
    """Append T rows per sequence into a KV slab: ``new`` (B, T, ...)
    lands at rows ``pos[b]..pos[b]+T-1`` of ``cache`` (B, S, ...) — the
    speculative verify / prefix suffix-extension widening of
    ``cache_append`` (kernel: ops/speculative.py)."""
    helper = LayerHelper("cache_append_window", name=name)
    out = helper.create_variable_for_type_inference(
        cache.dtype, shape=cache.shape)
    helper.append_op(
        type="cache_append_window",
        inputs={"Cache": [cache], "New": [new], "Pos": [pos]},
        outputs={"Out": [out]},
        attrs={},
    )
    return out


def decode_attention_window(q, k_cache, v_cache, lengths, scale=None,
                            name=None):
    """T-query decode attention with the staircase window mask: window
    query i attends ``lengths[b] + i + 1`` slab rows — what T
    sequential ``decode_attention`` steps would see, in ONE call (the
    speculative verify step; kernel: ops/speculative.py)."""
    helper = LayerHelper("decode_attention_window", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    helper.append_op(
        type="decode_attention_window",
        inputs={"Q": [q], "KCache": [k_cache], "VCache": [v_cache],
                "Lengths": [lengths]},
        outputs={"Out": [out]},
        attrs={"scale": scale},
    )
    return out


def spec_accept(proposed, logits, name=None):
    """In-graph speculative accept/reject: window tokens ``proposed``
    (B, T) vs target ``logits`` (B, T, V) -> (next_ids (B, T) int64,
    accept (B,) int32 longest-matching-prefix count). The caller emits
    ``next_ids[b, :accept[b]+1]`` and rolls rejected slab rows back by
    length truncation (kernel: ops/speculative.py)."""
    helper = LayerHelper("spec_accept", name=name)
    b = proposed.shape[0] if proposed.shape else None
    t = proposed.shape[1] if proposed.shape and len(proposed.shape) > 1 \
        else None
    next_ids = helper.create_variable_for_type_inference(
        "int64", shape=(b, t))
    accept = helper.create_variable_for_type_inference(
        "int32", shape=(b,))
    helper.append_op(
        type="spec_accept",
        inputs={"Proposed": [proposed], "Logits": [logits]},
        outputs={"NextIds": [next_ids], "Accept": [accept]},
        attrs={},
    )
    return next_ids, accept


def mtp_next_tokens(tokens, lengths, first, name=None):
    """A prompt's next tokens, for a prediction layer: ``tokens`` (B, S)
    shifted left by one with ``first`` (B,) at each row's last real
    position (kernel: ops/speculative.py)."""
    helper = LayerHelper("mtp_next_tokens", name=name)
    out = helper.create_variable_for_type_inference(tokens.dtype,
                                                    shape=tokens.shape)
    helper.append_op(
        type="mtp_next_tokens",
        inputs={"Tokens": [tokens], "Lengths": [lengths], "First": [first]},
        outputs={"Out": [out]}, attrs={})
    return out


def spec_pick(ids, accept, name=None):
    """``ids`` (B, T) at column ``accept`` (B,) of each row -> (B,)
    (kernel: ops/speculative.py)."""
    helper = LayerHelper("spec_pick", name=name)
    out = helper.create_variable_for_type_inference(
        ids.dtype, shape=(ids.shape[0],))
    helper.append_op(type="spec_pick",
                     inputs={"Ids": [ids], "Accept": [accept]},
                     outputs={"Out": [out]}, attrs={})
    return out


def greedy_sample(logits, name=None):
    """argmax token per row: (B, V) or (B, 1, V) -> (B,) int64 (kernel:
    ops/sampling.py)."""
    helper = LayerHelper("greedy_sample", name=name)
    out = helper.create_variable_for_type_inference(
        "int64", shape=(logits.shape[0],))
    helper.append_op(type="greedy_sample", inputs={"Logits": [logits]},
                     outputs={"Out": [out]}, attrs={})
    return out


def top_k_sample(logits, seed=None, k=40, temperature=1.0, name=None):
    """Sample from the renormalized top-k logits slice -> (B,) int64.
    ``seed`` (an int tensor; first element used) MUST be a per-step feed
    in compiled decode loops — the trace-time RNG is baked into the
    executable (kernel: ops/sampling.py)."""
    helper = LayerHelper("top_k_sample", name=name)
    out = helper.create_variable_for_type_inference(
        "int64", shape=(logits.shape[0],))
    inputs = {"Logits": [logits]}
    if seed is not None:
        inputs["Seed"] = [seed]
    helper.append_op(type="top_k_sample", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"k": k, "temperature": temperature})
    return out


def top_p_sample(logits, seed=None, p=0.9, temperature=1.0, name=None):
    """Nucleus sampling over the smallest probability mass >= p -> (B,)
    int64; same Seed contract as ``top_k_sample`` (kernel:
    ops/sampling.py)."""
    helper = LayerHelper("top_p_sample", name=name)
    out = helper.create_variable_for_type_inference(
        "int64", shape=(logits.shape[0],))
    inputs = {"Logits": [logits]}
    if seed is not None:
        inputs["Seed"] = [seed]
    helper.append_op(type="top_p_sample", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"p": p, "temperature": temperature})
    return out


def moe_ffn(x, num_experts, d_ff, capacity_factor=2.0, k=2, ep_axis="ep",
            param_attr=None, name=None):
    """Mixture-of-experts FFN block (kernel: ops/attention.py moe_ffn;
    math: parallel/moe.py — GShard top-k routing with per-expert capacity).
    Under a ParallelExecutor whose mesh has `ep_axis`, experts shard
    across devices with one all_to_all each way; single-device falls back
    to the identical-math local path."""
    helper = LayerHelper("moe_ffn", name=name)
    d = x.shape[-1]
    base = name or helper.name

    def mk(shape, suffix, is_bias=False):
        import copy

        from ..param_attr import ParamAttr

        if param_attr:
            # clone per parameter: a shared attr object would get its name
            # fixed on first use and alias all five params to one variable
            attr = copy.deepcopy(ParamAttr._to_attr(param_attr))
            attr.name = "%s.%s" % (attr.name or base, suffix)
        else:
            attr = ParamAttr(name="%s.%s" % (base, suffix))
        return helper.create_parameter(attr=attr, shape=shape,
                                       dtype=x.dtype, is_bias=is_bias)

    gate_w = mk((d, num_experts), "gate_w")
    w1 = mk((num_experts, d, d_ff), "w1")
    b1 = mk((num_experts, d_ff), "b1", is_bias=True)
    w2 = mk((num_experts, d_ff, d), "w2")
    b2 = mk((num_experts, d), "b2", is_bias=True)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(
        type="moe_ffn",
        inputs={"X": [x], "GateW": [gate_w], "W1": [w1], "B1": [b1],
                "W2": [w2], "B2": [b2]},
        outputs={"Out": [out]},
        attrs={"capacity_factor": float(capacity_factor), "k": int(k),
               "ep_axis": ep_axis},
    )
    return out


def fused_lm_head_loss(input, label, size, param_attr=None, bias_attr=None,
                       block_v=4096, transpose_w=False, name=None):
    """Fused vocabulary projection + softmax-cross-entropy: computes the
    per-token loss of `fc(input, size)` vs `label` WITHOUT materializing
    the (N, vocab) logits (kernel: ops/fused_loss.py, chunked online
    logsumexp with a custom backward). Replaces the reference's fc +
    softmax_with_cross_entropy chain (reference layers/nn.py:fc +
    operators/softmax_with_cross_entropy_op.cc) for large vocabularies.

    input: (..., D) features; label: (...,) or (..., 1) int ids;
    returns (N, 1) fp32 loss, N = prod of input's leading dims.

    transpose_w=True declares the weight as (size, D) instead of (D, size)
    — the tied-embedding layout: pass a param_attr naming the token
    embedding table and the head projects through the SAME parameter
    (x @ W^T), with both gradient contributions summed by the whole-step
    autodiff. No transposed copy is ever made (the kernel slices the
    table along the vocab axis in place)."""
    helper = LayerHelper("fused_lm_head_loss", **locals())
    dtype = helper.input_dtype()
    d = input.shape[-1]
    w_shape = [size, d] if transpose_w else [d, size]
    w = helper.create_parameter(
        attr=helper.param_attr, shape=w_shape, dtype=dtype, is_bias=False)
    if list(w.shape) != w_shape:
        # create_parameter reuses an existing param by NAME ignoring the
        # requested shape (the aliasing the tied path relies on) — catch
        # a layout mix-up (wrong transpose_w for the named table) here
        # instead of as garbage logits or a deep jnp.dot error. Blind
        # spot by construction: a SQUARE reused table (size == d) has no
        # shape signal for orientation and cannot be checked.
        raise ValueError(
            "fused_lm_head_loss: reused parameter %r has shape %s but "
            "transpose_w=%s requires %s" %
            (w.name, list(w.shape), bool(transpose_w), w_shape))
    inputs = {"X": [input], "W": [w], "Label": [label]}
    bias_attr = helper.bias_attr
    if bias_attr is not False:
        b = helper.create_parameter(
            attr=bias_attr, shape=[size], dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    lead = input.shape[:-1]
    n = -1 if any(s < 0 for s in lead) else _prod(lead)
    loss = helper.create_variable_for_type_inference("float32", shape=(n, 1))
    helper.append_op(
        type="fused_lm_head_loss",
        inputs=inputs,
        outputs={"Loss": [loss]},
        attrs={"block_v": block_v, "transpose_w": bool(transpose_w)},
    )
    return loss


# ---------------------------------------------------------------------------
# state-space layers (kernels: ops/ssm.py)
# ---------------------------------------------------------------------------


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None,
             unit_offset=False):
    """Root-mean-square normalization over the last axis with a learned
    gain (no mean subtraction, no shift): gain * x / sqrt(mean(x^2) +
    epsilon); under ``unit_offset`` the parameter is the gain's distance
    from one, (1 + g) * x / sqrt(..), and starts at zero."""
    from ..initializer import ConstantInitializer

    helper = LayerHelper("rms_norm", input=input, epsilon=epsilon,
                         param_attr=param_attr, name=name)
    gain = helper.create_parameter(
        attr=helper.param_attr, shape=[int(input.shape[-1])],
        dtype=input.dtype, default_initializer=ConstantInitializer(
            0.0 if unit_offset else 1.0))
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=input.shape)
    attrs = {"epsilon": epsilon}
    if unit_offset:  # written only where set
        attrs["unit_offset"] = True
    helper.append_op(
        type="rms_norm", inputs={"X": [input], "Scale": [gain]},
        outputs={"Out": [out]}, attrs=attrs)
    return out


def ssm_scan(x, delta, a, b, c, d, lengths=None, name=None):
    """Selective state-space scan from a zero state over padded
    sequences: x, delta (B, T, Di), a (Di, N), b, c (B, T, N), d (Di,),
    lengths (B,) -> (y (B, T, Di), state (B, Di, N) after each row's
    last real token; padding leaves a state untouched)."""
    helper = LayerHelper("ssm_scan", name=name)
    y = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    state = helper.create_variable_for_type_inference(
        x.dtype, shape=(x.shape[0], x.shape[2], a.shape[1]))
    inputs = {"X": [x], "Delta": [delta], "A": [a], "B": [b], "C": [c],
              "D": [d]}
    if lengths is not None:
        inputs["Lengths"] = [lengths]
    helper.append_op(type="ssm_scan", inputs=inputs,
                     outputs={"Y": [y], "State": [state]}, attrs={})
    return y, state


def ssm_step(x, delta, a, b, c, d, state, name=None):
    """One token of ``ssm_scan``: x, delta (B, 1, Di), b, c (B, 1, N),
    state (B, Di, N) in -> (y (B, 1, Di), state out)."""
    helper = LayerHelper("ssm_step", name=name)
    y = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    new = helper.create_variable_for_type_inference(
        state.dtype, shape=state.shape)
    helper.append_op(
        type="ssm_step",
        inputs={"X": [x], "Delta": [delta], "A": [a], "B": [b], "C": [c],
                "D": [d], "State": [state]},
        outputs={"Y": [y], "StateOut": [new]}, attrs={})
    return y, new


def causal_conv1d(x, w, bias=None, lengths=None, name=None):
    """Causal depthwise convolution over time: x (B, T, C), w (C, K),
    bias (C,) -> (y (B, T, C), window (B, K - 1, C): the inputs before
    each row's length, which the one-token step carries on from)."""
    helper = LayerHelper("causal_conv1d", name=name)
    y = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    window = helper.create_variable_for_type_inference(
        x.dtype, shape=(x.shape[0], w.shape[1] - 1, x.shape[2]))
    inputs = {"X": [x], "W": [w]}
    if bias is not None:
        inputs["Bias"] = [bias]
    if lengths is not None:
        inputs["Lengths"] = [lengths]
    helper.append_op(type="causal_conv1d", inputs=inputs,
                     outputs={"Y": [y], "Window": [window]}, attrs={})
    return y, window


def causal_conv1d_step(x, window, w, bias=None, name=None):
    """One token of ``causal_conv1d``: x (B, 1, C), window (B, K - 1,
    C) -> (y (B, 1, C), the window moved on by one)."""
    helper = LayerHelper("causal_conv1d_step", name=name)
    y = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    new = helper.create_variable_for_type_inference(
        window.dtype, shape=window.shape)
    inputs = {"X": [x], "Window": [window], "W": [w]}
    if bias is not None:
        inputs["Bias"] = [bias]
    helper.append_op(type="causal_conv1d_step", inputs=inputs,
                     outputs={"Y": [y], "WindowOut": [new]}, attrs={})
    return y, new


# ---------------------------------------------------------------------------
# rotary positions, routed experts, sliding-window attention and its ring
# (kernels: ops/rope.py, ops/moe.py, ops/attention.py, ops/kv_cache.py)
# ---------------------------------------------------------------------------


def _rope_attrs(theta, attention_factor, yarn, interleave):
    """The rotation's attributes as the ``rope`` and ``mla_*`` ops read
    them (``ops/rope.py``: ``yarn_of_attrs``)."""
    attrs = {"theta": float(theta),
             "attention_factor": float(attention_factor)}
    if interleave:  # written only where set: a program without it is
        attrs["interleave"] = True  # the program it was (its AOT key)
    if yarn:
        attrs.update(
            factor=float(yarn["factor"]),
            original_max_position=int(yarn["original_max_position"]),
            beta_fast=float(yarn.get("beta_fast", 32.0)),
            beta_slow=float(yarn.get("beta_slow", 1.0)))
    return attrs


def rope(x, positions=None, rotary_dim=None, theta=10000.0,
         attention_factor=1.0, yarn=None, interleave=False, name=None):
    """Rotary position embedding over the first ``rotary_dim`` channels
    of each head of x (B, T, H, Dh), at ``positions`` (B, T) (or (B,)
    for T = 1; None: 0..T-1): the half-split pairs, or the pairs (2i,
    2i+1) under ``interleave``. ``yarn``: None, or a dict with factor,
    original_max_position, beta_fast, beta_slow (YaRN's blended
    frequencies; ``attention_factor`` multiplies cos and sin)."""
    helper = LayerHelper("rope", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    inputs = {"X": [x]}
    if positions is not None:
        inputs["Positions"] = [positions]
    attrs = {"rotary_dim": int(rotary_dim or x.shape[-1])}
    attrs.update(_rope_attrs(theta, attention_factor, yarn, interleave))
    helper.append_op(type="rope", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def moe_route(x, w_router, top_k, scale=1.0, score="sigmoid", bias=None,
              n_group=1, topk_group=1, name=None):
    """Router of a routed-expert layer: x (B, T, D), w_router (D, E) ->
    (idx (B, T, k) int32, weights (B, T, k)): scores over all E experts
    in float32, the k largest (ties to the lower index), renormalised
    to sum 1 and multiplied by ``scale``. ``bias`` (E,): a selection
    bias (chosen by score + bias, weighted by score); ``n_group`` > 1:
    the choice is limited to the ``topk_group`` best groups."""
    helper = LayerHelper("moe_route", name=name)
    shape = tuple(x.shape[:-1]) + (int(top_k),)
    idx = helper.create_variable_for_type_inference("int32", shape=shape)
    w = helper.create_variable_for_type_inference("float32", shape=shape)
    inputs = {"X": [x], "W": [w_router]}
    attrs = {"top_k": int(top_k), "scale": float(scale),
             "score": str(score)}
    if bias is not None:  # written only where set: a program without
        inputs["Bias"] = [bias]  # them is the program it was
    if int(n_group) > 1:
        attrs.update(n_group=int(n_group), topk_group=int(topk_group))
    helper.append_op(type="moe_route", inputs=inputs,
                     outputs={"Idx": [idx], "Weights": [w]}, attrs=attrs)
    return idx, w


def moe_experts(x, idx, weights, w_gate, w_up, w_down, expert_lo=0,
                lengths=None, decode=False, count_elsewhere=False,
                name=None):
    """The routed experts HELD here, ``[expert_lo, expert_lo + Eh)``:
    every (token, expert) pair that falls on one is computed, whatever
    the load (no capacity, no drop). x (B, T, D); idx, weights from
    ``moe_route``; w_gate, w_up (Eh, D, F), w_down (Eh, F, D) -> (out
    (B, T, D), load (Eh,) int32: pairs each held expert received from
    real tokens; ``lengths`` (B,) says which tokens are real;
    ``count_elsewhere`` adds a last entry: the real tokens that sent no
    pair here)."""
    helper = LayerHelper("moe_experts", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    load = helper.create_variable_for_type_inference(
        "int32", shape=(int(w_gate.shape[0]) + bool(count_elsewhere),))
    inputs = {"X": [x], "Idx": [idx], "Weights": [weights],
              "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down]}
    if lengths is not None:
        inputs["Lengths"] = [lengths]
    attrs = {"expert_lo": int(expert_lo), "decode": bool(decode)}
    if count_elsewhere:  # written only where set
        attrs["count_elsewhere"] = True
    helper.append_op(
        type="moe_experts", inputs=inputs,
        outputs={"Out": [out], "Load": [load]}, attrs=attrs)
    return out, load


def moe_shared(x, w_gate, w_up, w_down, name=None):
    """The shared expert every token passes through:
    (silu(x w_gate) * (x w_up)) w_down."""
    helper = LayerHelper("moe_shared", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(
        type="moe_shared",
        inputs={"X": [x], "WGate": [w_gate], "WUp": [w_up],
                "WDown": [w_down]},
        outputs={"Out": [out]}, attrs={})
    return out


def _qkv_inputs(q, k, v, lengths):
    """The inputs of a prefill's attention op: Q, K, V and, where the
    rows' live tokens are known, Lengths."""
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if lengths is not None:
        inputs["Lengths"] = [lengths]
    return inputs


def prefill_attention(q, k, v, lengths=None, window=0, scale=None,
                      sink=None, name=None):
    """Causal attention of a serving prefill, forward only
    (``ops/attention.py: prefill_attention``): q (B, T, H, dq), k (B, T,
    Hkv, dq), v (B, T, Hkv, dv) -> (B, T, H, dv); with ``window`` a
    query sees the last ``window`` keys up to its own; ``lengths`` (B,)
    the rows' live tokens, for the kernel to skip what lies past
    them; ``sink`` (H,) a learned scalar a query head in the softmax's
    denominator."""
    helper = LayerHelper("prefill_attention", name=name)
    out = helper.create_variable_for_type_inference(
        q.dtype, shape=tuple(q.shape[:-1]) + (v.shape[-1],))
    inputs = _qkv_inputs(q, k, v, lengths)
    if sink is not None:
        inputs["Sink"] = [sink]
    helper.append_op(
        type="prefill_attention", inputs=inputs,
        outputs={"Out": [out]},
        attrs={"window": int(window or 0), "scale": scale})
    return out


def ring_append(ring, new, pos, name=None):
    """Write ``new`` (B, 1, ...) at row ``pos mod W`` of a
    sliding-window layer's ring (B, W, ...)."""
    helper = LayerHelper("ring_append", name=name)
    out = helper.create_variable_for_type_inference(
        ring.dtype, shape=ring.shape)
    helper.append_op(
        type="ring_append",
        inputs={"Cache": [ring], "New": [new], "Pos": [pos]},
        outputs={"Out": [out]}, attrs={})
    return out


def ring_pack(x, lengths, window, name=None):
    """A prefill's rows x (B, T, ...) -> the ring (B, window, ...): each
    row's last ``min(len, window)`` positions at ``position mod
    window``."""
    helper = LayerHelper("ring_pack", name=name)
    out = helper.create_variable_for_type_inference(
        x.dtype, shape=(x.shape[0], int(window)) + tuple(x.shape[2:]))
    helper.append_op(
        type="ring_pack", inputs={"X": [x], "Lengths": [lengths]},
        outputs={"Out": [out]}, attrs={"window": int(window)})
    return out


def decode_attn_ring(q, k_ring, v_ring, lengths, scale=None, sink=None,
                     name=None):
    """Single-query attention against a sliding-window layer's rings
    (B, W, Hkv, Dh) (V's heads of their own width where they have one);
    ``lengths`` (B,) positions held including this step's row; ``sink``
    (H,) a learned scalar a query head in the softmax's denominator."""
    helper = LayerHelper("decode_attn_ring", name=name)
    out = helper.create_variable_for_type_inference(
        q.dtype, shape=tuple(q.shape[:-1]) + (v_ring.shape[-1],))
    inputs = {"Q": [q], "KCache": [k_ring], "VCache": [v_ring],
              "Lengths": [lengths]}
    if sink is not None:
        inputs["Sink"] = [sink]
    helper.append_op(
        type="decode_attn_ring", inputs=inputs,
        outputs={"Out": [out]}, attrs={"scale": scale})
    return out


def decode_attention_uneven(q, k_rows, v_rows, lengths, n_kv_head,
                            scale=None, name=None):
    """Single-query attention against slabs of FLAT rows whose K and V
    differ in width (``ops/kv_cache.py: decode_attention_uneven``): q
    (B, 1, H, dk), k_rows (B, S, n_kv_head * dk), v_rows (B, S,
    n_kv_head * dv) -> (B, 1, H, dv)."""
    helper = LayerHelper("decode_attention_uneven", name=name)
    dv = v_rows.shape[-1] // int(n_kv_head)
    out = helper.create_variable_for_type_inference(
        q.dtype, shape=tuple(q.shape[:-1]) + (dv,))
    helper.append_op(
        type="decode_attention_uneven",
        inputs={"Q": [q], "KCache": [k_rows], "VCache": [v_rows],
                "Lengths": [lengths]},
        outputs={"Out": [out]},
        attrs={"n_kv_head": int(n_kv_head), "scale": scale,
               "block_s": _DEFAULT_ATTN_BLOCK})
    return out


def _diff_out(helper, q):
    """(B, T, H / 2, 2 dh) for differential queries (B, T, H, dh)."""
    b, t, h, dh = q.shape
    return helper.create_variable_for_type_inference(
        q.dtype, shape=(b, t, h // 2, 2 * dh))


def _diff_inputs(lambdas, gain):
    lq1, lk1, lq2, lk2 = lambdas
    return {"LQ1": [lq1], "LK1": [lk1], "LQ2": [lq2], "LK2": [lk2],
            "Gain": [gain]}


def diff_attention(q, k, v, lambdas, gain, lam_init, window=0,
                   epsilon=1e-5, lengths=None, name=None):
    """Differential attention of a prefill (ops/diff_attn.py): q (B, T,
    H, dh), k/v (B, T, Hkv dh) flat key/value rows, ``lambdas`` the
    layer's four vectors (lq1, lk1, lq2, lk2) of dh, ``gain`` (2 dh,);
    causal, within ``window`` where given; ``lengths`` (B,) the rows'
    live tokens. -> (B, T, H / 2, 2 dh)."""
    helper = LayerHelper("diff_attention", name=name)
    out = _diff_out(helper, q)
    inputs = _qkv_inputs(q, k, v, lengths)
    inputs.update(_diff_inputs(lambdas, gain))
    helper.append_op(
        type="diff_attention", inputs=inputs, outputs={"Out": [out]},
        attrs={"lam_init": float(lam_init), "window": int(window or 0),
               "epsilon": float(epsilon)})
    return out


def diff_decode_attention(q, k_cache, v_cache, lengths, lambdas, gain,
                          lam_init, ring=False, epsilon=1e-5, name=None):
    """One token of differential attention against the layer's slab (B,
    S, Hkv dh), or its ring (B, W, Hkv dh) with ``ring``, of flat rows;
    ``lengths`` (B,) positions held including this step's row."""
    helper = LayerHelper("diff_decode_attention", name=name)
    out = _diff_out(helper, q)
    inputs = {"Q": [q], "KCache": [k_cache], "VCache": [v_cache],
              "Lengths": [lengths]}
    inputs.update(_diff_inputs(lambdas, gain))
    helper.append_op(
        type="diff_decode_attention", inputs=inputs,
        outputs={"Out": [out]},
        attrs={"lam_init": float(lam_init), "ring": bool(ring),
               "epsilon": float(epsilon)})
    return out


def attn_cross(q, k, v, lengths, lambdas, gain, lam_init, epsilon=1e-5,
               name=None):
    """One query row of differential attention against keys and values
    ANOTHER layer owns (its slab in a step, its prompt rows in a
    prefill): rows ``[0, lengths)`` are seen, nothing is appended."""
    helper = LayerHelper("attn_cross", name=name)
    out = _diff_out(helper, q)
    inputs = {"Q": [q], "KCache": [k], "VCache": [v], "Lengths": [lengths]}
    inputs.update(_diff_inputs(lambdas, gain))
    helper.append_op(
        type="attn_cross", inputs=inputs, outputs={"Out": [out]},
        attrs={"lam_init": float(lam_init), "epsilon": float(epsilon)})
    return out


# ---------------------------------------------------------------------------
# latent attention (kernels: ops/mla.py)
# ---------------------------------------------------------------------------


def _mla_rot(rot):
    """Attributes of a latent layer's rotation from ``DecodeConfig.
    rope["latent"]``: theta, yarn, attention_factor, interleave, and the
    query scale's beta."""
    attrs = _rope_attrs(rot.get("theta", 10000.0),
                        rot.get("attention_factor", 1.0), rot.get("yarn"),
                        rot.get("interleave", False))
    attrs["scale_beta"] = float(rot.get("scale_beta", 0.0) or 0.0)
    return attrs


def mla_q(x, w_a, gain, w_b, n_head, rope_dim, rot, positions=None,
          epsilon=1e-6, name=None):
    """A latent layer's queries: x (B, T, D) -> (B, T, H, nope + rope):
    ``rms(x w_a) w_b`` by head (``x w_b`` where ``w_a`` and ``gain``
    are None: no bottleneck), each head's last ``rope_dim`` channels
    rotated at ``positions`` ((B,) at T = 1; None: 0..T-1), the row
    times the position-dependent query scale where ``rot`` has a
    ``scale_beta``."""
    helper = LayerHelper("mla_q", name=name)
    out = helper.create_variable_for_type_inference(
        x.dtype, shape=tuple(x.shape[:2]) + (
            int(n_head), int(w_b.shape[1]) // int(n_head)))
    inputs = {"X": [x], "WB": [w_b]}
    if w_a is not None:
        inputs.update(WA=[w_a], Gain=[gain])
    if positions is not None:
        inputs["Positions"] = [positions]
    attrs = _mla_rot(rot)
    attrs.update(n_head=int(n_head), rope_dim=int(rope_dim),
                 epsilon=float(epsilon))
    helper.append_op(type="mla_q", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def mla_kv(x, w_a, gain, rope_dim, rot, positions=None, epsilon=1e-6,
           rescale=1.0, name=None):
    """The latent row a position keeps: x (B, T, D) -> (B, T, rank +
    rope) = ``[rescale rms(c_kv) ; rope(k_r)]`` of ``[c_kv ; k_r] = x
    w_a``."""
    helper = LayerHelper("mla_kv", name=name)
    out = helper.create_variable_for_type_inference(
        x.dtype, shape=tuple(x.shape[:2]) + (int(w_a.shape[1]),))
    inputs = {"X": [x], "WA": [w_a], "Gain": [gain]}
    if positions is not None:
        inputs["Positions"] = [positions]
    attrs = _mla_rot(rot)
    attrs.update(rope_dim=int(rope_dim), epsilon=float(epsilon))
    if float(rescale) != 1.0:
        attrs["rescale"] = float(rescale)
    helper.append_op(type="mla_kv", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def mla_expand(rows, w_b, n_head, nope_dim, name=None):
    """The expanded path's keys and values from latent rows (B, T, rank
    + rope): (k (B, T, H, nope + rope), v (B, T, H, v))."""
    helper = LayerHelper("mla_expand", name=name)
    b, t, w = rows.shape
    per_head = int(w_b.shape[1]) // int(n_head)
    rope_dim = int(w) - int(w_b.shape[0])
    k = helper.create_variable_for_type_inference(
        rows.dtype, shape=(b, t, int(n_head), int(nope_dim) + rope_dim))
    v = helper.create_variable_for_type_inference(
        rows.dtype, shape=(b, t, int(n_head), per_head - int(nope_dim)))
    helper.append_op(
        type="mla_expand", inputs={"Rows": [rows], "WB": [w_b]},
        outputs={"K": [k], "V": [v]},
        attrs={"n_head": int(n_head), "nope_dim": int(nope_dim)})
    return k, v


def mla_attend(q, k, v, scale, lengths=None, name=None):
    """The expanded path's causal attention: q, k (B, T, H, dq), v (B,
    T, H, dv), dq == dv or not -> (B, T, H, dv); ``lengths`` (B,) the
    rows' live tokens (``ops/mla.py: mla_attend``)."""
    helper = LayerHelper("mla_attend", name=name)
    out = helper.create_variable_for_type_inference(v.dtype, shape=v.shape)
    helper.append_op(type="mla_attend", inputs=_qkv_inputs(q, k, v, lengths),
                     outputs={"Out": [out]}, attrs={"scale": float(scale)})
    return out


def kda_gate(f, b, a_log, dt_bias, kind, bound, beta_max=1.0, name=None):
    """A KDA layer's log-decay and write strength: f (B, T, H * dk), b
    (B, T, H), a_log (H,), dt_bias (H * dk,) -> (g (B, T, H, dk), beta
    (B, T, H) in (0, ``beta_max``)) (``ops/kda.py: kda_gate``)."""
    helper = LayerHelper("kda_gate", name=name)
    bsz, t, h = b.shape
    g = helper.create_variable_for_type_inference(
        "float32", shape=(bsz, t, h, int(f.shape[-1]) // int(h)))
    beta = helper.create_variable_for_type_inference("float32",
                                                     shape=b.shape)
    attrs = {"kind": str(kind), "bound": float(bound)}
    if float(beta_max) != 1.0:  # a program written before it existed has none
        attrs["beta_max"] = float(beta_max)
    helper.append_op(
        type="kda_gate",
        inputs={"F": [f], "B": [b], "ALog": [a_log], "DtBias": [dt_bias]},
        outputs={"G": [g], "Beta": [beta]}, attrs=attrs)
    return g, beta


def kda_scan(q, k, v, g, beta, lengths=None, lower_bound=None, name=None):
    """The chunked delta rule of a prefill from a zero state: q, k, g
    (B, T, H, dk), v (B, T, H, dv), beta (B, T, H) -> (o (B, T, H, dv),
    state (B, H, dk, dv) at each row's length). ``lower_bound``: the
    gate's least log-decay a token, None where it has none."""
    helper = LayerHelper("kda_scan", name=name)
    out = helper.create_variable_for_type_inference(v.dtype, shape=v.shape)
    state = helper.create_variable_for_type_inference(
        "float32", shape=(v.shape[0], v.shape[2], q.shape[3], v.shape[3]))
    inputs = {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]}
    if lengths is not None:
        inputs["Lengths"] = [lengths]
    attrs = {} if lower_bound is None else {
        "lower_bound": float(lower_bound)}
    helper.append_op(type="kda_scan", inputs=inputs,
                     outputs={"Out": [out], "State": [state]}, attrs=attrs)
    return out, state


def kda_step(q, k, v, g, beta, state, name=None):
    """One token of ``kda_scan``: q, k, g (B, 1, H, dk), v (B, 1, H,
    dv), beta (B, 1, H), state (B, H, dk, dv) -> (o, state out)."""
    helper = LayerHelper("kda_step", name=name)
    out = helper.create_variable_for_type_inference(v.dtype, shape=v.shape)
    new = helper.create_variable_for_type_inference(
        state.dtype, shape=state.shape)
    helper.append_op(
        type="kda_step",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta],
                "State": [state]},
        outputs={"Out": [out], "StateOut": [new]}, attrs={})
    return out, new


def mla_decode(q, slab, lengths, w_b, scale, chosen=None, scope=None,
               name=None):
    """The absorbed path: q (B, 1, H, nope + rope) attends the latent
    slab (B, S, rank + rope) up to ``lengths`` (B,) rows -> (B, 1, H,
    v); no key or value of any head is built. ``chosen`` (B, S) bool:
    of a slot's live rows only those (``dsa_mask``); ``scope``: the
    named scope where it is not ``ptpu.mla_decode``. A window: q (B, T,
    ...) under ``chosen`` (B, T, S), ``lengths`` the first row's."""
    helper = LayerHelper("mla_decode", name=name)
    b, t, h, dq = q.shape
    nope = int(dq) - (int(slab.shape[-1]) - int(w_b.shape[0]))
    out = helper.create_variable_for_type_inference(
        q.dtype, shape=(b, t, h, int(w_b.shape[1]) // int(h) - nope))
    inputs = {"Q": [q], "Cache": [slab], "Lengths": [lengths], "WB": [w_b]}
    attrs = {"scale": float(scale)}
    if chosen is not None:
        inputs["Chosen"] = [chosen]
    if scope:
        attrs["scope"] = str(scope)
    helper.append_op(type="mla_decode", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def latent_prefill(c_q, rows, w_qb, w_kvb, w_o, n_head, nope_dim, scale,
                   rot, gate=None, window=0, mask=None, lengths=None,
                   scope=None, name=None):
    """A prefill's expanded attention of a latent layer of many heads,
    a group of heads at a time, from the query latent c_q (B, T, q_rank)
    and the latent rows (B, T, rank + rope) to the output projection
    (B, T, D) (``ops/mla.py: latent_prefill``): causal, over the last
    ``window`` keys where set, under ``mask`` (B, T, T) int8 where
    given, each head times ``gate`` (B, T, H) where given; rows past
    ``lengths`` (B,) are no one's to read and may come out as zeros."""
    helper = LayerHelper("latent_prefill", name=name)
    out = helper.create_variable_for_type_inference(
        c_q.dtype, shape=tuple(c_q.shape[:2]) + (int(w_o.shape[1]),))
    inputs = {"CQ": [c_q], "Rows": [rows], "WQB": [w_qb], "WKVB": [w_kvb],
              "WO": [w_o]}
    if gate is not None:
        inputs["Gate"] = [gate]
    if mask is not None:
        inputs["Mask"] = [mask]
    if lengths is not None:
        inputs["Lengths"] = [lengths]
    attrs = _mla_rot(rot)
    attrs.update(n_head=int(n_head), nope_dim=int(nope_dim),
                 scale=float(scale), window=int(window or 0))
    if scope:
        attrs["scope"] = str(scope)
    helper.append_op(type="latent_prefill", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def _index_rot(rot):
    """Attributes of an indexer's rotation from ``DecodeConfig.
    rope["index"]``: theta, rotary_dim and, written only where set (a
    program without it is the program it was), interleave."""
    attrs = {"theta": float(rot["theta"]),
             "rotary_dim": int(rot["rotary_dim"])}
    if rot.get("interleave"):
        attrs["interleave"] = True
    return attrs


def dsa_index_keys(x, w, gain, bias, rot, positions=None, epsilon=1e-5,
                   name=None):
    """The index key a position keeps under a learned indexer: x (B, T,
    D) -> (B, T, d) = LayerNorm(x w), its first ``rot["rotary_dim"]``
    channels rotated (``ops/dsa.py: index_keys``)."""
    helper = LayerHelper("dsa_index_keys", name=name)
    out = helper.create_variable_for_type_inference(
        x.dtype, shape=tuple(x.shape[:2]) + (int(w.shape[1]),))
    inputs = {"X": [x], "W": [w], "Gain": [gain], "Bias": [bias]}
    if positions is not None:
        inputs["Positions"] = [positions]
    helper.append_op(
        type="dsa_index_keys", inputs=inputs, outputs={"Out": [out]},
        attrs=dict(_index_rot(rot), epsilon=float(epsilon)))
    return out


def dsa_mask(c_q, x, w_q, w_w, keys, n_heads, topk, rot, positions=None,
             lengths=None, name=None):
    """The indexer's choice (``ops/dsa.py``): from the query latent c_q
    (B, T, q_rank), the layer's input x and the index keys, a prefill's
    (B, T, T) int8 mask (``keys`` (B, T, d); with ``lengths`` (B,) the
    prompts' live tokens, the query rows past them are left unchosen),
    or with ``positions`` and ``lengths`` (B,) a step's (B, S) bool over
    the slab of keys (B, S, d): 1 where the query attends the
    position; a WINDOW of T > 1 rows on the slab (``positions`` and
    ``lengths`` the first row's) gives (B, T, S), a choice a row."""
    helper = LayerHelper("dsa_mask", name=name)
    b, t = c_q.shape[:2]
    inputs = {"CQ": [c_q], "X": [x], "WQ": [w_q], "WW": [w_w],
              "Keys": [keys]}
    if lengths is not None:
        inputs["Lengths"] = [lengths]
    if positions is None:
        out = helper.create_variable_for_type_inference(
            "int8", shape=(b, t, t))
    else:
        out = helper.create_variable_for_type_inference(
            "bool", shape=(b,) + ((int(t),) if int(t) > 1 else ())
            + (int(keys.shape[1]),))
        inputs["Positions"] = [positions]
    helper.append_op(
        type="dsa_mask", inputs=inputs, outputs={"Out": [out]},
        attrs=dict(_index_rot(rot), n_heads=int(n_heads), topk=int(topk)))
    return out


def mla_append(slab, row, pos, ring=False, name=None):
    """One latent row a slot: ``row`` (B, 1, W) at row ``pos[b]`` of
    ``slab`` (B, S, W), at ``pos[b] mod S`` of a ``ring``; a window's
    rows (B, T, W) at ``pos[b]..pos[b] + T - 1`` of a slab."""
    helper = LayerHelper("mla_append", name=name)
    out = helper.create_variable_for_type_inference(
        slab.dtype, shape=slab.shape)
    helper.append_op(
        type="mla_append",
        inputs={"Cache": [slab], "New": [row], "Pos": [pos]},
        outputs={"Out": [out]}, attrs={"ring": True} if ring else {})
    return out


def gmu(x, memory, w_in, w_out, name=None):
    """Gated memory unit: ``(memory * silu(x w_in)) w_out``; ``memory``
    (B, T, Di) is a state-space layer's scan output, row for row."""
    helper = LayerHelper("gmu", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(
        type="gmu",
        inputs={"X": [x], "Memory": [memory], "WIn": [w_in],
                "WOut": [w_out]},
        outputs={"Out": [out]}, attrs={})
    return out


def eva_summaries(k, v, phi, mu, chunk, name=None):
    """EVA's pooled key and value a chunk (``ops/eva.py``): k (rotated),
    v (B, T, H, D), phi, mu (H, D) -> (k~, v~) (B, T / chunk, H, D)."""
    helper = LayerHelper("eva_summaries", name=name)
    shape = (k.shape[0], k.shape[1] // int(chunk)) + tuple(k.shape[2:])
    ks = helper.create_variable_for_type_inference(k.dtype, shape=shape)
    vs = helper.create_variable_for_type_inference(v.dtype, shape=shape)
    helper.append_op(
        type="eva_summaries",
        inputs={"K": [k], "V": [v], "Phi": [phi], "Mu": [mu]},
        outputs={"KSum": [ks], "VSum": [vs]}, attrs={"chunk": int(chunk)})
    return ks, vs


def eva_prefill(q, k, v, ks, vs, lengths, window, chunk, name=None):
    """A prompt's EVA attention: each window's queries on [the summaries
    of the windows closed before it | its own rows, causal] -> (B, T, H,
    D)."""
    helper = LayerHelper("eva_prefill", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    helper.append_op(
        type="eva_prefill",
        inputs={"Q": [q], "K": [k], "V": [v], "KSum": [ks], "VSum": [vs],
                "Lengths": [lengths]},
        outputs={"Out": [out]},
        attrs={"window": int(window), "chunk": int(chunk)})
    return out


def eva_pack(x, xs, lengths, window, summary_rows, name=None):
    """A prompt's rows and their summaries -> the entry (B, summary_rows
    + window, H, D) an admission stores: summaries last first, then the
    block of the window the next position lies in."""
    helper = LayerHelper("eva_pack", name=name)
    out = helper.create_variable_for_type_inference(
        x.dtype, shape=(x.shape[0], int(summary_rows) + int(window))
        + tuple(x.shape[2:]))
    helper.append_op(
        type="eva_pack",
        inputs={"X": [x], "XSum": [xs], "Lengths": [lengths]},
        outputs={"Out": [out]},
        attrs={"window": int(window), "summary_rows": int(summary_rows)})
    return out


def eva_append(k_cache, v_cache, k, v, pos, phi, mu, window, chunk,
               name=None):
    """A step's row into both entries' blocks at ``pos mod window`` and,
    where ``pos`` closes a chunk, that chunk's two summary rows."""
    helper = LayerHelper("eva_append", name=name)
    k_out = helper.create_variable_for_type_inference(
        k_cache.dtype, shape=k_cache.shape)
    v_out = helper.create_variable_for_type_inference(
        v_cache.dtype, shape=v_cache.shape)
    helper.append_op(
        type="eva_append",
        inputs={"KCache": [k_cache], "VCache": [v_cache], "K": [k],
                "V": [v], "Pos": [pos], "Phi": [phi], "Mu": [mu]},
        outputs={"KOut": [k_out], "VOut": [v_out]},
        attrs={"window": int(window), "chunk": int(chunk)})
    return k_out, v_out


def eva_decode(q, k_cache, v_cache, pos, window, chunk, name=None):
    """A step's EVA attention over an entry's live range: the visible
    summaries and the block's rows up to ``pos``'s, one softmax."""
    helper = LayerHelper("eva_decode", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    helper.append_op(
        type="eva_decode",
        inputs={"Q": [q], "KCache": [k_cache], "VCache": [v_cache],
                "Pos": [pos]},
        outputs={"Out": [out]},
        attrs={"window": int(window), "chunk": int(chunk)})
    return out
