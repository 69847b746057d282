"""Expert parallelism: Switch/GShard-style MoE FFN over an ``ep`` mesh axis.

SURVEY §2 parallel commitment ("expert parallel for MoE"); no reference
twin — codeWorm2015/Paddle (2018) predates MoE. TPU-native design: the
canonical GShard dispatch. Tokens live batch-sharded over ``ep``; each
device also owns E/n experts. Dispatch is pure masked matmul (one-hot
(token, expert, capacity) tensors — no gathers, MXU-friendly), the
token↔expert exchange is ONE ``lax.all_to_all`` each way on the ICI, and
the capacity factor bounds per-expert work so every shape stays static.
Over-capacity tokens are dropped (their combine weight is zero) exactly as
in Switch Transformer; with k=2 the second choice picks up the slack.

Everything is differentiable: grads flow through combine/dispatch and the
all_to_alls transpose to themselves.

This is the capacity-factor TRAINING layer. A token past an expert's
capacity is dropped, so its output cannot equal a reference forward
pass: a cached decode graph does not use it. The SERVING op that drops
nothing is ``ops/moe.py`` (``layers.moe_route`` / ``moe_experts`` /
``moe_shared``: every chosen pair computed, the experts held here, a
shared expert), which the described-block builders (``models/jamba.py``)
put into the prefill and decode programs.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ._compat import shard_map

__all__ = ["MoEParams", "init_moe_params", "moe_ffn_local",
           "expert_parallel_ffn", "moe_capacity"]


class MoEParams(NamedTuple):
    gate_w: jnp.ndarray   # (D, E)
    w1: jnp.ndarray       # (E, D, F)
    b1: jnp.ndarray       # (E, F)
    w2: jnp.ndarray       # (E, F, D)
    b2: jnp.ndarray       # (E, D)


def init_moe_params(key, d_model: int, d_ff: int, num_experts: int,
                    dtype=jnp.float32) -> MoEParams:
    kg, k1, k2 = jax.random.split(key, 3)
    s1 = (2.0 / d_model) ** 0.5
    s2 = (2.0 / d_ff) ** 0.5
    return MoEParams(
        gate_w=jax.random.normal(kg, (d_model, num_experts), dtype) * 0.02,
        w1=jax.random.normal(k1, (num_experts, d_model, d_ff), dtype) * s1,
        b1=jnp.zeros((num_experts, d_ff), dtype),
        w2=jax.random.normal(k2, (num_experts, d_ff, d_model), dtype) * s2,
        b2=jnp.zeros((num_experts, d_model), dtype),
    )


def moe_capacity(n_tokens: int, num_experts: int,
                 capacity_factor: float) -> int:
    return max(int(math.ceil(n_tokens / num_experts * capacity_factor)), 1)


def _dispatch_tensors(gate_logits, num_experts: int, capacity: int, k: int):
    """GShard dispatch: (N, E) logits -> (dispatch (N, E, C) one-hot,
    combine (N, E, C) prob-weighted) with top-k routing and per-expert
    capacity. Over-capacity tokens get zero weight (dropped)."""
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    n = gate_logits.shape[0]
    dispatch = jnp.zeros((n, num_experts, capacity), jnp.float32)
    combine = jnp.zeros((n, num_experts, capacity), jnp.float32)
    filled = jnp.zeros((num_experts,), jnp.int32)
    remaining = probs
    for _ in range(k):
        e_idx = jnp.argmax(remaining, axis=-1)                # (N,)
        gate = jnp.take_along_axis(remaining, e_idx[:, None],
                                   axis=-1)[:, 0]
        onehot = jax.nn.one_hot(e_idx, num_experts)           # (N, E)
        # position of each token within its expert's buffer, continuing
        # after the slots the previous routing round already filled
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) + filled[None, :]
        pos = (pos * onehot).sum(-1).astype(jnp.int32)        # (N,)
        keep = pos < capacity
        slot = jax.nn.one_hot(jnp.where(keep, pos, capacity),
                              capacity + 1)[:, :capacity]     # (N, C)
        d = onehot[:, :, None] * slot[:, None, :]             # (N, E, C)
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        filled = filled + (onehot * keep[:, None]).sum(0).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)
    return dispatch, combine


def moe_ffn_local(x, params: MoEParams, capacity_factor: float = 1.25,
                  k: int = 2, activation=jax.nn.relu):
    """Single-device MoE FFN: x (..., D) -> (..., D). The numeric
    reference for the expert-parallel path (identical math, no comms)."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    tokens = x.reshape(-1, d)
    n = tokens.shape[0]
    e = params.gate_w.shape[-1]
    cap = moe_capacity(n, e, capacity_factor)
    # the ROUTER always runs f32 (GShard/Switch practice): a bf16 gate
    # logit can flip a top-k selection near a decision boundary, which is
    # a discrete output change, not rounding noise. The (N, E) matmul is
    # negligible next to the expert FFNs.
    dispatch, combine = _dispatch_tensors(
        tokens.astype(jnp.float32) @ params.gate_w, e, cap, k)
    # expert matmuls run in the input dtype with f32 accumulation (bf16 MXU full
    # rate under AMP; no-op for f32 inputs); gating/softmax stays f32
    xdt = tokens.dtype
    expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(xdt), tokens,
                           preferred_element_type=jnp.float32).astype(xdt)
    h = activation(jnp.einsum("ecd,edf->ecf", expert_in,
                              params.w1.astype(xdt),
                              preferred_element_type=jnp.float32)
                   + params.b1[:, None, :])
    expert_out = (jnp.einsum("ecf,efd->ecd", h.astype(xdt),
                             params.w2.astype(xdt),
                             preferred_element_type=jnp.float32)
                  + params.b2[:, None, :]).astype(xdt)
    out = jnp.einsum("nec,ecd->nd", combine,  # combine is already f32
                     expert_out.astype(jnp.float32))
    return out.astype(x.dtype).reshape(lead + (d,))


def expert_parallel_ffn(x, params: MoEParams, mesh: Mesh, axis: str = "ep",
                        capacity_factor: float = 1.25, k: int = 2,
                        activation=jax.nn.relu,
                        batch_dim_sharded: bool = True):
    """Expert-parallel MoE FFN over ``mesh[axis]`` devices.

    x: (B, T, D) with B sharded over `axis` when batch_dim_sharded (the
    usual dp==ep layout); params.w1/b1/w2/b2 sharded over `axis` on the
    leading expert dim; gate replicated. Each device routes its local
    tokens, one all_to_all sends expert buffers to the expert's owner,
    the FFN runs on E/n local experts, and the reverse all_to_all brings
    the outputs home for the weighted combine.
    """
    n_dev = mesh.shape[axis]
    e = params.gate_w.shape[-1]
    if e % n_dev != 0:
        raise ValueError("num_experts %d must divide over %d ep devices"
                         % (e, n_dev))

    xspec = P(axis) if batch_dim_sharded else P()
    pspec = MoEParams(gate_w=P(), w1=P(axis), b1=P(axis), w2=P(axis),
                      b2=P(axis))

    def device_fn(x_local, p):
        p = MoEParams(*p)
        lead = x_local.shape[:-1]
        d = x_local.shape[-1]
        tokens = x_local.reshape(-1, d)
        n_loc = tokens.shape[0]
        cap = moe_capacity(n_loc, e, capacity_factor)
        # router in f32 (see moe_ffn_local)
        dispatch, combine = _dispatch_tensors(
            tokens.astype(jnp.float32) @ p.gate_w, e, cap, k)
        # expert buffers stay in the input dtype: the two all_to_alls move
        # HALF the ICI bytes under bf16, and the matmuls run bf16 MXU with
        # f32 accumulation (no-op for f32 inputs; gating stays f32)
        xdt = tokens.dtype
        expert_in = jnp.einsum(
            "nec,nd->ecd", dispatch.astype(xdt), tokens,
            preferred_element_type=jnp.float32).astype(xdt)  # (E, C, D)
        # exchange: split the expert dim across devices, concat the
        # gathered shards along capacity -> (E/n, n*C, D) on each device
        expert_in = lax.all_to_all(expert_in, axis, split_axis=0,
                                   concat_axis=1, tiled=True)
        h = activation(jnp.einsum("ecd,edf->ecf", expert_in,
                                  p.w1.astype(xdt),
                                  preferred_element_type=jnp.float32)
                       + p.b1[:, None, :])
        expert_out = (jnp.einsum("ecf,efd->ecd", h.astype(xdt),
                                 p.w2.astype(xdt),
                                 preferred_element_type=jnp.float32)
                      + p.b2[:, None, :]).astype(xdt)
        # reverse exchange: back to (E, C, D) rows owned by this device's
        # tokens
        expert_out = lax.all_to_all(expert_out, axis, split_axis=1,
                                    concat_axis=0, tiled=True)
        out = jnp.einsum("nec,ecd->nd", combine,  # combine is already f32
                         expert_out.astype(jnp.float32))
        return out.astype(x_local.dtype).reshape(lead + (d,))

    # the replication/VMA check is disabled: with replicated tokens
    # (batch_dim_sharded=False) the output is mathematically replicated
    # over `axis` but the checker cannot prove it through the all_to_all
    # pair.
    fn = shard_map(device_fn, check_vma=False, mesh=mesh,
                   in_specs=(xspec, tuple(pspec)), out_specs=xspec)
    return fn(x, tuple(params))
