"""shard_map helpers shared by the parallel modules."""
from __future__ import annotations

import jax
from jax import lax

shard_map = jax.shard_map

__all__ = ["shard_map", "shard_map_partial", "pvary"]


def pvary(x, axes):
    """Mark x varying over manual mesh axes (shard_map vma typing).
    `axes`: one axis name or a tuple. IDEMPOTENT: axes x already varies
    over are skipped (pcast rejects varying->varying, and callers often
    promote loop carries that are invariant only on the first
    ring/pipeline step)."""
    if not isinstance(axes, tuple):
        axes = (axes,)
    have = jax.typeof(x).vma
    axes = tuple(a for a in axes if a not in have)
    if not axes:
        return x
    return lax.pcast(x, axes, to="varying")


def shard_map_partial(f, mesh, in_specs, out_specs, manual_axes):
    """shard_map manual over `manual_axes` only; any other mesh axes stay
    automatic (GSPMD partitions over them inside the manual region —
    e.g. the pipeline tick loop is manual over (dp, pp) while tensor
    parallelism rides an auto mp axis)."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     axis_names=set(manual_axes))
