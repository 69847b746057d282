"""Operator kernel registry.

The reference registers per-device C++ kernels through OpKernelType and a
global OpInfoMap (reference: paddle/fluid/framework/op_registry.h,
op_info.cc). Here every op has exactly ONE implementation: a pure function
from JAX arrays to JAX arrays. The tracer (framework/trace.py) calls these
while tracing a Block, and XLA compiles + fuses the whole program — there is
no per-op dispatch at run time.

Kernel signature::

    @register_op("relu")
    def relu(ctx):
        return {"Out": jnp.maximum(ctx.input("X"), 0)}

``ctx`` (OpContext) gives inputs, attrs, output var metadata, a PRNG stream,
and a callback to trace sub-blocks (control flow).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

KERNELS: Dict[str, Callable] = {}
# op types registered with ``names_device_calls`` (``register_op``)
NAMES_DEVICE_CALLS: set = set()

# ops that need train/test awareness, rng, etc. can inspect ctx freely.


def register_op(op_type: str, names_device_calls: bool = False):
    """``names_device_calls``: the kernel names Mosaic calls that a
    reader tells apart by the transform jax wraps around them
    (``jvp_ptpu.flash_fwd_``): ``trace.trace_op`` then opens no scope
    of its own around the kernel where it is differentiated."""
    def deco(fn):
        if op_type in KERNELS:
            raise ValueError("duplicate kernel for op %r" % op_type)
        KERNELS[op_type] = fn
        if names_device_calls:
            NAMES_DEVICE_CALLS.add(op_type)
        return fn

    return deco


def get_kernel(op_type: str) -> Callable:
    if op_type not in KERNELS:
        # same rendering helper the static analyzer's diagnostics use
        # (analysis/diagnostics.py), so registry errors and lint findings
        # suggest alike
        from ..analysis.diagnostics import did_you_mean

        raise NotImplementedError(
            "no TPU kernel registered for op %r (registered: %d ops)%s"
            % (op_type, len(KERNELS), did_you_mean(op_type, KERNELS))
        )
    return KERNELS[op_type]


def op_support_tpu(op_type: str) -> bool:
    """Reference parity with core.op_support_gpu (pybind/pybind.cc)."""
    return op_type in KERNELS


def registered_ops() -> List[str]:
    return sorted(KERNELS)


class OpProtoHolder:
    """Reference parity with framework.OpProtoHolder (python/paddle/fluid/
    framework.py): singleton answering "which ops exist / is this op
    registered". Slot/attr schemas live in the kernels themselves here (one
    python function per op), so the proto is just the registry entry."""

    _instance = None

    @classmethod
    def instance(cls) -> "OpProtoHolder":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def get_op_proto(self, type: str):
        if type not in KERNELS:
            raise ValueError("Operator \"%s\" has not been registered." % type)
        return KERNELS[type]

    def get_all_op_protos(self):
        return [KERNELS[k] for k in registered_ops()]

    def has_op_proto(self, type: str) -> bool:
        return type in KERNELS


class OpContext:
    """Per-op view handed to a kernel during tracing."""

    def __init__(self, op, env, rng_fn, subblock_fn=None, block=None):
        self._op = op
        self._env = env
        self._rng_fn = rng_fn
        self._subblock_fn = subblock_fn
        self._block = block

    # -- inputs ---------------------------------------------------------
    def input(self, slot: str, default=None):
        names = self._op.input(slot)
        if not names:
            return default
        return self._env[names[0]]

    def inputs(self, slot: str) -> list:
        return [self._env[n] for n in self._op.input(slot)]

    def has_input(self, slot: str) -> bool:
        return bool(self._op.input(slot))

    def input_name(self, slot: str) -> Optional[str]:
        names = self._op.input(slot)
        return names[0] if names else None

    # -- attrs / metadata ------------------------------------------------
    def attr(self, name: str, default=None):
        return self._op.attr(name, default)

    @property
    def op(self):
        return self._op

    def out_var(self, slot: str, idx: int = 0):
        """Variable metadata (shape/dtype) for an output slot."""
        name = self._op.output(slot)[idx]
        return self._block.var(name)

    def out_dtype(self, slot: str = "Out"):
        import numpy as np

        from ..framework.dtypes import as_numpy_dtype

        return as_numpy_dtype(self.out_var(slot).dtype)

    def value(self, name: str, default=None):
        """Current env value of an arbitrary variable name (used by ops that
        read their own output slot, e.g. write_to_array)."""
        return self._env[name] if name in self._env else default

    def full_env(self) -> dict:
        """Snapshot of the whole tracing env (control-flow ops close over
        outer values when tracing their sub-blocks)."""
        snap = getattr(self._env, "snapshot", None)
        return snap() if snap is not None else dict(self._env)

    # -- services --------------------------------------------------------
    def rng(self):
        """A fresh jax PRNG key for this op invocation."""
        return self._rng_fn()

    def trace_subblock(self, block_idx: int, env: dict, salt=None) -> dict:
        """Trace a sub-block into `env`. `salt` (a possibly-traced loop
        counter) is folded into every RNG key drawn inside, so stochastic
        ops get fresh bits per loop iteration."""
        if salt is None:
            return self._subblock_fn(block_idx, env)
        return self._subblock_fn(block_idx, env, salt)

    @property
    def is_test(self) -> bool:
        return bool(self._op.attr("is_test", False))
