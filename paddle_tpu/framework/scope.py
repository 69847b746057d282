"""Scope: runtime variable store, and Place: device abstraction.

Reference: paddle/fluid/framework/scope.h (Scope holds Variables by name,
hierarchical) and paddle/fluid/platform/place.h (CPUPlace / CUDAPlace).

TPU-native: a Scope maps names to live ``jax.Array``s (device-resident,
possibly sharded across a Mesh). Memory is owned by XLA — there is no buddy
allocator to port; donation in the executor gives in-place parameter update
semantics without copies.
"""
from __future__ import annotations

from typing import Dict, Optional

__all__ = ["Scope", "CPUPlace", "TPUPlace", "CUDAPlace", "CUDAPinnedPlace",
           "default_place", "current_device", "global_scope", "scope_guard"]


class Scope:
    def __init__(self, parent: Optional["Scope"] = None):
        self.parent = parent
        self.vars: Dict[str, object] = {}
        self.kids = []

    def var(self, name: str):
        """Find or create (as None placeholder) a variable slot."""
        if name not in self.vars and (self.parent is None or self.parent.find_var(name) is None):
            self.vars[name] = None
        return self.find_var(name)

    def find_var(self, name: str):
        if name in self.vars:
            return self.vars[name]
        if self.parent is not None:
            return self.parent.find_var(name)
        return None

    def has_var(self, name: str) -> bool:
        return name in self.vars or (self.parent is not None and self.parent.has_var(name))

    def set_var(self, name: str, value):
        self.vars[name] = value

    def erase(self, name: str):
        self.vars.pop(name, None)

    def new_scope(self) -> "Scope":
        kid = Scope(self)
        self.kids.append(kid)
        return kid

    def drop_kids(self):
        """Release all child scopes (reference Scope::DropKids); their
        arrays are freed once no fetched value references them."""
        self.kids = []

    def local_var_names(self):
        return list(self.vars.keys())


class Place:
    """Base device place. Resolves to a concrete jax.Device of ITS OWN
    platform or raises: a place never stands for another device."""

    _kind = "cpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def jax_device(self):
        import jax

        try:  # this process's own devices (a multi-host job sees all)
            devs = jax.local_devices(backend=self._kind)
        except RuntimeError as e:
            raise RuntimeError(
                "%r: no %s device is visible to JAX (default backend %r): %s"
                % (self, self._kind, jax.default_backend(), e)) from None
        if self.device_id >= len(devs):
            raise RuntimeError(
                "%r: only %d %s device(s) visible"
                % (self, len(devs), self._kind))
        return devs[self.device_id]

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)


class CPUPlace(Place):
    _kind = "cpu"


class TPUPlace(Place):
    _kind = "tpu"


# The reference's CUDAPlace; maps to the accelerator (TPU) so that reference
# scripts using CUDAPlace run unchanged.
class CUDAPlace(TPUPlace):
    pass


# Pinned (page-locked) host memory is a CUDA transfer optimization; on TPU
# feeds stage through the C++ arena instead, so this is plain host memory.
class CUDAPinnedPlace(CPUPlace):
    pass


def default_place() -> Place:
    """The place of JAX's default device: TPUPlace(0) where a chip is
    attached, CPUPlace(0) otherwise. What an Executor built with no place
    runs on."""
    import jax

    return TPUPlace() if jax.default_backend() == "tpu" else CPUPlace()


def current_device():
    """The device a computation traced NOW will run on: the one an
    enclosing ``jax.default_device`` names (an Executor runs under its
    place's), else JAX's default. Kernel dispatch and the AOT cache key
    ask this, not ``jax.default_backend()``, so a CPUPlace executor on a
    chip machine gets CPU kernels and CPU cache entries."""
    import jax

    dev = jax.config.jax_default_device
    if dev is None:
        return jax.local_devices()[0]
    if isinstance(dev, str):
        return jax.local_devices(backend=dev)[0]
    return dev


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


import contextlib


@contextlib.contextmanager
def scope_guard(scope: Scope):
    global _global_scope
    prev, _global_scope = _global_scope, scope
    try:
        yield
    finally:
        _global_scope = prev
