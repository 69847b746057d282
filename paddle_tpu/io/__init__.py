"""Model persistence: vars, inference models, training checkpoints.

Reference: python/paddle/fluid/io.py (save_vars/save_params/
save_persistables/load_* and save/load_inference_model, which run C++
`save`/`load` ops writing LoDTensor protobufs) and trainer.py:
save_checkpoint/load_checkpoint.

TPU-native format:
- variables: one ``.npy`` per var, or a single ``.npz`` when ``filename``
  is given (the reference's save_combine). Device arrays are fetched from
  the Scope — there are no save ops in the graph.
- inference model: program JSON (framework/core.py serialization) +
  params npz. Loading returns a ready-to-jit Program.
- checkpoints: step + program fingerprint + every persistable (parameters
  AND optimizer accumulators AND bn stats), with retention like the
  reference's max_num_checkpoints. For multi-host sharded state, orbax
  (save_sharded_checkpoint) writes each host's shards in parallel.
"""
from __future__ import annotations

import json
import os
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..checkpoint import (
    CheckpointFingerprintWarning,
    CheckpointMismatchError,
    check_fingerprint,
)
from ..checkpoint import layout as _ckpt_layout
from ..framework.core import Parameter, Program, Variable, default_main_program
from ..framework.dtypes import as_numpy_dtype, convert_dtype
from ..framework.scope import Scope, global_scope

__all__ = [
    "is_parameter",
    "is_persistable",
    "save_vars",
    "save_params",
    "save_persistables",
    "load_vars",
    "load_params",
    "load_persistables",
    "get_inference_program",
    "save_inference_model",
    "load_inference_model",
    "save_checkpoint",
    "load_checkpoint",
    "clean_checkpoint",
    "get_latest_checkpoint_serial",
    "get_parameter_value",
    "get_parameter_value_by_name",
    "save_sharded_checkpoint",
    "load_sharded_checkpoint",
    "CheckpointFingerprintWarning",
    "CheckpointMismatchError",
    "DataLoader",
]

_MODEL_FILE = "__model__"
_CKPT_PREFIX = _ckpt_layout.CKPT_PREFIX


def is_parameter(var: Variable) -> bool:
    """Reference: io.py:is_parameter."""
    return isinstance(var, Parameter)


def is_persistable(var: Variable) -> bool:
    """Reference: io.py:is_persistable."""
    return bool(var.persistable)


def _np_name(name: str) -> str:
    # var names are filesystem-safe except path separators
    return name.replace("/", "%2F")


def _npz_path(dirname: str, filename: str) -> str:
    # np.savez appends ".npz" to extensionless paths; normalize so that
    # save(filename="__params__") and load(filename="__params__") agree
    if not filename.endswith(".npz"):
        filename += ".npz"
    return os.path.join(dirname, filename)


def _scope_of(executor, scope: Optional[Scope]) -> Scope:
    return scope if scope is not None else global_scope()


# ---------------------------------------------------------------------------
# save/load vars
# ---------------------------------------------------------------------------


def save_vars(
    executor,
    dirname: str,
    main_program: Optional[Program] = None,
    vars: Optional[Sequence[Variable]] = None,
    predicate: Optional[Callable[[Variable], bool]] = None,
    filename: Optional[str] = None,
    scope: Optional[Scope] = None,
):
    """Reference: io.py:save_vars. Values come from the Scope (the runtime
    store), not from graph save ops."""
    scope = _scope_of(executor, scope)
    if vars is None:
        program = main_program if main_program is not None else default_main_program()
        vars = [v for v in program.list_vars() if predicate is None or predicate(v)]
    os.makedirs(dirname, exist_ok=True)
    arrays: Dict[str, np.ndarray] = {}
    for var in vars:
        name = var.name if isinstance(var, Variable) else str(var)
        val = scope.find_var(name)
        if val is None:
            raise RuntimeError("variable %r has no value in scope" % name)
        arrays[name] = np.asarray(val)
    if filename is not None:
        np.savez(_npz_path(dirname, filename), **{_np_name(k): v for k, v in arrays.items()})
    else:
        for name, arr in arrays.items():
            np.save(os.path.join(dirname, _np_name(name) + ".npy"), arr)
    return sorted(arrays)


def save_params(executor, dirname, main_program=None, filename=None, scope=None):
    """Reference: io.py:save_params — trainable parameters only."""
    return save_vars(executor, dirname, main_program=main_program,
                     predicate=is_parameter, filename=filename, scope=scope)


def save_persistables(executor, dirname, main_program=None, filename=None, scope=None):
    """Reference: io.py:save_persistables — params + optimizer accumulators
    + bn stats + lr vars: everything needed to resume."""
    return save_vars(executor, dirname, main_program=main_program,
                     predicate=is_persistable, filename=filename, scope=scope)


def load_vars(
    executor,
    dirname: str,
    main_program: Optional[Program] = None,
    vars: Optional[Sequence[Variable]] = None,
    predicate: Optional[Callable[[Variable], bool]] = None,
    filename: Optional[str] = None,
    scope: Optional[Scope] = None,
):
    """Reference: io.py:load_vars. Loaded arrays are set in the Scope as
    XLA-owned device buffers (checkpoint.manager.device_owned): compiled
    training steps DONATE state buffers, and donating memory XLA did not
    allocate (a zero-copy view of a numpy array) corrupts the heap on
    the warm-AOT resume path."""
    from ..checkpoint.manager import device_owned_tree

    scope = _scope_of(executor, scope)
    if vars is None:
        program = main_program if main_program is not None else default_main_program()
        vars = [v for v in program.list_vars() if predicate is None or predicate(v)]
    names = [v.name if isinstance(v, Variable) else str(v) for v in vars]
    if filename is not None:
        with np.load(_npz_path(dirname, filename)) as npz:
            data = {k: npz[k] for k in npz.files}
        wanted = {}
        for name in names:
            key = _np_name(name)
            if key not in data:
                raise RuntimeError("variable %r not found in %s" % (name, filename))
            wanted[name] = data[key]
        for name, val in device_owned_tree(wanted).items():
            scope.set_var(name, val)
    else:
        loaded = {}
        for name in names:
            path = os.path.join(dirname, _np_name(name) + ".npy")
            if not os.path.exists(path):
                raise RuntimeError("variable file %s does not exist" % path)
            loaded[name] = np.load(path)
        for name, val in device_owned_tree(loaded).items():
            scope.set_var(name, val)
    return sorted(names)


def load_params(executor, dirname, main_program=None, filename=None, scope=None):
    return load_vars(executor, dirname, main_program=main_program,
                     predicate=is_parameter, filename=filename, scope=scope)


def load_persistables(executor, dirname, main_program=None, filename=None, scope=None):
    return load_vars(executor, dirname, main_program=main_program,
                     predicate=is_persistable, filename=filename, scope=scope)


def get_parameter_value(para: Parameter, executor, scope=None) -> np.ndarray:
    """Reference: io.py:get_parameter_value."""
    return get_parameter_value_by_name(para.name, executor, scope=scope)


def get_parameter_value_by_name(name: str, executor, program=None, scope=None) -> np.ndarray:
    val = _scope_of(executor, scope).find_var(name)
    if val is None:
        raise RuntimeError("variable %r has no value in scope" % name)
    return np.asarray(val)


# ---------------------------------------------------------------------------
# inference model
# ---------------------------------------------------------------------------


def _prune_for_targets(program: Program, target_names: List[str]) -> Program:
    """Backward slice: keep only ops whose outputs (transitively) feed the
    targets. Plays the role of the reference's Program.prune()."""
    pruned = program.clone(for_test=True)
    gb = pruned.global_block()
    needed = set(target_names)
    kept = []
    for op in reversed(gb.ops):
        if any(n in needed for n in op.output_arg_names):
            kept.append(op)
            needed.update(op.input_arg_names)
    gb.ops = list(reversed(kept))
    pruned._bump()
    return pruned


def get_inference_program(target_vars, main_program: Optional[Program] = None) -> Program:
    """Reference: io.py:get_inference_program."""
    program = main_program if main_program is not None else default_main_program()
    if not isinstance(target_vars, (list, tuple)):
        target_vars = [target_vars]
    names = [v.name if isinstance(v, Variable) else str(v) for v in target_vars]
    return _prune_for_targets(program, names)


def save_inference_model(
    dirname: str,
    feeded_var_names: Sequence[str],
    target_vars: Sequence,
    executor,
    main_program: Optional[Program] = None,
    model_filename: Optional[str] = None,
    params_filename: Optional[str] = None,
    export_for_deployment: bool = True,
    scope: Optional[Scope] = None,
    optimize: int = 0,
    quantize=None,
):
    """Reference: io.py:save_inference_model. Writes the pruned inference
    program as JSON plus the params it needs.

    ``optimize=1|2`` additionally runs the optimizing transpiler
    (transpiler/passes/) over the pruned program before export: folded
    constants ship as parameters, fused ops ship fused, and at level 2
    the bucketize stamp rides the program JSON so any Predictor serving
    the directory buckets its feed signatures.

    ``quantize=CalibrationTable`` (paddle_tpu.quant) exports the int8
    post-training-quantized program instead: the full level-3 pipeline
    runs (fuse -> quantize -> bucketize), int8 weights ship as the
    exported params (the float originals are dropped from the export),
    and the quantized stamp rides the JSON. The source program and
    Scope keep their float values — raw and quantized exports of one
    model coexist, as do their AOT-cached executables."""
    program = main_program if main_program is not None else default_main_program()
    if not isinstance(target_vars, (list, tuple)):
        target_vars = [target_vars]
    target_names = [v.name if isinstance(v, Variable) else str(v) for v in target_vars]
    pruned = _prune_for_targets(program, target_names)
    if quantize is not None:
        from ..transpiler.passes import optimize_program

        pruned, _opt_ctx = optimize_program(
            pruned, scope=_scope_of(executor, scope),
            level=max(int(optimize), 3), feed_names=feeded_var_names,
            fetch_names=target_names, calib=quantize)
        if not getattr(pruned, "_quantized", None):
            raise ValueError(
                "quantize= was given but no op quantized — the "
                "calibration table covers none of this program's "
                "fc/conv activations (calibrate against the same "
                "inference program you export)")
    elif optimize:
        from ..transpiler.passes import optimize_program

        pruned, _opt_ctx = optimize_program(
            pruned, scope=_scope_of(executor, scope), level=int(optimize),
            feed_names=feeded_var_names, fetch_names=target_names)

    os.makedirs(dirname, exist_ok=True)
    meta = {
        "feed_names": list(feeded_var_names),
        "fetch_names": target_names,
        "program": pruned.to_dict(),
    }
    model_filename = model_filename or _MODEL_FILE
    with open(os.path.join(dirname, model_filename), "w") as f:
        json.dump(meta, f)

    # params actually referenced by the pruned program (any block)
    used = {n for blk in pruned.blocks for op in blk.ops for n in op.input_arg_names}
    params = [v for v in pruned.list_vars() if is_persistable(v) and v.name in used]
    save_vars(executor, dirname, vars=params,
              filename=params_filename or "__params__.npz", scope=scope)
    return target_names


def load_inference_model(
    dirname: str,
    executor,
    model_filename: Optional[str] = None,
    params_filename: Optional[str] = None,
    scope: Optional[Scope] = None,
):
    """Reference: io.py:load_inference_model →
    (program, feed_target_names, fetch_targets)."""
    from ..checkpoint.manager import device_owned_tree

    model_filename = model_filename or _MODEL_FILE
    with open(os.path.join(dirname, model_filename)) as f:
        meta = json.load(f)
    program = Program.from_dict(meta["program"])
    scope = _scope_of(executor, scope)
    path = _npz_path(dirname, params_filename or "__params__.npz")
    if os.path.exists(path):
        with np.load(path) as npz:
            params = {key.replace("%2F", "/"): npz[key]
                      for key in npz.files}
        # numpy has no bfloat16: a parameter held in it comes back from
        # the archive as 2-byte voids, the same bytes
        for name, val in params.items():
            var = program.global_block()._find_var_recursive(name)
            if (val.dtype.kind == "V" and val.dtype.itemsize == 2
                    and var is not None
                    and convert_dtype(var.dtype) == "bfloat16"):
                params[name] = val.view(as_numpy_dtype("bfloat16"))
        for name, val in device_owned_tree(params).items():
            scope.set_var(name, val)
    fetch_targets = [program.global_block().var(n) for n in meta["fetch_names"]]
    return program, list(meta["feed_names"]), fetch_targets


# ---------------------------------------------------------------------------
# training checkpoints
# ---------------------------------------------------------------------------


def save_checkpoint(
    executor,
    checkpoint_dir: str,
    trainer_id: int = 0,
    main_program: Optional[Program] = None,
    max_num_checkpoints: int = 3,
    step: int = 0,
    epoch: int = 0,
    scope: Optional[Scope] = None,
    extra_meta: Optional[dict] = None,
):
    """Reference: trainer.py:save_checkpoint — serial-numbered dirs with
    retention; stores every persistable + meta (step/epoch/fingerprint).

    Crash-safe: the whole checkpoint is assembled in a ``tmp-`` sibling
    (files fsynced, ``_COMPLETE`` sentinel last) and atomically renamed
    into place (checkpoint/layout.py) — a crash mid-save can no longer
    leave a highest-numbered corrupt serial that bricks the next
    restart. Readers skip anything without the sentinel."""
    from ..checkpoint.manager import _encode_npz

    program = main_program if main_program is not None else default_main_program()
    scope = _scope_of(executor, scope)
    arrays: Dict[str, np.ndarray] = {}
    for v in program.list_vars():
        if is_persistable(v):
            val = scope.find_var(v.name)
            if val is None:
                raise RuntimeError(
                    "variable %r has no value in scope" % v.name)
            arrays[v.name] = np.asarray(val)
    serial = _ckpt_layout.next_serial(checkpoint_dir)
    meta = {
        "step": step,
        "epoch": epoch,
        "trainer_id": trainer_id,
        "fingerprint": program.fingerprint(),
        "persistable_names": sorted(arrays),
    }
    if extra_meta:
        meta.update(extra_meta)
    _ckpt_layout.write_checkpoint(
        checkpoint_dir, serial,
        {_ckpt_layout.PERSISTABLES_FILE: _encode_npz(arrays)}, meta=meta)
    _ckpt_layout.retention_gc(checkpoint_dir, max_num_checkpoints)
    return serial


def load_checkpoint(
    executor,
    checkpoint_dir: str,
    serial: Optional[int] = None,
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
    strict: Optional[bool] = None,
) -> dict:
    """Reference: trainer.py:load_checkpoint. Returns the meta dict
    (step/epoch) so training loops can resume counters.

    Only COMPLETE checkpoints load: incomplete or sentinel-less serials
    (a crash mid-save under the old in-place writer) are skipped when
    picking the newest, and refused when named explicitly. A program-
    fingerprint mismatch warns (``CheckpointFingerprintWarning``) by
    default; ``strict=True`` (or ``PADDLE_TPU_CKPT_STRICT=1``) raises
    ``CheckpointMismatchError`` with both fingerprints and the
    differing persistable names — BEFORE any scope mutation."""
    program = main_program if main_program is not None else default_main_program()
    if serial is None:
        serial = get_latest_checkpoint_serial(checkpoint_dir)
    if serial < 0:
        raise RuntimeError(
            "no complete checkpoint found under %s (partial/corrupt "
            "saves are skipped)" % checkpoint_dir)
    cur = _ckpt_layout.serial_dir(checkpoint_dir, serial)
    if not _ckpt_layout.is_complete(cur):
        raise RuntimeError(
            "checkpoint serial %d under %s is incomplete (missing the %s "
            "sentinel — likely a crashed save); pass serial=None to load "
            "the newest complete one" % (
                serial, checkpoint_dir, _ckpt_layout.SENTINEL))
    meta = _ckpt_layout.read_meta(cur)
    check_fingerprint(meta, program, strict=strict)
    load_persistables(executor, cur, main_program=program,
                      filename="__persistables__.npz", scope=scope)
    return meta


def clean_checkpoint(checkpoint_dir: str, delete_dir: bool = False):
    """Reference: trainer.py:clean_checkpoint (partials included)."""
    import shutil

    for s in _ckpt_layout.all_serials(checkpoint_dir):
        shutil.rmtree(_ckpt_layout.serial_dir(checkpoint_dir, s),
                      ignore_errors=True)
    for path, serial, _complete in _ckpt_layout.list_entries(checkpoint_dir):
        if serial is None:
            shutil.rmtree(path, ignore_errors=True)
    if delete_dir and os.path.isdir(checkpoint_dir) and not os.listdir(checkpoint_dir):
        os.rmdir(checkpoint_dir)


def get_latest_checkpoint_serial(checkpoint_dir: str) -> int:
    """Reference: io.py/trainer.py:get_latest_checkpoint_serial (-1 when
    none exist). Counts COMPLETE checkpoints only — a crashed partial,
    however high its serial, is invisible."""
    return _ckpt_layout.latest_serial(checkpoint_dir)


# ---------------------------------------------------------------------------
# sharded (multi-host) checkpoints — orbax-backed
# ---------------------------------------------------------------------------


def save_sharded_checkpoint(
    checkpoint_dir: str,
    step: int,
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
):
    """Multi-host/sharded state: each host writes only its shards via orbax
    — the dense-checkpoint twin of the reference's per-pserver save path
    (distribute_transpiler)."""
    import orbax.checkpoint as ocp

    program = main_program if main_program is not None else default_main_program()
    scope = scope if scope is not None else global_scope()
    state = {}
    for v in program.list_vars():
        if is_persistable(v):
            val = scope.find_var(v.name)
            if val is not None:
                state[v.name] = val
    path = os.path.abspath(os.path.join(checkpoint_dir, "sharded_%d" % step))
    try:
        os.makedirs(os.path.abspath(checkpoint_dir), exist_ok=True)
        ocp.PyTreeCheckpointer().save(path, state)
    except Exception as e:
        # orbax failures surface as deep tracebacks (asyncio gather over
        # per-array futures); translate to something actionable
        raise RuntimeError(
            "sharded checkpoint save to %r failed (%s: %s) — check that "
            "%r is writable and has free space; orbax stages shard files "
            "under the target before an atomic finalize, so nothing "
            "partial was published" % (
                path, type(e).__name__, e, checkpoint_dir)) from e
    return path


def load_sharded_checkpoint(
    checkpoint_dir: str,
    step: int,
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
):
    import orbax.checkpoint as ocp

    scope = scope if scope is not None else global_scope()
    path = os.path.abspath(os.path.join(checkpoint_dir, "sharded_%d" % step))
    if not os.path.isdir(path):
        import re as _re

        available = sorted(
            int(m.group(1))
            for entry in (os.listdir(checkpoint_dir)
                          if os.path.isdir(checkpoint_dir) else [])
            for m in [_re.fullmatch(r"sharded_(\d+)", entry)] if m)
        raise FileNotFoundError(
            "no sharded checkpoint for step %d under %s (available "
            "steps: %s)" % (step, checkpoint_dir, available or "none"))
    try:
        state = ocp.PyTreeCheckpointer().restore(path)
    except Exception as e:
        raise RuntimeError(
            "sharded checkpoint at %r is unreadable or incomplete "
            "(%s: %s) — if the writing job was preempted mid-save, fall "
            "back to an earlier step (available under %s)" % (
                path, type(e).__name__, e, checkpoint_dir)) from e
    from ..checkpoint.manager import device_owned_tree

    # XLA-owned buffers: the executor donates state (see load_vars)
    for name, val in device_owned_tree(dict(state)).items():
        scope.set_var(name, val)
    return sorted(state)


# reader-op pipeline (py_reader / double_buffer / recordio readers)
from . import reader  # noqa: E402,F401
from .reader import EOFException  # noqa: E402,F401
# multiprocess input fast path (shared-memory zero-copy batches)
from . import dataloader  # noqa: E402,F401
from .dataloader import DataLoader  # noqa: E402,F401
