"""API-parity audit: compare paddle_tpu's public surface against the
reference tree, module by module, and print a coverage table.

Usage:
    JAX_PLATFORMS=cpu python tools/parity_report.py [--ref /root/reference]

For every reference module with an __all__ (fluid layers/*, fluid
top-level modules, paddle.reader, fluid.contrib), reports which symbols
exist here and lists any missing ones — including symbols added through
``__all__ += ...`` and list-variable concatenations like
``+ __activations__``. Also diffs the reference's operator registrations
(paddle/fluid/operators/**/*_op.cc, subdirectories included) against the
kernel registry, bucketing misses by why they are intentionally absent
(LoD/selected-rows/RPC machinery replaced by the dense GSPMD design).

``main()`` returns (symbol_rows, unexplained_ops) so tests/test_parity.py
can assert exact emptiness rather than parsing the printout.
"""
from __future__ import annotations

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# op families the dense/XLA design replaces wholesale rather than ports
INTENTIONAL = {
    "lod/tensor-array machinery (dense + lengths design)": {
        "array_to_lod_tensor", "lod_tensor_to_array", "lod_rank_table",
        "max_sequence_len", "merge_lod_tensor", "split_lod_tensor",
        "shrink_rnn_memory", "rnn_memory_helper", "tensor_array_read_write",
        "reorder_lod_tensor_by_rank",
    },
    "selected-rows machinery (dense scatter-add gradients)": {
        "extract_rows", "lookup_sparse_table", "merge_ids", "split_ids",
        "split_selected_rows", "split_byref",
    },
    "pserver/RPC stack (GSPMD sharding replaces it)": {
        "listen_and_serv", "send", "recv", "send_barrier", "fetch_barrier",
        "prefetch", "checkpoint_notify", "gen_nccl_id", "send_recv_util",
    },
    "executor-level plumbing (executor/scope handle these)": {
        "feed", "fetch", "save", "save_combine", "load", "load_combine",
        "delete_var",
    },
    "host-side CSP (fluid.concurrency)": {
        "channel_create", "channel_send", "channel_recv", "channel_close",
        "go", "select",
    },
    "reader-op pipeline (executor pulls from io/reader.py holders)": {
        # reference operators/reader/*: each C++ reader decorator maps to
        # a host-side pipeline stage behind the `read` op
        "create_py_reader", "create_double_buffer_reader",
        "create_batch_reader", "create_shuffle_reader",
        "create_multi_pass_reader", "create_threaded_reader",
        "create_random_data_generator", "create_recordio_file_reader",
        "create_custom_reader",  # layers.Preprocessor / PreprocessReader
        "open_files", "read",
        # REGISTER_FILE_READER(recordio, ...) is a file-FORMAT tag, not an
        # op; the C++ recordio reader in runtime/ serves the same role
        "recordio",
    },
    "NCCL collectives (XLA psum/all_gather/ppermute over ICI replace them)": {
        "ncclInit", "ncclAllReduce", "ncclReduce", "ncclBcast",
    },
    "host-side multi-device ops (Mesh/pjit + ParallelExecutor replace them)": {
        "parallel_do", "get_places",
    },
    "per-op RNN machinery (lax.scan StaticRNN/DynamicRNN replace it)": {
        "recurrent",
    },
    "layer-decomposed ops (the tracer emits mul/elementwise ops XLA re-fuses)": {
        "fc",
    },
}


_REG_CALL = re.compile(r"REGISTER_\w+\(\s*(\w+)")
_REG_DEFINE_PARAM = re.compile(r"#define\s+REGISTER_\w+\(\s*(\w+)")
_MACRO_LIST = re.compile(r"__macro\(\s*(\w+)\s*,\s*(\w+)")
_OP_NAME = re.compile(r"[a-z][a-zA-Z0-9_]*\Z")


def expand_op_cc(path, base):
    """Return the set of op names a reference *_op.cc actually registers
    (umbrella files like pool_with_index_op.cc register several ops).
    Handles the three registration idioms of the tree:
    - direct REGISTER_OPERATOR/REGISTER_OP*(name, ...) calls;
    - per-file helper macros (REGISTER_COMPARE_OP(less_than, ...)) —
      macro *parameters* are auto-excluded by harvesting every
      `#define REGISTER_*(param` name in the same file;
    - X-macro lists (activation_op: FOR_EACH_OP_FUNCTOR's
      `__macro(CamelName, snake_name)` rows), used only when the direct
      scan finds nothing so generic `__macro` args elsewhere can't leak.
    Grad registrations are dropped: autodiff is jax.vjp, not per-op grad
    kernels. Falls back to the file basename when nothing matches."""
    try:
        src = open(path, encoding="utf-8", errors="replace").read()
    except IOError:
        return {base}
    params = set(_REG_DEFINE_PARAM.findall(src))
    names = {n for n in _REG_CALL.findall(src)
             if n not in params and _OP_NAME.match(n)
             and not n.endswith("_grad")}
    if not names:
        names = {n for pair in _MACRO_LIST.findall(src) for n in pair
                 if _OP_NAME.match(n) and not n.endswith("_grad")}
    return names or {base}


def module_all(path):
    """All public symbols of a module: union of every list literal that
    feeds __all__ (direct assignment, +=, and `+ <listvar>` concatenation
    like layers/ops.py's __activations__)."""
    try:
        src = open(path, encoding="utf-8", errors="replace").read()
    except IOError:
        return None
    # list-literal assignments anywhere in the file: name -> symbols
    lists = {}
    for m in re.finditer(r"^(\w+)\s*\+?=\s*\[(.*?)\]", src, re.S | re.M):
        name, body = m.group(1), m.group(2)
        lists.setdefault(name, set()).update(
            re.findall(r"['\"](\w+)['\"]", body))
    if "__all__" not in lists:
        return None
    symbols = set(lists["__all__"])
    # pull in list variables referenced on any __all__ line (\Z: the
    # statement may be the last thing in the file)
    for m in re.finditer(r"^__all__\s*\+?=\s*(.+?)(?=^\S|\Z)", src,
                         re.S | re.M):
        for ref in re.findall(r"\b(__\w+__|\w+)\b", m.group(1)):
            if ref != "__all__" and ref in lists:
                symbols |= lists[ref]
    return sorted(symbols)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ref", default="/root/reference")
    args = ap.parse_args(argv)

    import paddle_tpu as fluid
    from paddle_tpu import layers

    rows = []
    total_have = total_want = 0

    fluid_dir = os.path.join(args.ref, "python", "paddle", "fluid")
    checks = [
        ("fluid.layers.nn", os.path.join(fluid_dir, "layers", "nn.py"), layers),
        ("fluid.layers.ops", os.path.join(fluid_dir, "layers", "ops.py"), layers),
        ("fluid.layers.tensor", os.path.join(fluid_dir, "layers", "tensor.py"), layers),
        ("fluid.layers.control_flow", os.path.join(fluid_dir, "layers", "control_flow.py"), layers),
        ("fluid.layers.io", os.path.join(fluid_dir, "layers", "io.py"), layers),
        ("fluid.layers.detection", os.path.join(fluid_dir, "layers", "detection.py"), layers),
        ("fluid.layers.metric_op", os.path.join(fluid_dir, "layers", "metric_op.py"), layers),
        ("fluid.layers.lr_scheduler", os.path.join(fluid_dir, "layers", "learning_rate_scheduler.py"), layers),
        ("fluid.layers.device", os.path.join(fluid_dir, "layers", "device.py"), layers),
        ("fluid.nets", os.path.join(fluid_dir, "nets.py"), fluid.nets),
        ("fluid.optimizer", os.path.join(fluid_dir, "optimizer.py"), fluid.optimizer),
        ("fluid.initializer", os.path.join(fluid_dir, "initializer.py"), fluid.initializer),
        ("fluid.regularizer", os.path.join(fluid_dir, "regularizer.py"), fluid.regularizer),
        ("fluid.clip", os.path.join(fluid_dir, "clip.py"), fluid.clip),
        ("fluid.metrics", os.path.join(fluid_dir, "metrics.py"), fluid.metrics),
        ("fluid.io", os.path.join(fluid_dir, "io.py"), fluid.io),
        ("fluid.average", os.path.join(fluid_dir, "average.py"), fluid.average),
        ("fluid.concurrency", os.path.join(fluid_dir, "concurrency.py"), fluid),
        ("fluid.recordio_writer", os.path.join(fluid_dir, "recordio_writer.py"), fluid.recordio_writer),
        ("paddle.reader", os.path.join(args.ref, "python", "paddle", "reader", "decorator.py"), fluid.reader),
        ("fluid.contrib.decoder", os.path.join(fluid_dir, "contrib", "decoder", "beam_search_decoder.py"), fluid.contrib),
    ]
    for label, path, target in checks:
        names = module_all(path)
        if names is None:
            continue
        missing = [n for n in names
                   if not hasattr(target, n) and not hasattr(fluid, n)]
        total_have += len(names) - len(missing)
        total_want += len(names)
        rows.append((label, len(names) - len(missing), len(names), missing))

    print("%-32s %9s  %s" % ("module", "coverage", "missing"))
    print("-" * 72)
    for label, have, want, missing in rows:
        print("%-32s %4d/%-4d  %s" % (label, have, want,
                                      ", ".join(missing) or "-"))
    print("-" * 72)
    if not total_want:
        raise SystemExit(
            "no reference modules with __all__ found under %r — wrong "
            "--ref path?" % args.ref)
    print("%-32s %4d/%-4d  (%.1f%%)" % ("TOTAL API symbols", total_have,
                                        total_want,
                                        100.0 * total_have / total_want))

    # operator diff: every *_op.cc anywhere under operators/ (the reader,
    # detection, nccl, ... subdirectories included)
    from paddle_tpu.ops.registry import registered_ops

    ours = set(registered_ops())
    op_dir = os.path.join(args.ref, "paddle", "fluid", "operators")
    ref_ops = set()
    n_files = 0
    for root, _dirs, files in os.walk(op_dir):
        for f in files:
            if f.endswith("_op.cc"):
                base = f[: -len("_op.cc")]
                if base.endswith("_mkldnn") or base == "tensorrt_engine":
                    continue
                n_files += 1
                ref_ops |= expand_op_cc(os.path.join(root, f), base)
    missing_ops = {o for o in ref_ops if o not in ours}
    explained = set()
    print("\nreference operators: %d files registering %d ops; "
          "registered kernels here: %d" % (n_files, len(ref_ops), len(ours)))
    for why, names in INTENTIONAL.items():
        hit = sorted(missing_ops & names)
        explained |= set(hit)
        if hit:
            print("  [by design] %s:\n      %s" % (why, ", ".join(hit)))
    rest = sorted(missing_ops - explained)
    print("  [unexplained gaps] %s" % (", ".join(rest) or "none"))
    return rows, rest


if __name__ == "__main__":
    main()
