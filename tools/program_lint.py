"""Program linter CLI: run the static analyzer over Program IR.

Targets (mix freely):

- a serialized program: a ``__model__`` JSON written by
  ``save_inference_model`` (feed/fetch metadata is used), a raw
  ``Program.to_dict()`` JSON, or a model DIRECTORY containing
  ``__model__``;
- an example SCRIPT (``--script build.py``): executed with fresh default
  programs, then the resulting default main program is linted (set
  ``LINT_FEEDS``/``LINT_FETCHES`` globals in the script to pass feed and
  fetch names);
- the bundled example models (``--example mlp|deepfm|lstm|all``) — the
  same graphs the benchmarks run, kept lint-clean by CI's
  ``lint-programs`` step.

Output: human-readable diagnostics (default) or ``--json`` (one document
covering all targets, including per-program infer coverage). Exit code 1
when any error-severity finding exists (``--strict``: warnings fail too).

Usage:
    JAX_PLATFORMS=cpu python tools/program_lint.py --example all
    python tools/program_lint.py path/to/__model__ --json
    python tools/program_lint.py --script examples/build_graph.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# CPU by default: linting is host-side graph analysis, it must run in CI
# and on laptops with no accelerator attached
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



# -- bundled example programs ---------------------------------------------

def _build_mlp():
    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer

    img = layers.data(name="pixel", shape=[784], dtype="float32")
    label = layers.data(name="label", shape=[1], dtype="int64")
    from paddle_tpu.models.mnist import mlp_model

    predict = mlp_model(img)
    cost = layers.cross_entropy(input=predict, label=label)
    avg_cost = layers.mean(cost)
    acc = layers.accuracy(input=predict, label=label)
    optimizer.SGD(learning_rate=0.01).minimize(avg_cost)
    return ["pixel", "label"], [avg_cost.name, acc.name]


def _build_deepfm():
    from paddle_tpu import layers, optimizer
    from paddle_tpu.models.deepfm import deepfm_net

    feat_ids = layers.data(name="feat_ids", shape=[10], dtype="int64")
    dense = layers.data(name="dense", shape=[13], dtype="float32")
    label = layers.data(name="label", shape=[1], dtype="int64")
    avg_cost, prob = deepfm_net(feat_ids, dense, label,
                                num_features=1000, num_fields=10)
    optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
    return ["feat_ids", "dense", "label"], [avg_cost.name, prob.name]


def _build_lstm():
    from paddle_tpu import layers, optimizer
    from paddle_tpu.models.stacked_lstm import stacked_lstm_net

    words = layers.data(name="words", shape=[80], dtype="int64")
    lengths = layers.data(name="lengths", shape=[], dtype="int32")
    label = layers.data(name="label", shape=[1], dtype="int64")
    predict = stacked_lstm_net(words, lengths, dict_dim=3000,
                               emb_dim=64, hid_dim=64, stacked_num=2)
    cost = layers.cross_entropy(input=predict, label=label)
    avg_cost = layers.mean(cost)
    optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
    return [words.name, lengths.name, label.name], [avg_cost.name]


def _build_decode():
    """The per-token KV-cache decode step (serving/decode.py): the graph
    the DecodeServer compiles once per (slots, slab) signature —
    decode_attention / cache_append / sampling ops stay lint-clean and
    infer-covered."""
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import transformer_lm_decode

    B, S, V, L, NH, D, DI, ML = 4, 64, 256, 2, 4, 64, 128, 128
    tokens = layers.data(name="tokens", shape=[B, 1], dtype="int64",
                         append_batch_size=False)
    positions = layers.data(name="positions", shape=[B, 1], dtype="int64",
                            append_batch_size=False)
    lengths = layers.data(name="lengths", shape=[B], dtype="int32",
                          append_batch_size=False)
    seed = layers.data(name="seed", shape=[1], dtype="int64",
                       append_batch_size=False)
    kc, vc = [], []
    for i in range(L):
        kc.append(layers.data(name="kcache_%d" % i,
                              shape=[B, S, NH, D // NH], dtype="float32",
                              append_batch_size=False))
        vc.append(layers.data(name="vcache_%d" % i,
                              shape=[B, S, NH, D // NH], dtype="float32",
                              append_batch_size=False))
    next_ids, logits, ncaches = transformer_lm_decode(
        tokens, positions, lengths, kc, vc, V, n_layer=L, n_head=NH,
        d_model=D, d_inner=DI, max_len=ML, strategy="topk", seed=seed)
    feeds = (["tokens", "positions", "lengths", "seed"]
             + [v.name for v in kc] + [v.name for v in vc])
    fetches = ([next_ids.name, logits.name]
               + [c.name for pair in ncaches for c in pair])
    return feeds, fetches


def _build_speculative():
    """The speculative VERIFY window (serving/decode.py kind="verify"):
    the graph that checks spec_k draft proposals in one call —
    cache_append_window / decode_attention_window / spec_accept stay
    lint-clean and infer-covered in CI (PR 14)."""
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import transformer_lm_verify

    B, T, S, V, L, NH, D, DI, ML = 4, 3, 64, 256, 2, 4, 64, 128, 128
    tokens = layers.data(name="tokens", shape=[B, T], dtype="int64",
                         append_batch_size=False)
    positions = layers.data(name="positions", shape=[B, T], dtype="int64",
                            append_batch_size=False)
    lengths = layers.data(name="lengths", shape=[B], dtype="int32",
                          append_batch_size=False)
    last_idx = layers.data(name="last_idx", shape=[B], dtype="int32",
                           append_batch_size=False)
    kc, vc = [], []
    for i in range(L):
        kc.append(layers.data(name="kcache_%d" % i,
                              shape=[B, S, NH, D // NH], dtype="float32",
                              append_batch_size=False))
        vc.append(layers.data(name="vcache_%d" % i,
                              shape=[B, S, NH, D // NH], dtype="float32",
                              append_batch_size=False))
    next_ids, accept, last_logits, ncaches = transformer_lm_verify(
        tokens, positions, lengths, last_idx, kc, vc, V, n_layer=L,
        n_head=NH, d_model=D, d_inner=DI, max_len=ML)
    feeds = (["tokens", "positions", "lengths", "last_idx"]
             + [v.name for v in kc] + [v.name for v in vc])
    fetches = ([next_ids.name, accept.name, last_logits.name]
               + [c.name for pair in ncaches for c in pair])
    return feeds, fetches


def _build_quant():
    """The int8 post-training-quantized serving graph (paddle_tpu/quant/
    + transpiler/passes/quantize.py): an fc stack initialized, run
    through the level-3 quantize pass with a synthetic calibration
    table (unit amax per activation — linting needs ranges to exist,
    not to be accurate), returned as the QUANTIZED program — so
    quantized_matmul stays lint-clean and infer-covered in CI.

    Unlike the other builders this returns the (program, feeds,
    fetches) triple directly: the quantized program is a transformed
    clone, not what program_guard accumulated."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.quant import CalibrationTable, activation_targets
    from paddle_tpu.transpiler.passes import optimize_program

    scope = fluid.Scope()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            img = layers.data(name="pixel", shape=[784], dtype="float32")
            from paddle_tpu.models.mnist import mlp_model

            predict = mlp_model(img)
        exe = fluid.Executor()
        exe.run(startup)
    infer = main.clone(for_test=True)
    calib = CalibrationTable(
        activations={n: 1.0 for n in activation_targets(infer)},
        batches=1)
    quantized, _ctx = optimize_program(
        infer, scope=scope, level=3, feed_names=["pixel"],
        fetch_names=[predict.name], calib=calib)
    assert getattr(quantized, "_quantized", None), \
        "quant example failed to quantize any op"
    return quantized, ["pixel"], [predict.name]


EXAMPLES = {"mlp": _build_mlp, "deepfm": _build_deepfm, "lstm": _build_lstm,
            "decode": _build_decode, "speculative": _build_speculative}
# builders that return the (program, feeds, fetches) triple themselves
# (transformed clones rather than ambient default-program graphs)
PROGRAM_EXAMPLES = {"quant": _build_quant}
ALL_EXAMPLES = sorted(set(EXAMPLES) | set(PROGRAM_EXAMPLES))


def build_example(name: str):
    """Build one bundled example graph in fresh default programs; returns
    (program, feed_names, fetch_names)."""
    import paddle_tpu as fluid

    if name in PROGRAM_EXAMPLES:
        return PROGRAM_EXAMPLES[name]()
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        feeds, fetches = EXAMPLES[name]()
    return prog, feeds, fetches


# -- serialized / script targets ------------------------------------------

def load_target(path: str):
    """(program, feed_names, fetch_names, label) from a path."""
    from paddle_tpu.framework.core import Program

    label = path
    if os.path.isdir(path):
        path = os.path.join(path, "__model__")
    with open(path) as f:
        doc = json.load(f)
    if "program" in doc:  # save_inference_model layout
        return (Program.from_dict(doc["program"]),
                list(doc.get("feed_names", [])),
                list(doc.get("fetch_names", [])), label)
    return Program.from_dict(doc), [], [], label


def run_script(path: str):
    """Execute a graph-building script under fresh default programs and
    lint what it built. The script may set LINT_FEEDS / LINT_FETCHES
    (lists of names); otherwise data vars count as feeds and no fetch
    roots are assumed (persistable writes keep training ops live)."""
    import paddle_tpu as fluid

    prog, startup = fluid.Program(), fluid.Program()
    glb = {"__name__": "__lint__", "__file__": path}
    with fluid.program_guard(prog, startup):
        with open(path) as f:
            code = compile(f.read(), path, "exec")
        exec(code, glb)  # noqa: S102 — explicit, user-invoked
    feeds = list(glb.get("LINT_FEEDS")
                 or [n for b in prog.blocks for n, v in b.vars.items()
                     if v.is_data])
    fetches = list(glb.get("LINT_FETCHES") or [])
    return prog, feeds, fetches, path


# -- driver ---------------------------------------------------------------

def lint_one(program, feeds, fetches, label, min_severity, as_json):
    from paddle_tpu.analysis import analyze_program

    analysis = analyze_program(program, feed_names=feeds,
                               fetch_names=fetches)
    rep = analysis.report
    if as_json:
        doc = rep.to_dict()
        doc["name"] = label
        return doc, rep
    print("== %s: %d ops, infer coverage %d/%d (%.0f%%)"
          % (label, rep.total_ops, rep.covered_ops, rep.total_ops,
             100.0 * rep.coverage))
    print(rep.render(min_severity))
    return None, rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Static lint for paddle_tpu Programs "
                    "(shape/dtype inference + TPU lints)")
    ap.add_argument("paths", nargs="*",
                    help="serialized program JSON / model dir")
    ap.add_argument("--example", action="append", default=[],
                    choices=ALL_EXAMPLES + ["all"],
                    help="lint a bundled example program (repeatable)")
    ap.add_argument("--script", action="append", default=[],
                    help="a graph-building python script to execute+lint")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    ap.add_argument("--optimize", type=int, default=0, metavar="LEVEL",
                    help="additionally lint each target AFTER the "
                         "optimizing transpiler at LEVEL (1|2) — the "
                         "pass manager must keep programs lint-clean "
                         "and fully infer-covered")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero on warnings too")
    ap.add_argument("--min-severity", default="info",
                    choices=["note", "info", "warning", "error"],
                    help="floor for the human-readable listing")
    args = ap.parse_args(argv)

    targets = []
    examples = ALL_EXAMPLES if "all" in args.example else args.example
    for name in examples:
        targets.append(("example:" + name,
                        lambda n=name: build_example(n) + ("example:" + n,)))
    for path in args.paths:
        targets.append((path, lambda p=path: load_target(p)))
    for path in args.script:
        targets.append((path, lambda p=path: run_script(p)))
    if not targets:
        ap.error("nothing to lint: give paths, --example or --script")

    json_docs = []
    failed = False
    for label, thunk in targets:
        try:
            program, feeds, fetches, label = thunk()
        except Exception as e:
            failed = True
            if args.as_json:
                json_docs.append({"name": label, "load_error": str(e)})
            else:
                print("== %s: FAILED to load/build: %s" % (label, e))
            continue
        variants = [(label, program)]
        if args.optimize:
            from paddle_tpu.framework.scope import Scope
            from paddle_tpu.transpiler.passes import optimize_program

            try:
                opt, _ctx = optimize_program(
                    program, scope=Scope(), level=args.optimize,
                    feed_names=feeds, fetch_names=fetches)
                variants.append(
                    ("%s+O%d" % (label, args.optimize), opt))
            except Exception as e:
                failed = True
                if args.as_json:
                    json_docs.append({"name": label + "+opt",
                                      "load_error": str(e)})
                else:
                    print("== %s: FAILED to optimize: %s" % (label, e))
        for vlabel, vprogram in variants:
            doc, rep = lint_one(vprogram, feeds, fetches, vlabel,
                                args.min_severity, args.as_json)
            if doc is not None:
                json_docs.append(doc)
            if rep.errors or (args.strict and rep.warnings):
                failed = True
    if args.as_json:
        print(json.dumps({"programs": json_docs}, indent=2))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
