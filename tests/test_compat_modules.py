"""Tests for the round-2 API-surface modules: average, annotations,
default_scope_funcs, recordio_writer, graphviz/net_drawer, op factory,
concurrency, contrib.memory_usage, and the new datasets."""
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers


def test_weighted_average():
    avg = fluid.average.WeightedAverage()
    avg.add(value=2.0, weight=1)
    avg.add(value=4.0, weight=2)
    assert abs(avg.eval() - 10.0 / 3.0) < 1e-9
    avg.reset()
    with pytest.raises(ValueError):
        avg.eval()
    with pytest.raises(ValueError):
        avg.add("nan", 1)


def test_deprecated_decorator(capsys):
    @fluid.annotations.deprecated(since="0.1", instead="new_thing")
    def old_thing(x):
        return x + 1

    assert old_thing(1) == 2
    assert "deprecated" in (capsys.readouterr().err or "deprecated")
    assert "new_thing" in old_thing.__doc__


def test_default_scope_funcs():
    from paddle_tpu.default_scope_funcs import (
        enter_local_scope, find_var, get_cur_scope, leave_local_scope,
        scoped_function, var)

    base = get_cur_scope()
    base.set_var("outer", 1)
    enter_local_scope()
    assert find_var("outer") == 1  # visible through parent chain
    get_cur_scope().set_var("inner", 2)
    leave_local_scope()
    assert get_cur_scope() is base
    assert find_var("inner") is None  # dropped with the local scope

    seen = {}
    scoped_function(lambda: seen.setdefault("s", get_cur_scope()))
    assert seen["s"] is not base


def test_recordio_writer_roundtrip(tmp_path):
    import pickle

    from paddle_tpu.runtime.recordio import RecordIOReader

    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        img = layers.data(name="img", shape=[4])
        lbl = layers.data(name="lbl", shape=[1], dtype="int64")
    feeder = fluid.DataFeeder(feed_list=[img, lbl], place=fluid.CPUPlace(),
                              program=prog)

    def reader():
        for i in range(3):  # 3 batches of 2 samples
            yield [(np.full(4, i, np.float32), i), (np.zeros(4, np.float32), 0)]

    path = str(tmp_path / "t.recordio")
    n = fluid.recordio_writer.convert_reader_to_recordio_file(
        path, reader, feeder)
    assert n == 3
    recs = [pickle.loads(r) for r in RecordIOReader(path)]
    assert len(recs) == 3
    assert recs[1][0].shape == (2, 4)
    np.testing.assert_allclose(recs[1][0][0], np.full(4, 1.0))
    assert recs[2][1].dtype == np.int64

    n2 = fluid.recordio_writer.convert_reader_to_recordio_files(
        str(tmp_path / "m.recordio"), 2, reader, feeder)
    assert n2 == 3
    files = sorted(p for p in os.listdir(tmp_path) if p.startswith("m-"))
    assert len(files) == 2  # 2 + 1 records


def test_graphviz_and_net_drawer(tmp_path):
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = layers.data(name="x", shape=[4])
        y = layers.fc(input=x, size=3, act="relu")
        layers.mean(y)
    g = fluid.net_drawer.draw_graph(
        startup, prog, filename=str(tmp_path / "net.gv"))
    src = str(g)
    assert "digraph" in src
    assert "fc" in src or "mul" in src
    assert (tmp_path / "net.gv").exists()

    # GraphPreviewGenerator API
    from paddle_tpu.graphviz import GraphPreviewGenerator

    gen = GraphPreviewGenerator("preview")
    p = gen.add_param("w", "float32", highlight=True)
    o = gen.add_op("matmul")
    gen.add_edge(p, o)
    out = gen(str(tmp_path / "prev.dot"))
    assert os.path.exists(out)


def test_operator_factory():
    from paddle_tpu.op import Operator, get_all_op_protos

    assert len(get_all_op_protos()) > 150
    op = Operator("scale", X=np.arange(4, dtype=np.float32), scale=2.0)
    out = op.run()["Out"]
    np.testing.assert_allclose(out, np.arange(4) * 2.0)

    scope = fluid.Scope()
    op2 = Operator("elementwise_add", X=np.ones((2, 2), np.float32),
                   Y=np.full((2, 2), 3.0, np.float32), Out="sum_out")
    op2.run(scope=scope)
    np.testing.assert_allclose(np.asarray(scope.find_var("sum_out")),
                               np.full((2, 2), 4.0))
    # reference-style scope-name inputs: X names a var holding data,
    # Out names a fresh output var
    scope.set_var("xin", np.arange(3, dtype=np.float32))
    op3 = Operator("scale", X="xin", Out="yout", scale=3.0)
    op3.run(scope=scope)
    np.testing.assert_allclose(np.asarray(scope.find_var("yout")),
                               np.arange(3) * 3.0)
    # re-running keeps 'yout' classified as the output (it now holds
    # data, which must not flip it into an input)
    scope.set_var("xin", np.arange(3, dtype=np.float32) + 1)
    op3.run(scope=scope)
    np.testing.assert_allclose(np.asarray(scope.find_var("yout")),
                               (np.arange(3) + 1) * 3.0)
    with pytest.raises(ValueError):
        Operator("not_a_real_op", X=np.ones(1))


def test_concurrency_channels():
    ch = fluid.make_channel(dtype="float32", capacity=4)
    done = fluid.make_channel(capacity=1)

    def producer():
        for i in range(5):
            assert fluid.channel_send(ch, i * 1.5)
        fluid.channel_close(ch)

    def consumer():
        got = []
        while True:
            v, ok = fluid.channel_recv(ch)
            if not ok:
                break
            got.append(v)
        fluid.channel_send(done, got)

    g = fluid.Go(producer)
    g2 = fluid.Go(consumer)
    g.join(timeout=10)
    g2.join(timeout=10)
    got, ok = fluid.channel_recv(done)
    assert ok and got == [0.0, 1.5, 3.0, 4.5, 6.0]


def test_concurrency_go_block_and_select():
    ch = fluid.make_channel(capacity=2)
    with fluid.Go() as g:
        g.run(lambda: fluid.channel_send(ch, 42))
    g.join(timeout=10)
    # run() outside a block launches immediately (never silently queued)
    marker = []
    g.run(lambda: marker.append(1))
    g.join(timeout=10)
    assert marker == [1]

    hits = []
    sel = fluid.Select()
    sel.case_recv(ch, lambda v: hits.append(v) or "recv")
    assert sel.run(timeout=5) == "recv"
    assert hits == [42]

    # default fires when nothing is ready
    sel2 = fluid.Select()
    sel2.case_recv(ch, lambda v: "recv")
    sel2.default(lambda: "idle")
    assert sel2.run() == "idle"

    # send on a closed channel must not fake success
    fluid.channel_close(ch)
    sel3 = fluid.Select()
    sel3.case_send(ch, 1, lambda: "sent")
    with pytest.raises(RuntimeError):
        sel3.run(timeout=5)

    # join() surfaces a timeout instead of returning placeholder results
    import time as _time

    slow = fluid.Go(lambda: _time.sleep(3.0))
    with pytest.raises(TimeoutError):
        slow.join(timeout=0.05)


def test_memory_usage():
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        x = layers.data(name="x", shape=[256])  # (-1, 256) fp32
        layers.fc(input=x, size=128)
    lo, hi, unit = fluid.contrib.memory_usage(prog, batch_size=32)
    assert unit in ("B", "KB", "MB")
    assert 0 < lo < hi
    with pytest.raises(ValueError):
        fluid.contrib.memory_usage(prog, batch_size=0)
    with pytest.raises(TypeError):
        fluid.contrib.memory_usage("not a program", 1)


def test_new_datasets():
    from paddle_tpu.dataset import flowers, mq2007, voc2012

    img, lbl = next(flowers.train()())
    assert img.shape == (3, 224, 224) and img.dtype == np.float32
    assert 0 <= lbl < 102
    assert 0.0 <= img.min() and img.max() <= 1.0

    im, seg = next(voc2012.train()())
    assert im.shape == (224, 224, 3) and im.dtype == np.uint8
    assert seg.shape == (224, 224) and seg.dtype == np.uint8
    classes = set(np.unique(seg)) - {255}
    assert classes <= set(range(21))

    label, left, right = next(mq2007.train(format="pairwise")())
    assert left.shape == (46,) and right.shape == (46,)
    assert label.shape == (1,)
    score, feat = next(mq2007.train(format="pointwise")())
    assert feat.shape == (46,) and score in (0.0, 1.0, 2.0)
    rels, feats = next(mq2007.test(format="listwise")())
    assert feats.shape[0] == rels.shape[0] and feats.shape[1] == 46
    # determinism
    a = next(mq2007.train(format="pointwise")())[1]
    b = next(mq2007.train(format="pointwise")())[1]
    np.testing.assert_array_equal(a, b)


def test_core_shim():
    from paddle_tpu import core

    assert core.VarDesc.VarType.FP32 == "float32"
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        v = layers.data(name="cv", shape=[4])
    assert v.dtype == core.VarDesc.VarType.FP32
    assert isinstance(core.CPUPlace(), fluid.CPUPlace)
    assert core.op_support_gpu("matmul")
    assert len(core.get_all_op_protos()) > 150
    # module aliases mirror the reference layout
    from paddle_tpu.inferencer import Inferencer
    from paddle_tpu.parallel_executor import ParallelExecutor
    assert Inferencer is fluid.Inferencer
    assert ParallelExecutor is fluid.ParallelExecutor


def test_pipe_reader(tmp_path):
    import gzip

    from paddle_tpu.reader import PipeReader

    p = tmp_path / "data.txt"
    p.write_text("a 1\nb 2\nc 3\n")
    pr = PipeReader("cat %s" % p)
    assert [l.split() for l in pr.get_line()] == [
        ["a", "1"], ["b", "2"], ["c", "3"]]

    gz = tmp_path / "data.gz"
    with gzip.open(gz, "wt") as f:
        f.write("x\ny\n")
    pr2 = PipeReader("cat %s" % gz, file_type="gzip")
    assert list(pr2.get_line()) == ["x", "y"]

    with pytest.raises(TypeError):
        PipeReader(["cat"])
    with pytest.raises(TypeError):
        PipeReader("cat x", file_type="bzip2")


def test_pipe_reader_multibyte_boundary(tmp_path):
    from paddle_tpu.reader import PipeReader

    # é is 2 bytes in UTF-8; bufsize=3 forces a split mid-character
    p = tmp_path / "uni.txt"
    p.write_text("ééé\nzz\n", encoding="utf-8")
    pr = PipeReader("cat %s" % p, bufsize=3)
    assert list(pr.get_line()) == ["ééé", "zz"]


def test_operator_factory_named_requires_scope():
    from paddle_tpu.op import Operator

    op = Operator("scale", X="xin", Out="yout", scale=2.0)
    with pytest.raises(ValueError):
        op.run()  # named slots without a scope


def test_operator_factory_numpy_scalar_attr():
    from paddle_tpu.op import Operator

    # numpy scalars are attribute values, never tensor inputs
    out = Operator("scale", X=np.arange(3, dtype=np.float32),
                   scale=np.float32(2.0)).run()["Out"]
    np.testing.assert_allclose(out, [0.0, 2.0, 4.0])


def test_pipe_reader_abandoned_stream_terminates(tmp_path):
    import time

    from paddle_tpu.reader import PipeReader

    t0 = time.monotonic()
    with PipeReader("sleep 300") as pr:
        pass  # abandon without reading: close() must not hang on wait()
    assert time.monotonic() - t0 < 10
    assert pr.process.poll() is not None  # child reaped


def test_operator_factory_inplace_param_out():
    # an UPPERCASE output slot bound to a var that already holds
    # data (in-place update shape) must still be classified as an output.
    import numpy as np

    from paddle_tpu.core import Scope
    from paddle_tpu.op import Operator

    scope = Scope()
    scope.set_var("p", np.array([1.0, 2.0], np.float32))
    scope.set_var("g", np.array([0.5, 0.5], np.float32))
    scope.set_var("lr", np.array([0.1], np.float32))
    op = Operator("sgd", Param="p", Grad="g", LearningRate="lr",
                  ParamOut="p")
    op.run(scope=scope)
    np.testing.assert_allclose(
        np.asarray(scope.find_var("p")), [0.95, 1.95], rtol=1e-6)
    # second run keeps the (now data-holding) output classified as output
    op.run(scope=scope)
    np.testing.assert_allclose(
        np.asarray(scope.find_var("p")), [0.90, 1.90], rtol=1e-6)


def test_go_multiple_failures_aggregate():
    # with >1 concurrent failure, join() raises an aggregate
    # naming every failed task instead of dropping all but the first.
    import pytest

    import paddle_tpu as fluid

    def boom_a():
        raise ValueError("a died")

    def boom_b():
        raise KeyError("b died")

    with fluid.Go() as g:
        g.run(boom_a)
        g.run(boom_b)
        g.run(lambda: 42)
    with pytest.raises(RuntimeError, match="2 Go tasks failed"):
        g.join()
    # per-task results keep the surviving value and record each exception
    assert g.result[2] == 42
    assert isinstance(g.result[0], ValueError)
    assert isinstance(g.result[1], KeyError)

    single = fluid.Go(boom_a)
    with pytest.raises(ValueError, match="a died"):
        single.join()
