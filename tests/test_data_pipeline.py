"""Data pipeline tests: native C++ recordio + blocking queue, reader
decorators, py_reader decoupled feeding, dataset loaders."""

import os
import threading

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import dataset, reader as reader_mod, recordio
from paddle_tpu.native import BlockingQueue, lib as native_lib


def test_native_lib_builds():
    """The image ships g++; the native path must actually be exercised."""
    assert native_lib() is not None


def test_native_lib_is_trusted_by_source_content(tmp_path, monkeypatch):
    """Staleness is decided from the sources' content, which a copied tree
    keeps, not from mtimes, which it does not: the library's name carries
    the hash, an edit to a source names a new library, and building it
    drops the binaries of other sources."""
    import os
    import shutil

    from paddle_tpu import native

    assert native_lib()._name == native._so_path()
    for src in native._SOURCES:
        shutil.copy(os.path.join(native._DIR, src), tmp_path / src)
    foreign = tmp_path / "libpaddle_tpu_native.so"
    foreign.write_bytes(b"not built from these sources")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    same = native._so_path()
    assert os.path.basename(same) == os.path.basename(native_lib()._name)
    with open(tmp_path / native._SOURCES[0], "a") as f:
        f.write("// edited\n")
    edited = native._so_path()
    assert edited != same and not os.path.exists(edited)
    native._build(edited)
    assert os.path.exists(edited) and not foreign.exists()
    assert [p.name for p in tmp_path.glob("*.tmp")] == []


class TestRecordIO:
    def test_roundtrip(self, tmp_path):
        path = str(tmp_path / "data.recordio")
        records = [b"hello", b"", b"x" * 10000, bytes(range(256))]
        with recordio.Writer(path, max_records=2) as w:
            for r in records:
                w.write(r)
        with recordio.Reader(path) as r:
            got = list(r)
        assert got == records

    def test_corruption_detected(self, tmp_path):
        path = str(tmp_path / "bad.recordio")
        with recordio.Writer(path) as w:
            w.write(b"payload-payload-payload")
        data = bytearray(open(path, "rb").read())
        data[-3] ^= 0xFF  # flip a payload byte -> crc mismatch
        open(path, "wb").write(bytes(data))
        with pytest.raises((IOError, StopIteration)):
            with recordio.Reader(path) as r:
                list(r)

    def test_many_records(self, tmp_path):
        path = str(tmp_path / "many.recordio")
        with recordio.Writer(path, max_records=64) as w:
            for i in range(1000):
                w.write(b"rec%06d" % i)
        with recordio.Reader(path) as r:
            got = list(r)
        assert len(got) == 1000
        assert got[777] == b"rec000777"


class TestBlockingQueue:
    def test_fifo_and_close(self):
        q = BlockingQueue(capacity=4)
        for i in range(4):
            assert q.push(b"%d" % i)
        q.close()
        got = [q.pop() for _ in range(5)]
        assert got == [b"0", b"1", b"2", b"3", None]

    def test_backpressure(self):
        q = BlockingQueue(capacity=2)
        done = []

        def producer():
            for i in range(10):
                q.push(b"%d" % i)
            done.append(True)
            q.close()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        out = []
        while True:
            item = q.pop()
            if item is None:
                break
            out.append(item)
        t.join(timeout=5)
        assert done and len(out) == 10

    def test_reset_reopens(self):
        q = BlockingQueue(capacity=2)
        q.push(b"a")
        q.close()
        q.reset()
        assert q.push(b"b")
        assert q.pop() == b"b"


class TestDecorators:
    def test_batch_shuffle_firstn(self):
        r = lambda: iter(range(100))
        batched = reader_mod.batch(lambda: iter(range(10)), 3)
        assert list(batched()) == [[0, 1, 2], [3, 4, 5], [6, 7, 8], [9]]
        assert list(reader_mod.batch(lambda: iter(range(10)), 3,
                                     drop_last=True)()) == [
            [0, 1, 2], [3, 4, 5], [6, 7, 8]]
        shuffled = list(reader_mod.shuffle(r, 16)())
        assert sorted(shuffled) == list(range(100))
        assert list(reader_mod.firstn(r, 5)()) == [0, 1, 2, 3, 4]

    def test_map_chain_compose(self):
        a = lambda: iter([1, 2])
        b = lambda: iter([3, 4])
        assert list(reader_mod.map_readers(lambda x, y: x + y, a, b)()) == [
            4, 6]
        assert list(reader_mod.chain(a, b)()) == [1, 2, 3, 4]
        assert list(reader_mod.compose(a, b)()) == [(1, 3), (2, 4)]

    def test_buffered_prefetch(self):
        out = list(reader_mod.buffered(lambda: iter(range(50)), 8)())
        assert out == list(range(50))

    def test_xmap(self):
        got = sorted(reader_mod.xmap_readers(
            lambda x: x * 2, lambda: iter(range(20)), 4, 8)())
        assert got == [2 * i for i in range(20)]


class TestPyReader:
    def test_decoupled_feeding_trains(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            rdr = fluid.layers.py_reader(
                capacity=8, shapes=[(-1, 784), (-1, 1)],
                dtypes=["float32", "int64"])
            img, label = rdr.vars
            img.stop_gradient = True
            pred = fluid.layers.fc(input=img, size=10)
            loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
                logits=pred, label=label))
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)

        rng = np.random.RandomState(0)
        W = rng.randn(784, 10).astype(np.float32)

        def batches():
            for _ in range(12):
                x = rng.randn(32, 784).astype(np.float32)
                y = np.argmax(x @ W, 1).astype(np.int64).reshape(-1, 1)
                yield (x, y)

        rdr.decorate_paddle_reader(batches)
        exe = fluid.Executor()
        scope = fluid.Scope()
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for epoch in range(2):
                rdr.start()
                while True:
                    try:
                        (l,) = exe.run(main, fetch_list=[loss])
                    except fluid.EOFException:
                        break
                    losses.append(float(l))
        assert len(losses) == 24
        assert losses[-1] < losses[0]


class TestDatasets:
    def test_mnist_shapes(self):
        img, lbl = next(dataset.mnist.train()())
        assert img.shape == (784,) and 0 <= lbl < 10
        assert img.min() >= -1.0 and img.max() <= 1.0

    def test_cifar_shapes(self):
        img, lbl = next(dataset.cifar.train10()())
        assert img.shape == (3072,) and 0 <= lbl < 10

    def test_imdb(self):
        ids, lbl = next(dataset.imdb.train()())
        assert isinstance(ids, list) and lbl in (0, 1)

    def test_uci_housing(self):
        x, y = next(dataset.uci_housing.train()())
        assert x.shape == (13,) and y.shape == (1,)

    def test_mnist_pipeline_end_to_end(self):
        """dataset → shuffle → batch → train an MLP one epoch."""
        train_reader = reader_mod.batch(
            reader_mod.shuffle(dataset.mnist.train(), 256), 64,
            drop_last=True)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[784],
                                    dtype="float32")
            label = fluid.layers.data(name="label", shape=[1],
                                      dtype="int64")
            pred = fluid.layers.fc(input=img, size=10)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits=pred,
                                                        label=label))
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        feeder = fluid.DataFeeder(feed_list=[img, label],
                                  place=fluid.CPUPlace())
        exe = fluid.Executor()
        scope = fluid.Scope()
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for batch in train_reader():
                (l,) = exe.run(main, feed=feeder.feed(batch),
                               fetch_list=[loss])
                losses.append(float(l))
        assert losses[-1] < losses[0]
