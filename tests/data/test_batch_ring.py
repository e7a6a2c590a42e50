"""The loader's recycled host batch buffers (``HostBatchRing``): a buffer
is written again only once nothing can read it, the batches stay what
fresh buffers give, and the ring engages only behind the prefetch
thread."""

import gc
import sys
import threading
import time
import weakref
from types import SimpleNamespace

import numpy as np
import pytest

from zookeeper_tpu import native
from zookeeper_tpu.core import configure
from zookeeper_tpu.data import DataLoader, batch_iterator
from zookeeper_tpu.data import pipeline
from zookeeper_tpu.data.pipeline import HostBatchRing
from zookeeper_tpu.observability import trace

needs_native = pytest.mark.skipif(
    not native.available(), reason="no toolchain (numpy-fallback CI leg)"
)

SHAPE = (4, 3)


class FakeStaged:
    """A staged array whose readiness the test sets, lying at
    ``pointer``."""

    def __init__(self, pointer=1):
        self.ready = threading.Event()
        self.pointer = pointer
        self.addressable_shards = [SimpleNamespace(data=self)]

    def is_ready(self):
        return self.ready.is_set()

    def block_until_ready(self):
        assert self.ready.wait(10)
        return self

    def unsafe_buffer_pointer(self):
        return self.pointer


def filled_ring(slots, staged_of=lambda buf: FakeStaged()):
    """A ring with ``slots`` buffers handed back, and those buffers and
    their staged fakes, oldest first."""
    ring = HostBatchRing(slots)
    bufs, fakes = [], []
    for _ in range(slots):
        buf = ring.take(SHAPE, np.float32)
        assert ring.recycled({"input": buf}) == 0
        fake = staged_of(buf)
        ring.release({"input": buf}, {"input": fake})
        bufs.append(buf)
        fakes.append(fake)
    return ring, bufs, fakes


def test_a_buffer_is_not_taken_again_while_its_staged_array_is_not_ready():
    ring, bufs, fakes = filled_ring(2)
    got = []
    taker = threading.Thread(
        target=lambda: got.append(ring.take(SHAPE, np.float32))
    )
    taker.start()
    taker.join(0.3)
    assert taker.is_alive() and not got  # the oldest is still being read
    fakes[1].ready.set()  # the newer one landing frees nothing
    taker.join(0.2)
    assert taker.is_alive() and not got
    fakes[0].ready.set()
    taker.join(10)
    assert got[0] is bufs[0]
    assert ring.recycled({"input": got[0], "target": np.zeros(4)}) == 1


def test_settle_waits_outside_the_assembly_in_its_own_span():
    prior = trace.get_tracer()
    trace.install(trace.Tracer(64))
    try:
        ring, bufs, fakes = filled_ring(2)
        threading.Timer(0.1, fakes[0].ready.set).start()
        ring.settle(step=7)
        (wait,) = [
            r for r in trace.get_tracer().snapshot()
            if r["name"] == "loader_recycle_wait"
        ]
        assert wait["step"] == 7 and wait["dur_ns"] > 50_000_000
        assert ring.take(SHAPE, np.float32) is bufs[0]
        # nothing left to wait for: no span
        fakes[1].ready.set()
        ring.settle(step=8)
        assert [
            r["step"] for r in trace.get_tracer().snapshot()
            if r["name"] == "loader_recycle_wait"
        ] == [7]
    finally:
        trace.install(prior)


def test_the_ring_keeps_no_staged_array_once_it_is_ready():
    ring, _, fakes = filled_ring(2)
    gone = weakref.ref(fakes[0])
    fakes[0].ready.set()
    del fakes[0]
    ring.settle()
    gc.collect()
    assert gone() is None


def test_a_buffer_its_staged_array_lies_in_is_handed_over():
    """A backend that reads the buffer where it lies (a pointer inside
    it, at any shard's offset): the ring forgets the buffer and takes a
    fresh one in its place."""
    def aliased(buf):
        fake = FakeStaged(pointer=buf.ctypes.data + 12)
        fake.ready.set()
        return fake

    ring, bufs, _ = filled_ring(2, aliased)
    again = [ring.take(SHAPE, np.float32) for _ in range(2)]
    assert all(a is not b for a in again for b in bufs)
    assert [ring.recycled({"input": a}) for a in again] == [0, 0]


def test_a_batch_of_another_shape_starts_the_ring_over():
    ring, bufs, fakes = filled_ring(2)
    for fake in fakes:
        fake.ready.set()
    short = ring.take((2, 3), np.float32)
    assert short.shape == (2, 3) and ring.recycled({"input": short}) == 0
    assert ring.take(SHAPE, np.float32) is not bufs[0]


def test_producers_sharing_a_ring_never_hold_one_buffer_at_once():
    """Sixteen threads (more than the cores) take, write, check and hand
    back buffers of one ring, with the interpreter switching threads
    often: no buffer is out to two of them at a time."""
    ring = HostBatchRing(3)
    out, guard, clashes = set(), threading.Lock(), []

    def producer(me):
        for _ in range(200):
            buf = ring.take(SHAPE, np.float32)
            with guard:
                if id(buf) in out:
                    clashes.append(me)
                out.add(id(buf))
            buf[...] = me
            time.sleep(0)
            if not (buf == me).all():
                clashes.append(me)
            with guard:
                out.discard(id(buf))
            staged = FakeStaged()
            staged.ready.set()
            ring.release({"input": buf}, {"input": staged})

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [
            threading.Thread(target=producer, args=(i,)) for i in range(16)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert clashes == []


def loader(name, **conf):
    """A loader on the native augmenting path (CIFAR's pad-and-crop
    recipe over a uint8 store): 40 images, 10 batches of 4 an epoch."""
    dl = DataLoader()
    configure(
        dl,
        {
            "dataset": "SyntheticCifar10",
            "dataset.num_train_examples": 40,
            "dataset.num_validation_examples": 0,
            "preprocessing": "ImageClassificationPreprocessing",
            "preprocessing.height": 32,
            "preprocessing.width": 32,
            "preprocessing.channels": 3,
            "preprocessing.augment": True,
            "preprocessing.pad_pixels": 4,
            "batch_size": 4,
            "seed": 3,
            "host_index": 0,
            "host_count": 1,
            **conf,
        },
        name=name,
    )
    return dl


def fresh_epoch(dl, epoch):
    """The epoch's batches as fresh buffers give them (no ring)."""
    return list(
        batch_iterator(
            dl._source("train"), dl.preprocessing, 4, training=True,
            shuffle=True, seed=3, epoch=epoch,
        )
    )


def count_allocations(monkeypatch, allocate=pipeline._aligned_empty):
    made = []

    def counted(shape, dtype):
        made.append(shape)
        return allocate(shape, dtype)

    monkeypatch.setattr(pipeline, "_aligned_empty", counted)
    return made


@needs_native
def test_held_batches_stay_whole_where_the_cpu_reads_the_buffer_in_place(
    monkeypatch,
):
    """On the CPU ``device_put`` of an aligned buffer lies in it. The
    consumer holds every batch of the epoch while the producer runs
    ahead: each stays byte-equal to a fresh-buffer run, because every
    buffer was handed over and none was written again."""
    made = count_allocations(monkeypatch)
    dl = loader("alias")
    held = list(dl.batches("train", epoch=0))
    assert len(held) == 10
    assert len(made) == 10  # one fresh buffer a batch: none reused
    for got, want in zip(held, fresh_epoch(dl, 0)):
        np.testing.assert_array_equal(np.asarray(got["input"]), want["input"])
        np.testing.assert_array_equal(np.asarray(got["target"]), want["target"])


def misaligned(shape, dtype):
    """A buffer 16 bytes past XLA's alignment: the CPU backend copies it
    (after ``device_put`` returns), as a TPU transfer reads it."""
    dtype = np.dtype(dtype)
    nbytes = int(np.prod(shape)) * dtype.itemsize
    raw = np.empty(nbytes + 128, np.uint8)
    start = (16 - raw.ctypes.data) % 64
    return raw[start:start + nbytes].view(dtype).reshape(shape)


@needs_native
def test_buffers_are_recycled_across_epochs_where_the_transfer_copies(
    monkeypatch,
):
    """Where the staged array is a copy, the ring's four buffers (two
    queued, one staged, one assembled) serve every batch after the
    first four, across epochs; the ``loader_assemble`` span says which
    batch went into a recycled buffer, and every batch the consumer
    holds is what fresh buffers give."""
    made = count_allocations(monkeypatch, misaligned)
    dl = loader("recycle")
    prior = trace.get_tracer()
    trace.install(trace.Tracer(4096))
    try:
        epochs = [list(dl.batches("train", epoch=e)) for e in (0, 1)]
        deadline = time.time() + 5
        while time.time() < deadline and sum(
            r["name"] == "loader_assemble" for r in trace.get_tracer().snapshot()
        ) < 22:
            time.sleep(0.01)  # each producer's last pull, after its pass
        records = trace.get_tracer().snapshot()
    finally:
        trace.install(prior)
    assert len(made) == 4
    flags = [
        r["attrs"]["recycled"] for r in records
        if r["name"] == "loader_assemble" and "recycled" in r["attrs"]
    ]
    assert flags == [0] * 4 + [1] * 16
    for e, held in enumerate(epochs):
        for got, want in zip(held, fresh_epoch(dl, e)):
            np.testing.assert_array_equal(
                np.asarray(got["input"]), want["input"]
            )


@needs_native
@pytest.mark.parametrize(
    "prefetch,unroll", [(0, 1), (2, 2)], ids=["no_prefetch", "slabs"]
)
def test_the_ring_engages_only_behind_the_prefetch_thread(
    prefetch, unroll, monkeypatch
):
    """Without prefetch the consumer holds the host batch itself, and a
    slab is a copy: the assembly gets no buffer, and no ring is made."""
    outs = []
    real = native.gather_augment_normalize
    monkeypatch.setattr(
        native,
        "gather_augment_normalize",
        lambda *a, **k: (outs.append(k.get("out")), real(*a, **k))[1],
    )
    dl = loader(f"off{prefetch}{unroll}", prefetch=prefetch)
    batches = list(dl.batches("train", epoch=0, unroll=unroll))
    assert len(batches) == 10 // unroll
    assert len(outs) == 10 and all(o is None for o in outs)
    assert getattr(dl, "_batch_ring", None) is None
