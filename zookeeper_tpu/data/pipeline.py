"""Batching + device prefetch.

The JAX-native replacement for the reference example's
``.cache().shuffle().batch().prefetch()`` tf.data chain (SURVEY.md §3.3):

- :func:`batch_iterator` — deterministic per-epoch global shuffle, host
  sharding for multi-host pods, per-example preprocessing (optionally on a
  thread pool), stacking into numpy batches;
- :func:`prefetch_to_device` — a double-buffered background thread that
  moves batches into (possibly sharded) device memory with
  ``jax.device_put``, overlapping host work with TPU steps;
- :class:`HostBatchRing` — recycled host buffers the native batch assembly
  writes into, each taken again only once nothing can read it;
- :class:`DataLoader` — the component tying a ``Dataset`` + ``Preprocessing``
  + batch settings together.

Determinism contract: given (seed, epoch, global example count), every host
computes the same global permutation and reads only its own contiguous slice
of each global batch — exact-resume and multi-host-consistent by
construction (SURVEY.md §7 "input pipeline at pod scale").
"""

from __future__ import annotations

import queue
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterator, Optional

import numpy as np

from zookeeper_tpu.core import ComponentField, Field, component
from zookeeper_tpu.data.dataset import Dataset
from zookeeper_tpu.observability import trace as _trace
from zookeeper_tpu.observability.registry import default_registry
from zookeeper_tpu.data.preprocessing import Preprocessing
from zookeeper_tpu.data.source import DataSource

Batch = Dict[str, np.ndarray]


def _column_arrays(source: DataSource) -> Optional[Dict[str, np.ndarray]]:
    """Whole-column ndarray views of a source's features, when it has
    them: ``.arrays`` (ArraySource) or ``.features`` (MemmapSource's
    read-only memmaps). None disables the native fast path."""
    for attr in ("arrays", "features"):
        cols = getattr(source, attr, None)
        if isinstance(cols, dict) and all(
            isinstance(v, np.ndarray) for v in cols.values()
        ):
            return cols
    return None


#: Alignment of a ring buffer: XLA's for its operands, so that a backend
#: able to read a NumPy operand where it lies (the CPU) always does, and
#: the ring always sees it (:func:`_lies_in`).
_ALIGN = 64


def _aligned_empty(shape, dtype) -> np.ndarray:
    dtype = np.dtype(dtype)
    nbytes = int(np.prod(shape)) * dtype.itemsize
    raw = np.empty(nbytes + _ALIGN, np.uint8)
    start = -raw.ctypes.data % _ALIGN
    return raw[start:start + nbytes].view(dtype).reshape(shape)


def _lies_in(staged, buf: np.ndarray) -> bool:
    """Whether any shard of the staged array is ``buf``'s own memory."""
    lo = buf.ctypes.data
    hi = lo + buf.nbytes
    return any(
        lo <= shard.data.unsafe_buffer_pointer() < hi
        for shard in staged.addressable_shards
    )


class HostBatchRing:
    """Recycled host buffers for the native batch assembly.

    A fresh batch buffer (512 x 224 x 224 x 3 float32 is 308 MB) is a new
    mapping every step: the assembling threads fault in every page and the
    kernel zeroes it. The ring hands the assembly a buffer it has written
    before instead, and never one that anything can still read:

    - the producer hands each buffer back with the array staged from it
      (:meth:`release`); :meth:`take` reuses it only once that array is
      ready, i.e. its host-to-device transfer has read the buffer;
    - where the staged array lies in the buffer itself (a backend that
      reads a NumPy operand where it lies, as the CPU's does), the buffer
      is the consumer's from then on: the ring forgets it and allocates a
      fresh one in its place.

    It holds ``slots`` buffers of one ``(shape, dtype)``, reusing the
    oldest; a batch of another shape (an eval split's short last batch)
    starts it over. Safe to share between the producers of consecutive
    passes.
    """

    def __init__(self, slots: int):
        if slots < 1:
            raise ValueError(f"slots={slots} must be >= 1.")
        self.slots = slots
        self._lock = threading.Lock()
        self._key = None
        #: ``[buffer, staged array or None once it was ready]``, oldest first.
        self._handed_back: deque = deque()
        #: id -> (buffer, recycled) of buffers taken and not yet handed back.
        self._taken: Dict[int, Any] = {}

    def _reap(self) -> None:
        """Drop the references to staged arrays that are ready (so the
        ring keeps no device memory alive) and forget the buffers they
        lie in. Caller holds the lock."""
        kept: deque = deque()
        for entry in self._handed_back:
            buf, staged = entry
            if staged is not None and staged.is_ready():
                if _lies_in(staged, buf):
                    continue
                entry[1] = None
            kept.append(entry)
        self._handed_back = kept

    def settle(self, step: Optional[int] = None) -> None:
        """Wait until the buffer the next :meth:`take` reuses has been
        read by its transfer: outside the assembly, in a
        ``loader_recycle_wait`` span when there is anything to wait for."""
        with self._lock:
            self._reap()
            full = len(self._handed_back) >= self.slots
            staged = self._handed_back[0][1] if full else None
        if staged is not None:
            with _trace.span("loader_recycle_wait", step=step):
                staged.block_until_ready()

    def take(self, shape, dtype) -> np.ndarray:
        """A buffer of ``shape`` and ``dtype`` that nothing else reads:
        the oldest handed back once the ring is full, else a fresh one."""
        key = (tuple(shape), np.dtype(dtype))
        buf = staged = None
        with self._lock:
            if key != self._key:
                self._key, self._handed_back = key, deque()
            self._reap()
            if len(self._handed_back) >= self.slots:
                buf, staged = self._handed_back.popleft()
        if staged is not None:  # settle() did not run first
            staged.block_until_ready()
            if _lies_in(staged, buf):
                buf = None
        recycled = buf is not None
        if buf is None:
            buf = _aligned_empty(*key)
        with self._lock:
            self._taken[id(buf)] = (buf, recycled)
        return buf

    def recycled(self, batch: Any) -> int:
        """1 if ``batch`` was assembled into a buffer the ring reused."""
        import jax

        with self._lock:
            return int(any(
                self._taken.get(id(x), (None, False))[1]
                for x in jax.tree.leaves(batch)
            ))

    def release(self, batch: Any, staged: Any) -> None:
        """Hand back the ring's buffers among ``batch``'s leaves, each with
        the array ``staged`` holds in its place."""
        import jax

        with self._lock:
            for host, dev in zip(
                jax.tree.leaves(batch), jax.tree.leaves(staged)
            ):
                entry = self._taken.pop(id(host), None)
                if entry is None or (host.shape, host.dtype) != self._key:
                    continue
                self._handed_back.append([host, dev])
                if len(self._handed_back) > self.slots:
                    self._handed_back.popleft()


def batch_iterator(
    source: DataSource,
    preprocessing: Optional[Preprocessing],
    batch_size: int,
    *,
    training: bool,
    shuffle: bool = True,
    seed: int = 0,
    epoch: int = 0,
    drop_remainder: bool = True,
    host_index: int = 0,
    host_count: int = 1,
    num_workers: int = 0,
    start_batch: int = 0,
    buffers: Optional[HostBatchRing] = None,
) -> Iterator[Batch]:
    """Yield batches of stacked numpy arrays from ``source``.

    ``batch_size`` is the *per-host* batch size; with ``host_count > 1`` each
    global batch of ``batch_size * host_count`` examples is split
    contiguously and this host materializes slice ``host_index``.

    ``start_batch`` skips the first k global batches WITHOUT fetching
    them — the epoch's permutation is (seed, epoch)-fixed, so batch k
    onward is identical to an uninterrupted epoch's. This is the exact
    mid-epoch-resume hook (a step-granular checkpoint restores at
    ``step % steps_per_epoch == k``).

    ``buffers``: the native assembly writes each batch's input into a
    buffer taken from this ring (the per-example Python path ignores it).
    Only a consumer that hands every batch back to the ring
    (:func:`prefetch_to_device`) may pass one.
    """
    if host_count < 1 or not 0 <= host_index < host_count:
        # A mis-wired host identity (a stale process_id env, a bad
        # test injection) would silently read the WRONG slice — or no
        # slice at all — of every global batch; per-host disjointness
        # is the multi-host determinism contract, so fail loudly.
        raise ValueError(
            f"host_index={host_index} outside [0, host_count="
            f"{host_count}): every host must own exactly one slice of "
            "the global batch."
        )
    n = len(source)
    global_batch = batch_size * host_count
    # Multi-host pods MUST drop the final partial global batch: a batch
    # present on some hosts but not others would desync the lockstep jitted
    # step (one host enters the gradient all-reduce, the rest never join —
    # pod-wide hang), and shape-changing partial batches would recompile.
    if host_count > 1:
        drop_remainder = True

    num_batches = n // global_batch if drop_remainder else -(-n // global_batch)
    if training and n > 0 and num_batches == 0:
        # A train split smaller than one global batch (with remainder
        # dropping) yields ZERO batches: the run would "train" zero
        # steps every epoch forever with no error — same silent
        # pathology as a bad resume point. Eval splits stay permissive:
        # their callers handle produced-no-batches explicitly (e.g.
        # validation metrics simply absent that epoch).
        raise ValueError(
            f"Train split has {n} examples but the global batch is "
            f"{global_batch} (batch_size={batch_size} x "
            f"host_count={host_count}) with drop_remainder: every epoch "
            "would yield zero batches."
        )
    if start_batch < 0 or (start_batch > 0 and start_batch >= num_batches):
        # A miscomputed resume point must fail loudly: a negative value
        # silently shifts range() semantics, and start_batch at/beyond
        # the epoch end silently yields an EMPTY epoch (a run that
        # "trains" zero steps per epoch forever). A legitimate epoch-
        # boundary resume rolls into the NEXT epoch at step 0, so
        # start_batch == num_batches is never correct. Validated BEFORE
        # the empty-source exit so a zero-example source with a stale
        # resume point still fails instead of yielding nothing forever.
        raise ValueError(
            f"start_batch={start_batch} outside [0, {num_batches}) "
            f"(the epoch has {num_batches} batches)"
        )
    if n == 0:
        return
    if shuffle:
        order = np.random.default_rng(
            np.random.SeedSequence([seed, epoch])
        ).permutation(n)
    else:
        order = np.arange(n)

    # Native fast path: when preprocessing reduces to a fused C++ batch
    # assembly over a uint8 feature store — plain gather+affine
    # ("normalize" mode) or the full training augmentation recipe
    # ("augment" mode: RandomResizedCrop/pad+crop, flip, normalize,
    # bit-identical to the Python path via the shared counter RNG) —
    # assemble whole batches in one call (threads, no per-example
    # Python) — the LCE-equivalent host kernel. Duck-typed over any
    # source exposing whole-column ndarray access: ArraySource
    # (``.arrays``, in-RAM) and MemmapSource (``.features``, disk-backed
    # > RAM — the path ImageNet-scale training actually uses; the C++
    # gather reads straight out of the mapping, so page faults ride the
    # kernel's threads, VERDICT round-2 #3).
    native_spec = None
    if preprocessing is not None and hasattr(preprocessing, "native_batch_spec"):
        spec = preprocessing.native_batch_spec(training)
        if spec is not None:
            arrays = _column_arrays(source)
            if arrays is not None:
                img = arrays.get(spec["image_key"])
                lbl = arrays.get(spec["label_key"])
                mode = spec.get("mode", "normalize")
                ok = (
                    img is not None
                    and lbl is not None
                    and img.dtype == np.uint8
                    and img.flags["C_CONTIGUOUS"]
                )
                if ok and mode == "normalize":
                    # gather_normalize has a numpy fallback, so no
                    # availability gate here.
                    ok = tuple(img.shape[1:]) == tuple(
                        spec["expected_shape"]
                    )
                elif ok:  # mode == "augment"
                    # The augmented kernel has NO numpy fallback (the
                    # per-example Python path below IS the bit-identical
                    # reference), so it engages only when the library
                    # loads and the store shape fits the recipe:
                    # RandomResizedCrop accepts any fixed source
                    # resolution (it resizes), pad+crop requires the
                    # source to already be output-shaped.
                    from zookeeper_tpu import native

                    eh, ew, ec = spec["expected_shape"]
                    ok = (
                        native.available()
                        and img.ndim == 4
                        and (
                            img.shape[3] == ec
                            if spec["random_resized_crop"]
                            # pad+crop: source already output-shaped,
                            # and the kernel's reflect indexing is
                            # valid only for pad < side (numpy's
                            # np.pad handles pad >= side by repeated
                            # reflection, which the kernel does not
                            # model — fall back to Python there).
                            else tuple(img.shape[1:]) == (eh, ew, ec)
                            and spec["pad_pixels"] < min(eh, ew)
                        )
                    )
                if ok:
                    native_spec = (spec, img, lbl)

    if native_spec is not None:
        from zookeeper_tpu import native

        spec, img, lbl = native_spec

        def out(shape):
            return None if buffers is None else buffers.take(shape, np.float32)

        if spec.get("mode", "normalize") == "normalize":
            def assemble(idx):
                return native.gather_normalize(
                    img, idx, spec["scale"], spec["shift"],
                    out=out((len(idx), *img.shape[1:])),
                )
        else:
            eh, ew, _ = spec["expected_shape"]

            def assemble(idx):
                return native.gather_augment_normalize(
                    img,
                    idx,
                    out_height=eh,
                    out_width=ew,
                    seed=seed,
                    epoch=epoch,
                    random_resized_crop=spec["random_resized_crop"],
                    crop_scale_range=spec["crop_scale_range"],
                    log_aspect_range=spec["log_aspect_range"],
                    pad_pixels=spec["pad_pixels"],
                    random_flip=spec["random_flip"],
                    post_scale=spec["post_scale"],
                    post_shift=spec["post_shift"],
                    out=out((len(idx), eh, ew, img.shape[3])),
                )

        for b in range(start_batch, num_batches):
            start = b * global_batch + host_index * batch_size
            stop = min(start + batch_size, n, (b + 1) * global_batch)
            if stop <= start:
                continue
            idx = order[start:stop].astype(np.int64)
            yield {
                "input": assemble(idx),
                "target": lbl[idx].astype(np.int32),
            }
        return

    def fetch(global_index: int) -> Dict[str, np.ndarray]:
        idx = int(order[global_index])
        example = dict(source[idx])
        example.setdefault("_index", np.int64(idx))
        example.setdefault("_epoch", np.int64(epoch))
        example.setdefault("_seed", np.int64(seed))
        if preprocessing is not None:
            example = preprocessing(example, training)
        return example

    pool = (
        ThreadPoolExecutor(num_workers, thread_name_prefix="zk-data-worker")
        if num_workers > 0
        else None
    )
    try:
        for b in range(start_batch, num_batches):
            start = b * global_batch + host_index * batch_size
            stop = min(start + batch_size, n, (b + 1) * global_batch)
            indices = range(start, stop)
            if pool is not None:
                examples = list(pool.map(fetch, indices))
            else:
                examples = [fetch(i) for i in indices]
            if not examples:
                continue
            keys = examples[0].keys()
            yield {k: np.stack([e[k] for e in examples]) for k in keys}
    finally:
        if pool is not None:
            pool.shutdown(wait=False)


def slab_iterator(
    iterator: Iterator[Batch],
    unroll: int,
    *,
    max_batches: Optional[int] = None,
) -> Iterator[Batch]:
    """Group ``unroll`` consecutive batches into one ``[unroll, batch,
    ...]`` *slab* (the ``lax.scan`` multi-step's input unit — see
    ``training.step.build_multi_step``).

    Order-preserving by construction: slab ``i`` is exactly batches
    ``[i * unroll, (i + 1) * unroll)`` of the underlying iterator, so
    the determinism contract (seed/epoch-fixed permutation, exact
    ``start_batch`` resume) is untouched — slab boundaries never change
    which example lands in which step. A resume point that is not a
    multiple of ``unroll`` simply starts slabbing from that batch
    ("lands mid-slab" relative to an uninterrupted run's boundaries).

    The FINAL slab may be partial (fewer than ``unroll`` batches) when
    the epoch length is not a multiple of ``unroll``; consumers scan
    over the leading dim, so a partial slab just compiles a second,
    shorter program. Batches within a slab must share shapes (train
    pipelines drop the remainder batch, so this holds by construction;
    a shape-changing partial FINAL BATCH cannot be slabbed and raises).

    ``max_batches`` caps how many batches are consumed in total (the
    ``steps_per_epoch`` cutoff, applied BEFORE stacking so a cap that
    falls mid-slab yields a final partial slab instead of silently
    training past the cap).
    """
    if unroll < 1:
        raise ValueError(f"unroll={unroll} must be >= 1.")

    def stack(buf):
        return {k: np.stack([b[k] for b in buf]) for k in buf[0]}

    if max_batches is not None and max_batches <= 0:
        return
    buf: list = []
    consumed = 0
    first_sig = None
    for batch in iterator:
        # Shape signature checked against the FIRST batch of the whole
        # iteration, not just within one slab: a partial final batch
        # that lands alone in the last slab must still fail loudly
        # (it would otherwise compile a third executable — and under a
        # mesh, fail batch-axis sharding — far from this boundary).
        sig = tuple(sorted((k, v.shape) for k, v in batch.items()))
        if first_sig is None:
            first_sig = sig
        elif sig != first_sig:
            raise ValueError(
                "slab_iterator got batches of differing shapes (a "
                "partial final batch?): slabs require drop_remainder "
                "batching."
            )
        buf.append(batch)
        consumed += 1
        if len(buf) == unroll:
            yield stack(buf)
            buf = []
        if max_batches is not None and consumed >= max_batches:
            break
    if buf:
        yield stack(buf)


_END = object()


def prefetch_to_device(
    iterator: Iterator[Batch],
    *,
    size: int = 2,
    sharding: Optional[Any] = None,
    split: Optional[str] = None,
    ring: Optional[HostBatchRing] = None,
) -> Iterator[Any]:
    """Asynchronously stage host batches into device memory.

    A background thread pulls from ``iterator`` and calls
    ``jax.device_put(batch, sharding)``; the main thread yields device
    buffers while the next transfer is in flight. With a
    ``jax.sharding.NamedSharding`` whose batch axis spans the mesh's data
    axis, this is the host→HBM half of data parallelism — XLA never sees a
    host transfer inside the step.

    ``ring``: the one ``iterator`` takes its buffers from; each staged
    batch is handed back to it (:meth:`HostBatchRing.release`).
    """
    import jax

    q: "queue.Queue[Any]" = queue.Queue(maxsize=max(1, size))
    stop = threading.Event()
    err: list[BaseException] = []

    def put_or_stop(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    # Fixed for the generator's lifetime; computed once, not per batch.
    mesh = getattr(sharding, "mesh", None)
    multi_process = mesh is not None and any(
        d.process_index != jax.process_index() for d in mesh.devices.flat
    )

    def stage(batch):
        if sharding is None:
            return jax.device_put(batch)
        if multi_process:
            # Each host holds only its slice of the global batch
            # (batch_iterator contract); assemble the distributed global
            # array from per-process shards.
            return jax.tree.map(
                lambda x: jax.make_array_from_process_local_data(sharding, x),
                batch,
            )
        return jax.device_put(batch, sharding)

    # Prefetch occupancy (docs/DESIGN.md §13): sampled after every
    # producer put and consumer get. Pinned at the queue's max while
    # the device is the bottleneck; sitting at 0 means the loop is
    # DATA-BOUND and the host pipeline is the thing to fix (the same
    # diagnosis the trace's per-slab data_wait spans give, scrapeable).
    # Labeled by split so a train loop and a validation loop in the
    # same process each get their own series instead of flapping one
    # shared gauge (split cardinality is bounded by the dataset's).
    occupancy = default_registry().gauge(
        "zk_prefetch_occupancy",
        help="device-prefetch queue fill (staged batches ready)",
        labels={"split": split} if split else None,
    )

    def producer():
        # Three leaf spans per batch, sharing ``step`` = the batch's
        # index in this pass: where this thread's time goes is the
        # loader's breakdown (assemble: gather/augment or the worker
        # pool, ``recycled`` 1 when into a reused ring buffer; stage:
        # host-to-device; put_wait: queue full, so the device is the
        # limit), and a fourth, loader_recycle_wait, ahead of assemble
        # when the ring's next buffer is still being read. No-ops while
        # tracing is off.
        try:
            it = iter(iterator)
            index = 0
            while True:
                if ring is not None:
                    ring.settle(step=index)
                attrs = {} if _trace.enabled() else None
                with _trace.span("loader_assemble", step=index, attrs=attrs):
                    try:
                        host = next(it)
                    except StopIteration:
                        break
                    if attrs is not None:
                        attrs["recycled"] = (
                            0 if ring is None else ring.recycled(host)
                        )
                with _trace.span("loader_stage", step=index):
                    batch = stage(host)
                if ring is not None:
                    ring.release(host, batch)
                del host
                with _trace.span("loader_put_wait", step=index):
                    put = put_or_stop(batch)
                if not put:
                    return  # Consumer gone: drop refs, free device buffers.
                occupancy.set(q.qsize())
                index += 1
        except BaseException as e:  # propagate into consumer
            err.append(e)
        finally:
            put_or_stop(_END)

    thread = threading.Thread(
        target=producer, name="zk-prefetch", daemon=True
    )
    thread.start()
    try:
        while True:
            item = q.get()
            occupancy.set(q.qsize())
            if item is _END:
                if err:
                    raise err[0]
                return
            yield item
    finally:
        # Consumer stopped early (e.g. steps_per_epoch cap): unblock and
        # terminate the producer so threads/HBM buffers don't accumulate
        # across epochs. Zero the gauge — a dead loop's last fill must
        # not scrape as a live, healthy queue.
        occupancy.set(0)
        stop.set()


@component
class DataLoader:
    """Component bundling dataset + preprocessing + batching policy.

    ``batch_size`` is the GLOBAL batch size (reference semantics: the
    experiment's ``batch_size`` field, inherited by scope into the loader).
    Per-host slicing happens automatically from ``jax.process_index()``
    unless overridden (tests inject ``host_index``/``host_count``).
    """

    dataset: Dataset = ComponentField()
    preprocessing: Preprocessing = ComponentField()
    #: No default on purpose: inherits the experiment's ``batch_size`` by
    #: scoped field inheritance (a default here would shadow it — child
    #: defaults beat ancestor defaults).
    batch_size: int = Field()
    shuffle: bool = Field(True)
    seed: int = Field(0)
    drop_remainder: bool = Field(True)
    num_workers: int = Field(0)
    prefetch: int = Field(2)
    host_index: int = Field(-1)  # -1 => jax.process_index()
    host_count: int = Field(-1)  # -1 => jax.process_count()

    def _ring(self) -> HostBatchRing:
        """The loader's host batch buffers, kept across epochs: one per
        batch in the prefetch queue, one being staged, one being
        assembled."""
        ring = getattr(self, "_batch_ring", None)
        if ring is None:
            ring = HostBatchRing(self.prefetch + 2)
            object.__setattr__(self, "_batch_ring", ring)
        return ring

    def _source(self, split: str) -> Optional[DataSource]:
        """The split's DataSource, cached for the loader's lifetime: a
        source may be expensive to materialize (synthetic generation, store
        open, TFDS index), and rebuilding it every epoch / every
        steps_per_epoch call is wasted host time at scale."""
        cache = getattr(self, "_source_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_source_cache", cache)
        if split not in cache:
            cache[split] = (
                self.dataset.train() if split == "train" else self.dataset.validation()
            )
        return cache[split]

    def _hosts(self):
        hi, hc = self.host_index, self.host_count
        if hi < 0 or hc < 0:
            import jax

            hi = jax.process_index() if hi < 0 else hi
            hc = jax.process_count() if hc < 0 else hc
        return hi, hc

    @property
    def per_host_batch_size(self) -> int:
        _, hc = self._hosts()
        if self.batch_size % hc != 0:
            raise ValueError(
                f"Global batch size {self.batch_size} not divisible by "
                f"host count {hc}."
            )
        return self.batch_size // hc

    def batches(
        self,
        split: str = "train",
        *,
        epoch: int = 0,
        sharding: Optional[Any] = None,
        training: Optional[bool] = None,
        start_batch: int = 0,
        unroll: int = 1,
        max_batches: Optional[int] = None,
    ) -> Iterator[Any]:
        """``training=None`` infers train-mode behavior (shuffle, augment,
        drop-remainder) from the split name; pass ``training=False`` to
        iterate the train split in eval mode (e.g. scoring a checkpoint
        on training data: deterministic order, no augmentation).
        ``start_batch`` resumes the (deterministic) epoch mid-way — see
        :func:`batch_iterator`.

        ``unroll > 1`` yields device-resident SLABS of ``unroll``
        stacked consecutive batches (``[unroll, batch, ...]``) instead
        of single batches — the input unit of the fused multi-step loop
        (:func:`slab_iterator` documents the order/resume contract;
        ``sharding`` should then be the partitioner's
        ``slab_sharding()``). Slabs are assembled on host and staged by
        the SAME double-buffered background thread as single batches,
        so one ``device_put`` moves ``unroll`` batches. ``max_batches``
        caps total batches consumed (the ``steps_per_epoch`` cutoff —
        with slabs, apply it here so a cap that falls mid-slab
        truncates the final slab instead of over-training)."""
        if training is None:
            training = split == "train"
        source = self._source(split)
        if source is None:
            raise ValueError(f"Dataset has no '{split}' split.")
        hi, hc = self._hosts()
        # Recycled buffers only where each batch goes to the device
        # before the next is assembled: without prefetch the consumer
        # holds the host batch itself, and a slab is a copy.
        ring = self._ring() if self.prefetch > 0 and unroll == 1 else None
        it = batch_iterator(
            source,
            self.preprocessing,
            self.per_host_batch_size,
            training=training,
            shuffle=self.shuffle and training,
            seed=self.seed,
            epoch=epoch,
            drop_remainder=self.drop_remainder or training,
            host_index=hi,
            host_count=hc,
            num_workers=self.num_workers,
            start_batch=start_batch,
            buffers=ring,
        )
        if unroll > 1:
            it = slab_iterator(it, unroll, max_batches=max_batches)
        elif max_batches is not None:
            import itertools

            it = itertools.islice(it, max_batches)
        if self.prefetch > 0:
            return prefetch_to_device(
                it, size=self.prefetch, sharding=sharding, split=split,
                ring=ring,
            )
        return it

    def steps_per_epoch(self, split: str = "train") -> int:
        source = self._source(split)
        if source is None:
            raise ValueError(f"Dataset has no '{split}' split.")
        return len(source) // self.batch_size
