import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.data import (
    ArraySource,
    DataLoader,
    SyntheticMnist,
    ImageClassificationPreprocessing,
    PassThroughPreprocessing,
    TokenPreprocessing,
    batch_iterator,
    prefetch_to_device,
    slab_iterator,
)


def make_source(n=32):
    return ArraySource(
        {
            "image": np.arange(n, dtype=np.float32)[:, None, None, None]
            * np.ones((1, 4, 4, 1), np.float32),
            "label": np.arange(n, dtype=np.int32) % 10,
        }
    )


def collect_inputs(batches):
    return np.concatenate([b["input"][:, 0, 0, 0] for b in batches])


def test_batch_shapes_and_drop_remainder():
    pre = PassThroughPreprocessing()
    configure(pre, {"input_key": "image", "target_key": "label"}, name="pre")
    batches = list(
        batch_iterator(make_source(30), pre, 8, training=False, shuffle=False)
    )
    assert len(batches) == 3  # 30 // 8, remainder dropped
    assert batches[0]["input"].shape == (8, 4, 4, 1)
    assert batches[0]["target"].shape == (8,)
    batches = list(
        batch_iterator(
            make_source(30), pre, 8, training=False, shuffle=False,
            drop_remainder=False,
        )
    )
    assert len(batches) == 4
    assert batches[-1]["input"].shape[0] == 6


def test_shuffle_deterministic_per_epoch():
    pre = PassThroughPreprocessing()
    configure(pre, {}, name="pre")
    kw = dict(training=True, shuffle=True, seed=7)
    a = collect_inputs(batch_iterator(make_source(), pre, 8, epoch=0, **kw))
    b = collect_inputs(batch_iterator(make_source(), pre, 8, epoch=0, **kw))
    c = collect_inputs(batch_iterator(make_source(), pre, 8, epoch=1, **kw))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert sorted(a) == sorted(c)  # same examples, different order


def test_host_sharding_partitions_global_batch():
    pre = PassThroughPreprocessing()
    configure(pre, {}, name="pre")
    kw = dict(training=True, shuffle=True, seed=3, epoch=0)
    # 2 hosts, per-host batch 4 => global batch 8 over 32 examples.
    h0 = list(batch_iterator(make_source(), pre, 4, host_index=0, host_count=2, **kw))
    h1 = list(batch_iterator(make_source(), pre, 4, host_index=1, host_count=2, **kw))
    assert len(h0) == len(h1) == 4
    merged = np.concatenate(
        [np.concatenate([a["input"], b["input"]]) for a, b in zip(h0, h1)]
    )[:, 0, 0, 0]
    single = collect_inputs(batch_iterator(make_source(), pre, 8, **kw))
    np.testing.assert_array_equal(np.sort(merged), np.sort(single))
    # Same global order: each global batch has the same example set.
    for a, b, idx in zip(h0, h1, range(4)):
        got = set(np.concatenate([a["input"], b["input"]])[:, 0, 0, 0])
        want = set(single[idx * 8 : (idx + 1) * 8])
        assert got == want


def test_num_workers_matches_serial():
    pre = PassThroughPreprocessing()
    configure(pre, {}, name="pre")
    kw = dict(training=True, shuffle=True, seed=5)
    serial = collect_inputs(batch_iterator(make_source(), pre, 8, **kw))
    threaded = collect_inputs(
        batch_iterator(make_source(), pre, 8, num_workers=4, **kw)
    )
    np.testing.assert_array_equal(serial, threaded)


def test_preprocessing_scaling_and_augment_determinism():
    pre = ImageClassificationPreprocessing()
    configure(
        pre,
        {"height": 4, "width": 4, "channels": 1, "augment": True, "pad_pixels": 1},
        name="pre",
    )
    src = ArraySource(
        {
            "image": (np.arange(16, dtype=np.uint8).reshape(1, 4, 4, 1))
            * np.ones((8, 1, 1, 1), np.uint8),
            "label": np.zeros(8, np.int64),
        }
    )
    out1 = list(batch_iterator(src, pre, 4, training=True, shuffle=False))
    out2 = list(batch_iterator(src, pre, 4, training=True, shuffle=False))
    np.testing.assert_array_equal(out1[0]["input"], out2[0]["input"])
    assert out1[0]["input"].min() >= -1.0 and out1[0]["input"].max() <= 1.0
    assert out1[0]["target"].dtype == np.int32


def test_augmentation_varies_per_epoch():
    """Same example must get a DIFFERENT (but deterministic) augmentation
    each epoch — seeding from index alone would repeat the identical crop
    every epoch and silently shrink augmentation diversity."""
    pre = ImageClassificationPreprocessing()
    configure(
        pre,
        {"height": 6, "width": 6, "channels": 1, "augment": True, "pad_pixels": 2},
        name="pre",
    )
    rng = np.random.default_rng(3)
    src = ArraySource(
        {
            "image": rng.integers(0, 255, (8, 6, 6, 1), dtype=np.uint8),
            "label": np.zeros(8, np.int64),
        }
    )

    def epoch_inputs(epoch):
        return np.concatenate(
            [
                b["input"]
                for b in batch_iterator(
                    src, pre, 4, training=True, shuffle=False, epoch=epoch
                )
            ]
        )

    e0, e0_again, e1 = epoch_inputs(0), epoch_inputs(0), epoch_inputs(1)
    np.testing.assert_array_equal(e0, e0_again)  # deterministic per epoch
    assert not np.array_equal(e0, e1)  # varies across epochs


def test_prefetch_to_device_yields_device_arrays():
    import jax

    pre = PassThroughPreprocessing()
    configure(pre, {}, name="pre")
    it = batch_iterator(make_source(16), pre, 4, training=False, shuffle=False)
    out = list(prefetch_to_device(it, size=2))
    assert len(out) == 4
    assert isinstance(out[0]["input"], jax.Array)
    np.testing.assert_allclose(
        np.asarray(out[0]["input"])[:, 0, 0, 0], [0, 1, 2, 3]
    )


def test_prefetch_propagates_errors():
    def bad_iter():
        yield {"x": np.zeros(1)}
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="boom"):
        list(prefetch_to_device(bad_iter(), size=1))


def test_dataloader_end_to_end():
    loader = DataLoader()
    configure(
        loader,
        {
            "dataset": "SyntheticMnist",
            "dataset.num_train_examples": 64,
            "preprocessing": "ImageClassificationPreprocessing",
            "preprocessing.height": 28,
            "preprocessing.width": 28,
            "preprocessing.channels": 1,
            "batch_size": 16,
            "host_index": 0,
            "host_count": 1,
            "prefetch": 0,
        },
        name="loader",
    )
    assert isinstance(loader.dataset, SyntheticMnist)
    assert loader.steps_per_epoch("train") == 4
    batches = list(loader.batches("train", epoch=0))
    assert len(batches) == 4
    assert batches[0]["input"].shape == (16, 28, 28, 1)
    assert batches[0]["target"].shape == (16,)


def test_dataloader_batch_size_divisibility():
    loader = DataLoader()
    configure(
        loader,
        {
            "dataset": "SyntheticMnist",
            "preprocessing": "PassThroughPreprocessing",
            "batch_size": 5,
            "host_index": 0,
            "host_count": 2,
        },
        name="loader",
    )
    with pytest.raises(ValueError, match="not divisible"):
        loader.per_host_batch_size


def test_prefetch_early_stop_terminates_producer():
    import threading
    import time

    pre = PassThroughPreprocessing()
    configure(pre, {}, name="pre")

    def run_once():
        it = batch_iterator(
            make_source(32), pre, 4, training=False, shuffle=False
        )
        gen = prefetch_to_device(it, size=1)
        next(gen)
        gen.close()  # Early stop: consumer abandons mid-iteration.

    before = threading.active_count()
    for _ in range(5):
        run_once()
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    # Producer threads must terminate, not accumulate.
    assert threading.active_count() <= before + 1


def test_multihost_forces_drop_remainder():
    pre = PassThroughPreprocessing()
    configure(pre, {}, name="pre")
    # 10 examples, global batch 8, drop_remainder=False requested: both
    # hosts must still agree on the batch count (partial batch dropped).
    kw = dict(
        training=False, shuffle=False, drop_remainder=False, host_count=2
    )
    src = make_source(10)
    h0 = list(batch_iterator(src, pre, 4, host_index=0, **kw))
    h1 = list(batch_iterator(src, pre, 4, host_index=1, **kw))
    assert len(h0) == len(h1) == 1
    assert h0[0]["input"].shape[0] == h1[0]["input"].shape[0] == 4


def test_native_fast_path_matches_per_example_path():
    rng = np.random.default_rng(9)
    src = ArraySource(
        {
            "image": rng.integers(0, 256, size=(32, 8, 8, 3), dtype=np.uint8),
            "label": rng.integers(0, 10, size=(32,)).astype(np.int64),
        }
    )
    pre = ImageClassificationPreprocessing()
    configure(pre, {"height": 8, "width": 8, "channels": 3}, name="pre")
    assert pre.native_batch_spec(training=False) is not None
    kw = dict(training=False, shuffle=True, seed=11)
    fast = list(batch_iterator(src, pre, 8, **kw))
    # Force the per-example path by hiding the spec.
    slow_pre = ImageClassificationPreprocessing()
    configure(slow_pre, {"height": 8, "width": 8, "channels": 3}, name="p2")
    object.__setattr__(slow_pre, "native_batch_spec", lambda training: None)
    slow = list(batch_iterator(src, slow_pre, 8, **kw))
    assert len(fast) == len(slow) == 4
    for a, b in zip(fast, slow):
        # Affine order differs ((x/255)*2-1 vs x*(2/255)-1): fp32 rounding.
        np.testing.assert_allclose(a["input"], b["input"], atol=1e-4)
        np.testing.assert_array_equal(a["target"], b["target"])
        assert a["input"].dtype == np.float32
        assert a["target"].dtype == np.int32


def test_native_batch_spec_modes():
    """Training-with-augmentation now has its OWN fused-kernel mode (the
    path every real ImageNet-recipe run takes — previously a silent
    fallback to per-example Python); eval stays on the plain
    gather+normalize spec."""
    pre = ImageClassificationPreprocessing()
    configure(pre, {"augment": True, "pad_pixels": 4}, name="pre")
    train_spec = pre.native_batch_spec(training=True)
    assert train_spec["mode"] == "augment"
    assert train_spec["pad_pixels"] == 4
    assert not train_spec["random_resized_crop"]
    eval_spec = pre.native_batch_spec(training=False)
    assert eval_spec["mode"] == "normalize"
    # RRC recipe carries its (validated) ranges, log-space aspect.
    import math

    pre2 = ImageClassificationPreprocessing()
    configure(
        pre2,
        {"augment": True, "random_resized_crop": True,
         "crop_aspect_range": (0.5, 2.0)},
        name="pre2",
    )
    spec2 = pre2.native_batch_spec(training=True)
    assert spec2["random_resized_crop"]
    assert spec2["log_aspect_range"] == (math.log(0.5), math.log(2.0))
    # Invalid ranges fail fast at spec time (the native path never runs
    # the per-example Python validation).
    pre3 = ImageClassificationPreprocessing()
    configure(
        pre3,
        {"augment": True, "random_resized_crop": True,
         "crop_scale_range": (0.0, 1.0)},
        name="pre3",
    )
    with pytest.raises(ValueError, match="RandomResizedCrop ranges"):
        pre3.native_batch_spec(training=True)


def test_preprocessing_resize_nearest():
    import numpy as np

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.data import ImageClassificationPreprocessing

    p = ImageClassificationPreprocessing()
    configure(
        p,
        {"height": 16, "width": 16, "channels": 1, "resize": True,
         "zero_center": False},
        name="p",
    )
    src = np.arange(64, dtype=np.uint8).reshape(8, 8)
    out = p.input({"image": src}, training=False)
    assert out.shape == (16, 16, 1)
    # Exact 2x upsample: each source pixel appears as a 2x2 block.
    expected = np.repeat(np.repeat(src, 2, axis=0), 2, axis=1) / 255.0
    np.testing.assert_allclose(out[..., 0], expected, rtol=1e-6)

    # Downsample path too (16 -> 8 picks every other pixel).
    p2 = ImageClassificationPreprocessing()
    configure(
        p2,
        {"height": 4, "width": 4, "channels": 1, "resize": True,
         "zero_center": False},
        name="p2",
    )
    out2 = p2.input({"image": src}, training=False)
    np.testing.assert_allclose(out2[..., 0], src[::2, ::2] / 255.0, rtol=1e-6)


def test_random_resized_crop_shape_determinism_and_epoch_variation():
    """Inception-style RandomResizedCrop: output is always (height, width),
    the same (index, epoch) seed reproduces the same crop (resumability),
    and different epochs produce different crops (augmentation variety)."""
    from zookeeper_tpu.core import configure
    from zookeeper_tpu.data import ImageClassificationPreprocessing

    pp = ImageClassificationPreprocessing()
    configure(
        pp,
        {
            "height": 16,
            "width": 16,
            "channels": 3,
            "augment": True,
            "random_resized_crop": True,
            "random_flip": False,
        },
        name="pp",
    )
    rng = np.random.default_rng(0)
    image = rng.integers(0, 255, (48, 64, 3)).astype(np.uint8)

    def run(index, epoch):
        ex = {
            "image": image,
            "label": np.int32(0),
            "_index": np.int64(index),
            "_epoch": np.int64(epoch),
        }
        return pp(ex, training=True)["input"]

    a = run(3, 0)
    assert a.shape == (16, 16, 3)
    np.testing.assert_array_equal(a, run(3, 0))  # deterministic
    assert not np.array_equal(a, run(3, 1))  # varies per epoch
    assert not np.array_equal(a, run(4, 0))  # varies per example
    # Bilinear taps are convex combinations of source pixels: output
    # stays inside the source's value range after the affine rescale.
    src = (image.astype(np.float32) / 255.0) * 2 - 1
    assert a.min() >= src.min() - 1e-6 and a.max() <= src.max() + 1e-6


def test_random_resized_crop_eval_path_unaffected():
    from zookeeper_tpu.core import configure
    from zookeeper_tpu.data import ImageClassificationPreprocessing

    pp = ImageClassificationPreprocessing()
    configure(
        pp,
        {
            "height": 8,
            "width": 8,
            "channels": 1,
            "augment": True,
            "random_resized_crop": True,
        },
        name="pp",
    )
    img = np.zeros((12, 12, 1), np.uint8)
    out = pp({"image": img, "label": np.int32(1)}, training=False)
    # Eval ignores augmentation entirely: center crop to (8, 8).
    assert out["input"].shape == (8, 8, 1)


def test_random_resized_crop_invalid_ranges_fail_fast():
    from zookeeper_tpu.core import configure
    from zookeeper_tpu.data import ImageClassificationPreprocessing

    pp = ImageClassificationPreprocessing()
    configure(
        pp,
        {
            "height": 8, "width": 8, "augment": True,
            "random_resized_crop": True,
            "crop_aspect_range": (0.0, 1.33),
        },
        name="pp",
    )
    ex = {"image": np.zeros((16, 16, 3), np.uint8), "label": np.int32(0)}
    with pytest.raises(ValueError, match="RandomResizedCrop ranges"):
        pp(ex, training=True)


def test_random_resized_crop_skips_pre_resize():
    """resize=True + RRC must crop from the FULL-res source, not a
    pre-shrunk one: a crop from a 64x64 source with scale pinned to a
    quarter of the area can only contain pixels from a 32x32 region —
    impossible if the source had first been resized to 16x16."""
    from zookeeper_tpu.core import configure
    from zookeeper_tpu.data import ImageClassificationPreprocessing

    pp = ImageClassificationPreprocessing()
    configure(
        pp,
        {
            "height": 16, "width": 16, "channels": 1, "resize": True,
            "augment": True, "random_resized_crop": True,
            "random_flip": False, "zero_center": False,
            "crop_scale_range": (0.25, 0.25),
            "crop_aspect_range": (1.0, 1.0),
        },
        name="pp",
    )
    # Source: a 64x64 gradient with 64 distinct row values. A 32x32 crop
    # resized to 16 rows keeps ADJACENT-ROW spacing of 2 (nearest,
    # stride 2); a pre-resize to 16 rows first would sample rows 4 apart.
    img = np.tile(np.arange(64, dtype=np.uint8)[:, None, None], (1, 64, 1))
    ex = {
        "image": img, "label": np.int32(0),
        "_index": np.int64(0), "_epoch": np.int64(0),
    }
    out = pp(ex, training=True)["input"]
    rows = np.unique((out * 255.0).round().astype(np.int64)[..., 0], axis=1)
    row_vals = rows[:, 0]
    steps = np.diff(row_vals)
    assert out.shape == (16, 16, 1)
    # Full-res 32-row crop -> stride-2 row sampling.
    assert set(np.unique(steps)) == {2}


def test_native_fast_path_hits_memmap_store(tmp_path, monkeypatch):
    """The disk-backed (>= RAM) store rides the SAME fused C++ batch
    assembly as the in-RAM source (VERDICT round-2 #3: the native path
    used to be gated on ArraySource, leaving MemmapSource — the path
    ImageNet-scale training actually uses — on per-example Python)."""
    from zookeeper_tpu import native
    from zookeeper_tpu.data.store import MemmapSource, MemmapWriter

    rng = np.random.default_rng(21)
    images = rng.integers(0, 256, size=(48, 8, 8, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, size=(48,)).astype(np.int64)
    with MemmapWriter(str(tmp_path / "store")) as w:
        w.append({"image": images[:30], "label": labels[:30]})
        w.append({"image": images[30:], "label": labels[30:]})
    src = MemmapSource(str(tmp_path / "store"))

    pre = ImageClassificationPreprocessing()
    configure(pre, {"height": 8, "width": 8, "channels": 3}, name="pre")

    calls = []
    real = native.gather_normalize
    monkeypatch.setattr(
        native, "gather_normalize",
        lambda *a, **k: (calls.append(1), real(*a, **k))[1],
    )
    kw = dict(training=False, shuffle=True, seed=5)
    fast = list(batch_iterator(src, pre, 16, **kw))
    assert len(calls) == 3, "native fused assembly was not hit for Memmap"

    # Bit-identical to the in-RAM ArraySource native path (same kernel,
    # same order): the store IS the arrays, just memory-mapped.
    ram = list(
        batch_iterator(
            ArraySource({"image": images, "label": labels}), pre, 16, **kw
        )
    )
    assert len(fast) == len(ram) == 3
    for a, b in zip(fast, ram):
        np.testing.assert_array_equal(a["input"], b["input"])
        np.testing.assert_array_equal(a["target"], b["target"])


def test_slab_iterator_preserves_order_partial_and_cap():
    """Slabs are consecutive batches stacked on a new leading axis:
    order unchanged, final slab partial when the epoch length is not a
    multiple of unroll, and max_batches truncates mid-slab."""
    pre = PassThroughPreprocessing()
    configure(pre, {"input_key": "image", "target_key": "label"}, name="pre")

    def batches():
        return batch_iterator(
            make_source(32), pre, 4, training=False, shuffle=False
        )

    flat = collect_inputs(batches())
    slabs = list(slab_iterator(batches(), 3))
    # 8 batches at unroll 3 -> slabs of 3, 3, 2.
    assert [s["input"].shape[0] for s in slabs] == [3, 3, 2]
    assert slabs[0]["input"].shape == (3, 4, 4, 4, 1)
    restacked = np.concatenate(
        [s["input"].reshape(-1, 4, 4, 1) for s in slabs]
    )[:, 0, 0, 0]
    np.testing.assert_array_equal(restacked, flat)

    # max_batches mid-slab: 5 batches at unroll 4 -> 4 + 1.
    capped = list(slab_iterator(batches(), 4, max_batches=5))
    assert [s["input"].shape[0] for s in capped] == [4, 1]
    np.testing.assert_array_equal(
        np.concatenate([s["input"].reshape(-1, 4, 4, 1) for s in capped])[
            :, 0, 0, 0
        ],
        flat[:20],
    )

    # unroll=1 slabs are [1, batch, ...] (degenerate but well-formed).
    ones = list(slab_iterator(batches(), 1, max_batches=2))
    assert [s["input"].shape[:2] for s in ones] == [(1, 4), (1, 4)]

    # max_batches=0 yields NOTHING (matching islice semantics on the
    # unroll=1 loader surface), not a one-batch slab.
    assert list(slab_iterator(batches(), 4, max_batches=0)) == []

    with pytest.raises(ValueError, match="unroll"):
        list(slab_iterator(batches(), 0))


def test_slab_iterator_rejects_shape_changing_batches():
    """A partial FINAL BATCH (drop_remainder=False) cannot be stacked
    into a slab — fail loudly instead of mis-stacking, INCLUDING when
    slab alignment puts the partial batch alone in the last slab
    (where a per-slab check would see uniform shapes and silently
    emit a shape-changing slab)."""
    pre = PassThroughPreprocessing()
    configure(pre, {"input_key": "image", "target_key": "label"}, name="pre")

    def batches(n):
        return batch_iterator(
            make_source(n), pre, 8, training=False, shuffle=False,
            drop_remainder=False,
        )

    # 30 examples: batches 8,8,8,6 — partial shares slab 1 of 4.
    with pytest.raises(ValueError, match="slab"):
        list(slab_iterator(batches(30), 4))
    # 36 examples: batches 8,8,8,8,4 — partial is ALONE in slab 2.
    with pytest.raises(ValueError, match="slab"):
        list(slab_iterator(batches(36), 4))


def test_dataloader_unroll_yields_device_slabs():
    """DataLoader.batches(unroll=k) stages [k, batch, ...] device slabs
    equal to the same call's consecutive single batches stacked."""
    import jax

    conf = {
        "dataset": "SyntheticMnist",
        "dataset.num_train_examples": 64,
        "preprocessing": "ImageClassificationPreprocessing",
        "preprocessing.height": 28,
        "preprocessing.width": 28,
        "preprocessing.channels": 1,
        "batch_size": 16,
        "host_index": 0,
        "host_count": 1,
    }
    loader = DataLoader()
    configure(loader, conf, name="loader")
    singles = list(loader.batches("train", epoch=0))
    loader2 = DataLoader()
    configure(loader2, conf, name="loader2")
    slabs = list(loader2.batches("train", epoch=0, unroll=2))
    assert len(singles) == 4 and len(slabs) == 2
    assert isinstance(slabs[0]["input"], jax.Array)
    assert slabs[0]["input"].shape == (2, 16, 28, 28, 1)
    for i, slab in enumerate(slabs):
        for j in range(2):
            np.testing.assert_array_equal(
                np.asarray(slab["input"][j]),
                np.asarray(singles[2 * i + j]["input"]),
            )
            np.testing.assert_array_equal(
                np.asarray(slab["target"][j]),
                np.asarray(singles[2 * i + j]["target"]),
            )

    # max_batches caps the eager (unroll=1) surface too.
    loader3 = DataLoader()
    configure(loader3, conf, name="loader3")
    assert len(list(loader3.batches("train", epoch=0, max_batches=3))) == 3


def test_preprocessing_input_dtype_hints():
    """The data layer's dtype hint for dummy-input consumers
    (models.summary): tokens are int32, pixels float32, passthrough
    unknown."""
    assert TokenPreprocessing().input_dtype == "int32"
    img = ImageClassificationPreprocessing()
    assert img.input_dtype == "float32"
    assert PassThroughPreprocessing().input_dtype is None


def test_start_batch_out_of_range_fails_loudly():
    """A miscomputed resume point must raise, not silently train zero
    steps: negative start_batch, start_batch at the epoch end, and
    start_batch beyond it are all rejected (a legitimate epoch-boundary
    resume rolls into the next epoch at step 0). Validation happens at
    first iteration (batch_iterator is a generator)."""
    src = make_source(32)  # 4 batches of 8
    kw = dict(training=True, shuffle=True, seed=0)

    # Valid interior resume points still work.
    assert len(list(batch_iterator(src, None, 8, **kw, start_batch=3))) == 1

    for bad in (-1, 4, 5):
        with pytest.raises(ValueError, match="start_batch"):
            list(batch_iterator(src, None, 8, **kw, start_batch=bad))

    # Through the DataLoader surface too (the path Experiment uses).
    loader = DataLoader()
    configure(
        loader,
        {
            "dataset": "SyntheticMnist",
            "dataset.num_train_examples": 32,
            "preprocessing": "PassThroughPreprocessing",
            "batch_size": 8,
        },
        name="loader",
    )
    with pytest.raises(ValueError, match="start_batch"):
        list(loader.batches("train", epoch=0, start_batch=-2))


def test_start_batch_validated_even_on_empty_source():
    """The validation must not be bypassed by the empty-source early
    exit: a zero-example source with a stale resume point fails loudly
    instead of silently yielding nothing forever."""
    empty = ArraySource(
        {
            "image": np.zeros((0, 4, 4, 1), np.float32),
            "label": np.zeros((0,), np.int32),
        }
    )
    # start_batch=0 on an empty source is a legitimate empty iteration.
    assert list(batch_iterator(empty, None, 8, training=True)) == []
    for bad in (-1, 3):
        with pytest.raises(ValueError, match="start_batch"):
            list(
                batch_iterator(
                    empty, None, 8, training=True, start_batch=bad
                )
            )


def test_train_split_smaller_than_global_batch_fails_loudly():
    """A train split that cannot fill one global batch (remainder
    dropped) would otherwise 'train' zero steps per epoch forever; eval
    iteration of the same source stays permissive (callers handle
    produced-no-batches explicitly)."""
    src = make_source(6)  # 6 examples < batch 8
    with pytest.raises(ValueError, match="zero batches"):
        list(batch_iterator(src, None, 8, training=True))
    # Eval mode without remainder dropping still yields the partial batch.
    got = list(
        batch_iterator(
            src, None, 8, training=False, shuffle=False,
            drop_remainder=False,
        )
    )
    assert len(got) == 1 and got[0]["image"].shape[0] == 6
    # Eval mode WITH remainder dropping: empty, silently (callers own it).
    assert (
        list(batch_iterator(src, None, 8, training=False, shuffle=False))
        == []
    )


# -- PR 24: the producer thread's leaf spans ------------------------------


@pytest.fixture
def loader_records():
    """Records of a tiny traced ``prefetch_to_device`` pass: 5 batches
    through a queue of 1 with a consumer slower than the producer, so the
    producer also waits on a full queue."""
    import time

    from zookeeper_tpu.observability import trace

    pre = PassThroughPreprocessing()
    configure(pre, {}, name="pre")
    prior = trace.get_tracer()
    trace.install(trace.Tracer(1024))
    try:
        it = batch_iterator(
            make_source(20), pre, 4, training=False, shuffle=False
        )
        n = 0
        for _ in prefetch_to_device(it, size=1):
            time.sleep(0.01)
            n += 1
        assert n == 5
        deadline = time.time() + 5.0
        while time.time() < deadline and not any(
            r["name"] == "loader_assemble" and r["step"] == 5
            for r in trace.get_tracer().snapshot()
        ):
            time.sleep(0.01)  # the producer's last pull, after the pass
        yield trace.get_tracer().snapshot()
    finally:
        trace.install(prior)


def test_producer_spans_are_leaves_on_the_prefetch_thread(loader_records):
    from tests.observability.trace_leaves import overlapping_spans

    spans = [r for r in loader_records if r["phase"] == "X"]
    assert {r["name"] for r in spans} == {
        "loader_assemble", "loader_stage", "loader_put_wait",
    }
    assert {r["thread_name"] for r in spans} == {"zk-prefetch"}
    assert overlapping_spans(loader_records) == []


def test_leaves_of_one_batch_share_its_index_and_do_not_overlap(loader_records):
    by_step = {}
    for r in loader_records:
        by_step.setdefault(r["step"], []).append(r)
    # batches 0..4 have all three leaves, in order; the pull that found
    # the iterator exhausted (index 5) made no batch
    assert sorted(by_step) == [0, 1, 2, 3, 4, 5]
    assert [r["name"] for r in by_step[5]] == ["loader_assemble"]
    at = 0
    for step in range(5):
        names = [r["name"] for r in by_step[step]]
        assert names == ["loader_assemble", "loader_stage", "loader_put_wait"]
        for r in by_step[step]:
            assert r["ts_ns"] >= at
            at = r["ts_ns"] + r["dur_ns"]
    # a slow consumer behind a queue of one: the producer waited to put
    waited = sum(
        r["dur_ns"] for r in loader_records if r["name"] == "loader_put_wait"
    )
    assert waited > 5_000_000


def test_producer_records_nothing_while_tracing_is_off():
    from zookeeper_tpu.observability import trace

    assert not trace.enabled()
    pre = PassThroughPreprocessing()
    configure(pre, {}, name="pre")
    it = batch_iterator(make_source(8), pre, 4, training=False, shuffle=False)
    assert len(list(prefetch_to_device(it, size=2))) == 2
    assert trace.get_tracer() is None
