"""The fused native augmented batch assembly vs the Python reference.

The contract under test (ISSUE 3 tentpole): both augmentation paths draw
from the shared ``(seed, index, epoch)`` counter RNG (``data/augrng``)
and use only exactly-rounded float ops, so the native C++ kernel and the
per-example Python path produce BIT-identical batches — which is what
keeps the bit-exact-resume and multi-host-agreement contracts intact
when the pipeline silently switches between them.
"""

import numpy as np
import pytest

from zookeeper_tpu import native
from zookeeper_tpu.core import configure
from zookeeper_tpu.data import (
    ArraySource,
    ImageClassificationPreprocessing,
    batch_iterator,
)
from zookeeper_tpu.data.augrng import AugRng, recipe_exp

needs_native = pytest.mark.skipif(
    not native.available(), reason="no toolchain (numpy-fallback CI leg)"
)


def make_pre(conf, name):
    pre = ImageClassificationPreprocessing()
    configure(pre, conf, name=name)
    return pre


def force_python(pre):
    """Hide the native spec so batch_iterator takes the per-example
    Python path (the reference implementation)."""
    object.__setattr__(pre, "native_batch_spec", lambda training: None)
    return pre


def image_source(shape, n=24, rng_seed=0, n_labels=10):
    rng = np.random.default_rng(rng_seed)
    return ArraySource(
        {
            "image": rng.integers(0, 256, size=(n, *shape), dtype=np.uint8),
            "label": rng.integers(0, n_labels, size=(n,)).astype(np.int64),
        }
    )


RECIPES = {
    # The CIFAR/larq recipe: reflect-pad 4 + crop + flip, zero-centered.
    "cifar_pad_crop": (
        {"height": 16, "width": 16, "channels": 3, "augment": True,
         "pad_pixels": 4},
        (16, 16, 3),
    ),
    # ImageNet-style RandomResizedCrop from a LARGER square source.
    "rrc_square": (
        {"height": 16, "width": 16, "channels": 3, "augment": True,
         "random_resized_crop": True},
        (24, 24, 3),
    ),
    # RRC from a NON-SQUARE source (rejection sampling + aspect handling
    # hit different branches; 17 is coprime with everything).
    "rrc_non_square": (
        {"height": 12, "width": 12, "channels": 3, "augment": True,
         "random_resized_crop": True},
        (24, 17, 3),
    ),
    # RRC downscale-heavy, grayscale channel, no flip, no zero-center.
    "rrc_gray_noflip": (
        {"height": 8, "width": 8, "channels": 1, "augment": True,
         "random_resized_crop": True, "random_flip": False,
         "zero_center": False},
        (32, 32, 1),
    ),
    # RRC where the crop can UPSCALE (source smaller than output).
    "rrc_upscale": (
        {"height": 16, "width": 16, "channels": 3, "augment": True,
         "random_resized_crop": True},
        (10, 13, 3),
    ),
    # Flip-only (pad_pixels=0 consumes no crop draws).
    "flip_only": (
        {"height": 8, "width": 8, "channels": 3, "augment": True,
         "pad_pixels": 0},
        (8, 8, 3),
    ),
}


@needs_native
@pytest.mark.parametrize("recipe", sorted(RECIPES))
@pytest.mark.parametrize("seed,epoch", [(0, 0), (7, 2)])
def test_native_vs_python_bit_identical(recipe, seed, epoch):
    """The tentpole contract: whole batches across a (seed, epoch) grid,
    bitwise equal (assert_array_equal, not allclose)."""
    conf, shape = RECIPES[recipe]
    src = image_source(shape)
    kw = dict(training=True, shuffle=True, seed=seed, epoch=epoch)
    fast = list(
        batch_iterator(src, make_pre(conf, f"f{recipe}{seed}{epoch}"), 8, **kw)
    )
    slow = list(
        batch_iterator(
            src,
            force_python(make_pre(conf, f"s{recipe}{seed}{epoch}")),
            8,
            **kw,
        )
    )
    assert len(fast) == len(slow) == 3
    for a, b in zip(fast, slow):
        np.testing.assert_array_equal(a["input"], b["input"])
        np.testing.assert_array_equal(a["target"], b["target"])
        assert a["input"].dtype == np.float32
        assert a["target"].dtype == np.int32


@needs_native
def test_native_augment_engages(monkeypatch):
    """The training fast path actually calls the fused kernel (no silent
    fallback to per-example Python — the regression this PR closes)."""
    conf, shape = RECIPES["cifar_pad_crop"]
    src = image_source(shape)
    calls = []
    real = native.gather_augment_normalize
    monkeypatch.setattr(
        native,
        "gather_augment_normalize",
        lambda *a, **k: (calls.append(1), real(*a, **k))[1],
    )
    out = list(
        batch_iterator(
            src, make_pre(conf, "engage"), 8, training=True, shuffle=True
        )
    )
    assert len(out) == 3
    assert len(calls) == 3, "augmented native assembly was not hit"


@needs_native
def test_native_augment_mid_epoch_resume():
    """start_batch resume through the native path reproduces the
    uninterrupted epoch's suffix exactly (the bit-exact-resume
    contract surviving the new kernel)."""
    conf, shape = RECIPES["rrc_square"]
    src = image_source(shape, n=32)
    kw = dict(training=True, shuffle=True, seed=5, epoch=3)
    full = list(batch_iterator(src, make_pre(conf, "r0"), 8, **kw))
    resumed = list(
        batch_iterator(src, make_pre(conf, "r1"), 8, start_batch=2, **kw)
    )
    assert len(full) == 4 and len(resumed) == 2
    for a, b in zip(full[2:], resumed):
        np.testing.assert_array_equal(a["input"], b["input"])
        np.testing.assert_array_equal(a["target"], b["target"])


@needs_native
@pytest.mark.parametrize("recipe", ["cifar_pad_crop", "rrc_non_square"])
def test_augment_into_out_is_the_returned_array(recipe):
    """``out=`` gives bit-identical numbers to the returned-array form,
    written into the buffer given, whatever that buffer held before."""
    conf, shape = RECIPES[recipe]
    store = image_source(shape).arrays["image"]
    idx = np.array([5, 0, 17, 5, 23], np.int64)
    kw = dict(
        out_height=conf["height"], out_width=conf["width"], seed=3, epoch=1,
        random_resized_crop=conf.get("random_resized_crop", False),
        crop_scale_range=(0.08, 1.0),
        log_aspect_range=(float(np.log(3 / 4)), float(np.log(4 / 3))),
        pad_pixels=conf.get("pad_pixels", 0),
    )
    fresh = native.gather_augment_normalize(store, idx, **kw)
    out = np.full_like(fresh, np.nan)
    got = native.gather_augment_normalize(store, idx, out=out, **kw)
    assert got is out
    np.testing.assert_array_equal(out, fresh)


@pytest.mark.parametrize("library", [True, False], ids=["native", "numpy"])
def test_normalize_into_out_is_the_returned_array(library, monkeypatch):
    """The same for the plain gather, in the kernel and in its numpy
    fallback."""
    if library and not native.available():
        pytest.skip("no toolchain (numpy-fallback CI leg)")
    if not library:
        monkeypatch.setattr(native, "_load", lambda: None)
    store = image_source((6, 5, 3)).arrays["image"]
    idx = np.array([3, 0, 9, 3], np.int64)
    fresh = native.gather_normalize(store, idx, 2.0 / 255.0, -1.0)
    out = np.full((4, 6, 5, 3), np.nan, np.float32)
    got = native.gather_normalize(store, idx, 2.0 / 255.0, -1.0, out=out)
    assert got is out
    np.testing.assert_array_equal(out, fresh)
    np.testing.assert_array_equal(
        fresh, store[idx].astype(np.float32) * np.float32(2.0 / 255.0)
        + np.float32(-1.0),
    )


@pytest.mark.parametrize(
    "bad",
    [
        np.zeros((4, 6, 5, 3), np.float64),  # dtype
        np.zeros((4, 6, 5, 2), np.float32),  # shape
        np.zeros((4, 5, 6, 3), np.float32).transpose(0, 2, 1, 3),  # layout
    ],
    ids=["dtype", "shape", "layout"],
)
def test_out_is_checked(bad):
    store = image_source((6, 5, 3)).arrays["image"]
    with pytest.raises(ValueError, match="out must be"):
        native.gather_normalize(
            store, np.array([0, 1, 2, 3]), 1.0, 0.0, out=bad
        )


def test_python_fallback_when_library_absent(monkeypatch):
    """With the .so unavailable the pipeline must keep producing batches
    through the per-example Python path — and because the two paths are
    bit-identical, the OUTPUT is the same either way (asserted against a
    spec-hidden reference run)."""
    conf, shape = RECIPES["cifar_pad_crop"]
    src = image_source(shape)
    monkeypatch.setattr(native, "available", lambda: False)
    monkeypatch.setattr(
        native,
        "gather_augment_normalize",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("fused kernel must not be called when absent")
        ),
    )
    kw = dict(training=True, shuffle=True, seed=1, epoch=0)
    got = list(batch_iterator(src, make_pre(conf, "fb0"), 8, **kw))
    ref = list(
        batch_iterator(src, force_python(make_pre(conf, "fb1")), 8, **kw)
    )
    assert len(got) == 3
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a["input"], b["input"])


@needs_native
def test_fallback_when_store_unsupported(monkeypatch):
    """Unsupported stores (non-uint8 dtype; 2-D grayscale layout;
    shape-mismatched pad+crop source) fall back to Python instead of
    feeding the kernel garbage."""
    monkeypatch.setattr(
        native,
        "gather_augment_normalize",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("fused kernel must not be called for this store")
        ),
    )
    conf, _ = RECIPES["cifar_pad_crop"]
    rng = np.random.default_rng(3)
    # float32 store.
    src = ArraySource(
        {
            "image": rng.random((16, 16, 16, 3)).astype(np.float32),
            "label": np.zeros(16, np.int64),
        }
    )
    assert (
        len(
            list(
                batch_iterator(
                    src, make_pre(conf, "u0"), 8, training=True
                )
            )
        )
        == 2
    )
    # (N, H, W) grayscale store without the channel axis.
    gray_conf = dict(conf, channels=1)
    src2 = ArraySource(
        {
            "image": rng.integers(0, 256, (16, 16, 16), dtype=np.uint8),
            "label": np.zeros(16, np.int64),
        }
    )
    assert (
        len(
            list(
                batch_iterator(
                    src2, make_pre(gray_conf, "u1"), 8, training=True
                )
            )
        )
        == 2
    )
    # pad+crop recipe over a source that is NOT output-shaped (the
    # Python path center-crops afterwards; the kernel doesn't model it).
    src3 = ArraySource(
        {
            "image": rng.integers(0, 256, (16, 20, 20, 3), dtype=np.uint8),
            "label": np.zeros(16, np.int64),
        }
    )
    assert (
        len(
            list(
                batch_iterator(
                    src3, make_pre(conf, "u2"), 8, training=True
                )
            )
        )
        == 2
    )
    # pad_pixels >= image side: numpy reflect-pads repeatedly, which the
    # kernel's single-bounce reflect does not model — must fall back
    # (the kernel would otherwise read OUT OF BOUNDS and silently
    # diverge from the reference).
    big_pad = dict(conf, pad_pixels=16)
    src4 = ArraySource(
        {
            "image": rng.integers(0, 256, (16, 16, 16, 3), dtype=np.uint8),
            "label": np.zeros(16, np.int64),
        }
    )
    assert (
        len(
            list(
                batch_iterator(
                    src4, make_pre(big_pad, "u3"), 8, training=True
                )
            )
        )
        == 2
    )


def test_augrng_determinism_and_spread():
    """The shared counter RNG's Python half: keyed streams are
    reproducible, distinct across any one key component, and uniform
    draws stay in-range."""
    a = [AugRng(1, 2, 3).next_u64() for _ in range(4)]
    assert a == [AugRng(1, 2, 3).next_u64() for _ in range(4)]
    streams = {
        tuple(AugRng(s, i, e).next_u64() for _ in range(4))
        for s, i, e in [(1, 2, 3), (0, 2, 3), (1, 0, 3), (1, 2, 0)]
    }
    assert len(streams) == 4
    r = AugRng(0, 0, 0)
    us = [r.uniform(-2.0, 3.0) for _ in range(200)]
    assert all(-2.0 <= u < 3.0 for u in us)
    assert min(us) < -1.0 and max(us) > 2.0  # actually spreads
    assert {r.randint(4) for _ in range(100)} == {0, 1, 2, 3}


def test_recipe_exp_accuracy():
    """The shared Horner exp: within a few ulp of libm exp over the
    aspect-draw range real configs use."""
    import math

    for u in np.linspace(-2.0, 2.0, 41):
        assert recipe_exp(float(u)) == pytest.approx(
            math.exp(float(u)), rel=1e-14
        )


def test_bilinear_resize_reference_values():
    """_resize_bilinear: exact 2x upsample of a ramp keeps half-pixel
    symmetry (edge rows clamp, interior rows average), and downsample by
    2 averages adjacent pixels exactly."""
    from zookeeper_tpu.data.preprocessing import _resize_bilinear

    img = np.arange(4, dtype=np.float32)[:, None, None] * np.ones(
        (1, 4, 1), np.float32
    )
    up = _resize_bilinear(img, 8, 8)
    assert up.shape == (8, 8, 1)
    # Half-pixel centers: row values are clamp-interpolated at
    # sy = (y + .5)/2 - .5 = [-0.25, 0.25, 0.75, ...] -> [0, .25, .75...].
    np.testing.assert_allclose(
        up[:, 0, 0],
        [0.0, 0.25, 0.75, 1.25, 1.75, 2.25, 2.75, 3.0],
        rtol=1e-6,
    )
    down = _resize_bilinear(img, 2, 2)
    np.testing.assert_allclose(down[:, 0, 0], [0.5, 2.5], rtol=1e-6)
