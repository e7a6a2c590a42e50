"""ctypes bindings for the native host kernels (see src/zk_native.cpp).

Builds ``libzk_native-<srchash>.so`` on first use with g++ (cached by
content hash: the binary filename embeds a hash of the source, so a stale
or mismatched binary can never be picked up — git does not preserve mtimes,
making mtime staleness checks unreliable after a clone). Every entry point
has a numpy fallback so the framework works on machines without a
toolchain — the native path is a host-throughput optimization, never a
requirement. No prebuilt binary ships in the repo.
"""

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "zk_native.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
#: How the library was obtained: "built" (compiled by this process),
#: "found" (a binary for this source hash already lay on disk) or
#: "unavailable" (no toolchain / build failed — the numpy and Python
#: fallbacks serve).
_status = "unavailable"


def _build_dirs():
    """Candidate directories for the built binary: package dir first (warm
    for every user of the checkout), then a per-user cache (covers
    read-only site-packages installs)."""
    yield _HERE
    cache = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache"
    )
    yield os.path.join(cache, "zookeeper_tpu")


# -ffp-contract=off: the augmented-assembly kernel is BIT-identical
# to the numpy reference only if mul+add stays two rounded ops (an
# auto-contracted FMA on FMA-capable targets would flip the last
# ulp of every bilinear tap). Module-level so the digest can cover it.
_BUILD_FLAGS = (
    "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
    "-ffp-contract=off",
)


def _src_digest() -> str:
    # The digest covers the COMPILE FLAGS as well as the source: flags
    # like -ffp-contract are correctness-load-bearing (bit-identity
    # contract), so a flags-only change must miss the binary cache just
    # like a source edit.
    h = hashlib.sha256()
    h.update(" ".join(_BUILD_FLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return h.hexdigest()[:12]


def _build(lib_path: str) -> bool:
    # Unique temp per builder: concurrent processes must not interleave
    # writes into one file (os.replace then promotes only complete builds).
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", *_BUILD_FLAGS, _SRC, "-o", tmp]
    try:
        os.makedirs(os.path.dirname(lib_path), exist_ok=True)
        subprocess.run(
            cmd, check=True, capture_output=True, timeout=120
        )
        os.replace(tmp, lib_path)
        # GC binaries for older source revisions (hash-named, never reused).
        base = os.path.basename(lib_path)
        for f in os.listdir(os.path.dirname(lib_path)):
            if (
                f.startswith("libzk_native-")
                and f.endswith(".so")
                and f != base
            ):
                try:
                    os.unlink(os.path.join(os.path.dirname(lib_path), f))
                except OSError:
                    pass
        return True
    except (OSError, subprocess.SubprocessError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            digest = _src_digest()
        except OSError:
            return None
        lib = None
        for d in _build_dirs():
            lib_path = os.path.join(d, f"libzk_native-{digest}.so")
            how = "found"
            if not os.path.exists(lib_path):
                if not _build(lib_path):
                    continue
                how = "built"
            try:
                lib = ctypes.CDLL(lib_path)
                break
            except OSError:
                # Corrupt or wrong-arch binary: rebuild once, else move on.
                try:
                    os.unlink(lib_path)
                except OSError:
                    continue
                if _build(lib_path):
                    how = "built"
                    try:
                        lib = ctypes.CDLL(lib_path)
                        break
                    except OSError:
                        continue
        if lib is None:
            return None
        global _status
        _status = how
        lib.zk_pack_bits_f32.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64, ctypes.c_int64,
        ]
        lib.zk_gather_normalize_u8.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float,
        ]
        lib.zk_gather_augment_normalize_u8.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),   # store
            ctypes.POINTER(ctypes.c_int64),   # indices
            ctypes.POINTER(ctypes.c_float),   # out
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,  # batch,h,w
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,  # c,oh,ow
            ctypes.c_int64, ctypes.c_int64,   # seed, epoch
            ctypes.c_int32,                   # random_resized_crop
            ctypes.c_double, ctypes.c_double,  # scale range
            ctypes.c_double, ctypes.c_double,  # log-aspect range
            ctypes.c_int32, ctypes.c_int32,   # pad_pixels, random_flip
            ctypes.c_float, ctypes.c_float,   # post_scale, post_shift
        ]
        lib.zk_xnor_gemm_ref.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32,
        ]
        lib.zk_version.restype = ctypes.c_int
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def status() -> str:
    """How the library was obtained: "built", "found" or "unavailable"
    (see ``_status``). Loads it on first use, like :func:`available`."""
    _load()
    return _status


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def pack_bits(x: np.ndarray) -> np.ndarray:
    """Pack sign bits of the last axis (length % 32 == 0) into int32 words."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    rows = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
    cols = x.shape[-1]
    if cols % 32 != 0:
        raise ValueError(f"Packed axis must be a multiple of 32, got {cols}.")
    out_shape = (*x.shape[:-1], cols // 32)
    lib = _load()
    if lib is None:  # numpy fallback
        bits = (x.reshape(rows, cols) >= 0).astype(np.uint32)
        bits = bits.reshape(rows, cols // 32, 32)
        words = (bits << np.arange(32, dtype=np.uint32)).sum(
            axis=-1, dtype=np.uint32
        )
        return words.astype(np.int32).reshape(out_shape)
    out = np.empty((rows, cols // 32), dtype=np.int32)
    lib.zk_pack_bits_f32(
        _ptr(x.reshape(rows, cols), ctypes.c_float), _ptr(out, ctypes.c_int32),
        rows, cols,
    )
    return out.reshape(out_shape)


def gather_normalize(
    store: np.ndarray, indices: np.ndarray, scale: float, shift: float
) -> np.ndarray:
    """Fused batch assembly: ``(scale * store[indices] + shift)`` as float32.

    ``store``: [N, ...] uint8; returns [len(indices), ...] float32.
    """
    store = np.ascontiguousarray(store)
    if store.dtype != np.uint8:
        raise ValueError("gather_normalize expects a uint8 store.")
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    example_shape = store.shape[1:]
    example_size = int(np.prod(example_shape))
    batch = len(indices)
    lib = _load()
    if lib is None:  # numpy fallback
        return (
            store[indices].astype(np.float32) * np.float32(scale)
            + np.float32(shift)
        )
    out = np.empty((batch, example_size), dtype=np.float32)
    lib.zk_gather_normalize_u8(
        _ptr(store.reshape(store.shape[0], example_size), ctypes.c_uint8),
        _ptr(indices, ctypes.c_int64),
        _ptr(out, ctypes.c_float),
        batch, example_size, float(scale), float(shift),
    )
    return out.reshape(batch, *example_shape)


def gather_augment_normalize(
    store: np.ndarray,
    indices: np.ndarray,
    *,
    out_height: int,
    out_width: int,
    seed: int,
    epoch: int,
    random_resized_crop: bool,
    crop_scale_range=(0.08, 1.0),
    log_aspect_range=(0.0, 0.0),
    pad_pixels: int = 0,
    random_flip: bool = True,
    post_scale: float = 2.0,
    post_shift: float = -1.0,
) -> np.ndarray:
    """Fused AUGMENTED batch assembly over a ``[N, H, W, C]`` uint8 store:
    per-example RandomResizedCrop (bilinear) or reflect-pad+crop, flip,
    normalize — bit-identical to the Python reference path
    (``ImageClassificationPreprocessing`` with ``augment=True``) through
    the shared ``(seed, index, epoch)`` counter RNG (``data/augrng.py``).

    Unlike the other entry points there is NO numpy fallback here: the
    per-example Python preprocessing path IS the reference
    implementation, so callers (``data/pipeline.py``) gate on
    ``available()`` and simply keep using it when the toolchain is
    absent. Raises RuntimeError if called without the library.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(
            "native library unavailable — use the Python preprocessing "
            "path (bit-identical by contract)."
        )
    store = np.ascontiguousarray(store)
    if store.dtype != np.uint8 or store.ndim != 4:
        raise ValueError(
            "gather_augment_normalize expects a [N, H, W, C] uint8 store, "
            f"got {store.dtype} {store.shape}."
        )
    if not random_resized_crop and store.shape[1:3] != (out_height, out_width):
        raise ValueError(
            "pad+crop recipe requires the store's spatial shape "
            f"{store.shape[1:3]} to equal the output ({out_height}, "
            f"{out_width}); only RandomResizedCrop resizes."
        )
    if not random_resized_crop and pad_pixels >= min(out_height, out_width):
        # The kernel's single-bounce reflect indexing is valid only for
        # pad < side; numpy's np.pad(mode="reflect") reflects repeatedly
        # for larger pads, so the Python path must handle those.
        raise ValueError(
            f"pad_pixels={pad_pixels} >= min image side "
            f"{min(out_height, out_width)} is outside the fused kernel's "
            "reflect range — use the Python preprocessing path."
        )
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    batch = len(indices)
    channels = store.shape[3]
    out = np.empty((batch, out_height, out_width, channels), np.float32)
    lib.zk_gather_augment_normalize_u8(
        _ptr(store, ctypes.c_uint8),
        _ptr(indices, ctypes.c_int64),
        _ptr(out, ctypes.c_float),
        batch, store.shape[1], store.shape[2], channels,
        out_height, out_width, int(seed), int(epoch),
        int(bool(random_resized_crop)),
        float(crop_scale_range[0]), float(crop_scale_range[1]),
        float(log_aspect_range[0]), float(log_aspect_range[1]),
        int(pad_pixels), int(bool(random_flip)),
        float(post_scale), float(post_shift),
    )
    return out


def xnor_gemm(
    a_packed: np.ndarray, b_packed: np.ndarray, k_true: int
) -> np.ndarray:
    """CPU XNOR-popcount GEMM on packed operands (reference twin of the
    Pallas TPU kernel): a [M, KP] int32, b [N, KP] int32 -> [M, N] int32."""
    a_packed = np.ascontiguousarray(a_packed, dtype=np.int32)
    b_packed = np.ascontiguousarray(b_packed, dtype=np.int32)
    m, kp = a_packed.shape
    n, kp2 = b_packed.shape
    if kp != kp2:
        raise ValueError(f"Packed K mismatch: {kp} vs {kp2}.")
    lib = _load()
    if lib is None:  # numpy fallback
        xor = np.bitwise_xor(
            a_packed[:, None, :].view(np.uint32),
            b_packed[None, :, :].view(np.uint32),
        )
        mismatches = np.unpackbits(
            xor.view(np.uint8), axis=-1, bitorder="little"
        ).sum(axis=-1, dtype=np.int32)
        return (k_true - 2 * mismatches).astype(np.int32)
    out = np.empty((m, n), dtype=np.int32)
    lib.zk_xnor_gemm_ref(
        _ptr(a_packed, ctypes.c_int32), _ptr(b_packed, ctypes.c_int32),
        _ptr(out, ctypes.c_int32), m, n, kp, int(k_true),
    )
    return out
