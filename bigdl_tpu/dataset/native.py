"""ctypes bindings for the native C++ input pipeline
(``native/bigdl_native.cpp``) — the TPU-native analog of the reference's
multi-threaded decode/augment path (image/MTLabeledBGRImgToBatch.scala:48-133)
and its raw dataset readers (models/lenet/Utils.scala idx parsing,
models/vgg CIFAR bins).

``NativePrefetchDataSet`` plugs into the same :class:`DataSet` protocol the
Optimizer consumes: worker threads crop/flip/normalize raw uint8 samples on
the host while the device runs the previous step, so step time is
max(compute, input) instead of their sum.

Falls back cleanly: :func:`available` is False when the shared library
can't be built (no g++); callers then use the pure-python transformers in
``bigdl_tpu.dataset.image``.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

from bigdl_tpu.dataset.dataset import DataSet, MiniBatch

__all__ = ["available", "NativePrefetchDataSet", "read_idx", "read_cifar10"]

# Native sources ship as package data (bigdl_tpu/native/); when the install
# is read-only (system site-packages) the build happens in a per-user cache
# dir instead, so `pip install bigdl-tpu` degrades gracefully rather than
# failing at first import.
_PKG_NATIVE_DIR = os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "native")


def _build_dir() -> str:
    if (os.access(_PKG_NATIVE_DIR, os.W_OK)
            or os.path.exists(os.path.join(_PKG_NATIVE_DIR,
                                           "libbigdl_native.so"))):
        # writable (dev checkout / user install) or a wheel shipped a
        # prebuilt .so — build/load in place
        return _PKG_NATIVE_DIR
    cache = os.path.join(
        os.environ.get("XDG_CACHE_HOME",
                       os.path.join(os.path.expanduser("~"), ".cache")),
        "bigdl_tpu", "native")
    os.makedirs(cache, exist_ok=True)
    import filecmp
    import shutil
    for fname in ("bigdl_native.cpp", "Makefile"):
        src = os.path.join(_PKG_NATIVE_DIR, fname)
        dst = os.path.join(cache, fname)
        # copy only on content change, with a fresh dst mtime: mtime
        # comparison alone misfires on SOURCE_DATE_EPOCH wheels (stale .so
        # after upgrade), while unconditional copying would force a full
        # g++ rebuild on every process start
        if os.path.exists(src) and not (
                os.path.exists(dst) and filecmp.cmp(src, dst, shallow=False)):
            shutil.copyfile(src, dst)
    return cache


# resolved lazily in _load_impl(): computing the cache dir at import can
# raise (read-only install + unwritable HOME) and would break the
# graceful-degrade contract for every `import bigdl_tpu.dataset`
_NATIVE_DIR: Optional[str] = None
_LIB_PATH: Optional[str] = None

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_load_lock = threading.Lock()  # streaming workers probe concurrently


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    with _load_lock:
        if _load_attempted:
            return _lib
        _load_attempted = True
        _lib = _load_impl()
        return _lib


def _load_impl() -> Optional[ctypes.CDLL]:
    global _NATIVE_DIR, _LIB_PATH
    try:
        _NATIVE_DIR = _build_dir()
    except OSError:
        return None
    _LIB_PATH = os.path.join(_NATIVE_DIR, "libbigdl_native.so")
    try:  # always run make: incremental, and rebuilds a stale .so whose
        # symbols predate the current bindings (g++ is in the toolchain).
        # flock serializes concurrent builds across PROCESSES sharing the
        # filesystem (multi-host runs) — dlopen of a half-linked .so is
        # undefined behavior
        import fcntl

        with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lk:
            fcntl.flock(lk, fcntl.LOCK_EX)
            try:
                subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                               capture_output=True, timeout=120)
            finally:
                fcntl.flock(lk, fcntl.LOCK_UN)
    except (OSError, subprocess.SubprocessError) as e:
        # report, never hide: a checkout ships no .so (git-ignored), so a
        # failed build means the pure-python paths; a failed REbuild next
        # to an older .so means that .so may predate the bindings
        import logging
        stale = os.path.exists(_LIB_PATH)
        make_err = getattr(e, "stderr", None)  # CalledProcessError only
        logging.getLogger(__name__).warning(
            "native build failed in %s (%s); %s", _NATIVE_DIR,
            make_err.decode(errors="replace").strip()[-300:]
            if make_err else repr(e),
            "loading the existing (possibly stale) .so" if stale
            else "using the pure-python data paths")
        if not stale:
            return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
        _bind(lib)
    except (OSError, AttributeError):
        # AttributeError: prebuilt .so missing a newer symbol — fall back
        # to the pure-python paths rather than crashing available()
        return None
    return lib


def _bind(lib: ctypes.CDLL) -> None:
    lib.bt_pipeline_create.restype = ctypes.c_void_p
    lib.bt_pipeline_create.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
        ctypes.c_int, ctypes.c_int,
    ]
    lib.bt_pipeline_next.restype = ctypes.c_long
    lib.bt_pipeline_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                     ctypes.c_void_p]
    lib.bt_pipeline_batches_per_epoch.restype = ctypes.c_long
    lib.bt_pipeline_batches_per_epoch.argtypes = [ctypes.c_void_p]
    lib.bt_pipeline_destroy.restype = None
    lib.bt_pipeline_destroy.argtypes = [ctypes.c_void_p]
    lib.bt_read_idx.restype = ctypes.c_int64
    lib.bt_read_idx.argtypes = [ctypes.c_char_p,
                                ctypes.POINTER(ctypes.c_void_p),
                                ctypes.POINTER(ctypes.c_int64),
                                ctypes.POINTER(ctypes.c_int)]
    lib.bt_read_cifar10.restype = ctypes.c_int64
    lib.bt_read_cifar10.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                                    ctypes.c_void_p, ctypes.c_int64]
    lib.bt_free.restype = None
    lib.bt_free.argtypes = [ctypes.c_void_p]
    lib.bt_augment_sample.restype = ctypes.c_int
    lib.bt_augment_sample.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.bt_jpeg_available.restype = ctypes.c_int
    lib.bt_jpeg_available.argtypes = []
    lib.bt_decode_jpeg.restype = ctypes.c_int
    lib.bt_decode_jpeg.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
    ]


def available() -> bool:
    """True when the native library is loadable (builds it if needed)."""
    return _load() is not None


def augment_sample_native(img: np.ndarray, out: np.ndarray, off_h: int,
                          off_w: int, flip: bool, mean: np.ndarray,
                          std: np.ndarray) -> None:
    """One-pass crop+flip+normalize (C ``bt_augment_sample``; GIL released
    during the call, so the streaming decode pool scales across cores).
    ``img``: contiguous uint8 (H, W, C); ``out``: float32 (ch, cw, C)."""
    lib = _load()
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    ok = lib.bt_augment_sample(
        img.ctypes.data_as(ctypes.c_void_p), img.shape[0], img.shape[1],
        img.shape[2], out.ctypes.data_as(ctypes.c_void_p), out.shape[0],
        out.shape[1], off_h, off_w, int(flip),
        mean.ctypes.data_as(ctypes.c_void_p),
        std.ctypes.data_as(ctypes.c_void_p))
    if not ok:
        raise ValueError(
            f"crop {out.shape[:2]} at offset ({off_h}, {off_w}) falls "
            f"outside source image {img.shape[:2]} — is short_side "
            f"smaller than the crop?")


def jpeg_available() -> bool:
    """True when the native lib was built against libjpeg.
    BIGDL_NO_NATIVE_JPEG=1 forces the PIL path (A/B benchmarking)."""
    if os.environ.get("BIGDL_NO_NATIVE_JPEG"):
        return False
    lib = _load()
    try:
        return bool(lib and lib.bt_jpeg_available())
    except AttributeError:  # stale .so predating the decode symbols
        return False


def decode_jpeg(raw: bytes, short_side: Optional[int] = None,
                fill: Optional[tuple[int, int]] = None):
    """Native JPEG decode+resize (libjpeg DCT scaling + bilinear to the
    exact target — the C counterpart of streaming.decode_resize). Returns
    an RGB uint8 (H, W, 3) array, or None when the native path can't
    serve this input (caller falls back to PIL). GIL released by ctypes,
    so a thread pool of decoders scales across cores."""
    if not jpeg_available():
        return None
    lib = _load()
    if short_side is not None:
        mode, th, tw = 0, int(short_side), 0
    else:
        mode, (th, tw) = 1, (int(fill[0]), int(fill[1]))
    out = ctypes.c_void_p()
    oh, ow = ctypes.c_int(), ctypes.c_int()
    rc = lib.bt_decode_jpeg(raw, len(raw), mode, th, tw,
                            ctypes.byref(out), ctypes.byref(oh),
                            ctypes.byref(ow))
    if rc != 0:
        return None
    try:
        n = oh.value * ow.value * 3
        img = np.frombuffer(
            ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8 * n)).contents,
            dtype=np.uint8).reshape(oh.value, ow.value, 3).copy()
    finally:
        lib.bt_free(out)
    return img


class NativePrefetchDataSet(DataSet):
    """Endless-or-one-epoch batch source backed by the C++ worker pool.

    ``images``: uint8 array [n, h, w, c]; ``labels``: int array [n].
    ``crop`` crops to (crop_h, crop_w) (random when training, else center);
    ``mean``/``std`` are per-channel, applied as ``(x - mean)/std`` on raw
    0-255 values. One python iterator epoch yields ``batches_per_epoch``
    minibatches; with ``train=True`` the C++ side keeps prefetching across
    the epoch boundary (reshuffling every epoch), so epoch N+1's first batch
    is already in the queue when epoch N ends.
    """

    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 batch_size: int, crop: Optional[tuple[int, int]] = None,
                 train: bool = False, hflip: Optional[bool] = None,
                 mean: Optional[Sequence[float]] = None,
                 std: Optional[Sequence[float]] = None,
                 shuffle: Optional[bool] = None, seed: int = 0,
                 n_threads: int = 4, queue_cap: int = 8):
        lib = _load()
        if lib is None:
            raise RuntimeError(
                "native library unavailable — use the python pipeline "
                "(bigdl_tpu.dataset.image) instead")
        self._lib = lib
        images = np.ascontiguousarray(images, dtype=np.uint8)
        if images.ndim == 3:
            images = images[..., None]
        n, h, w, c = images.shape
        labels = np.ascontiguousarray(labels, dtype=np.int32)
        assert len(labels) == n
        self._images, self._labels = images, labels  # keep alive (borrowed)
        crop_h, crop_w = crop if crop is not None else (h, w)
        self.batch_size = batch_size
        self.crop_h, self.crop_w, self.channels = crop_h, crop_w, c
        mean_arr = (np.asarray(mean, np.float32) if mean is not None
                    else np.zeros(c, np.float32))
        std_arr = (np.asarray(std, np.float32) if std is not None
                   else np.ones(c, np.float32))
        assert mean_arr.size == c and std_arr.size == c
        self._mean, self._std = mean_arr, std_arr
        self._shuffle = train if shuffle is None else shuffle
        self._hflip = train if hflip is None else hflip
        self._train = train
        self._seed = seed
        self._n_threads, self._queue_cap = n_threads, queue_cap
        self.batches_per_epoch = n // batch_size
        # train mode: one persistent endless pipeline that prefetches across
        # epoch boundaries; eval mode: a fresh one-epoch pipeline per
        # iteration (the Validator re-iterates the dataset every trigger)
        self._handle = self._create(loop=True) if train else None

    def _create(self, loop: bool):
        h_, w_ = self._images.shape[1:3]
        handle = self._lib.bt_pipeline_create(
            self._images.ctypes.data_as(ctypes.c_void_p),
            len(self._images), h_, w_, self.channels,
            self._labels.ctypes.data_as(ctypes.c_void_p), self.batch_size,
            self.crop_h, self.crop_w, int(self._train), int(self._hflip),
            self._mean.ctypes.data_as(ctypes.c_void_p),
            self._std.ctypes.data_as(ctypes.c_void_p),
            int(self._shuffle), int(loop), self._seed,
            self._n_threads, self._queue_cap)
        if not handle:
            raise ValueError("bt_pipeline_create failed (check shapes/batch)")
        return handle

    def __iter__(self):
        img_buf = np.empty((self.batch_size, self.crop_h, self.crop_w,
                            self.channels), np.float32)
        lab_buf = np.empty(self.batch_size, np.int32)
        handle = self._handle if self._train else self._create(loop=False)
        try:
            for _ in range(self.batches_per_epoch):
                t = self._lib.bt_pipeline_next(
                    handle, img_buf.ctypes.data_as(ctypes.c_void_p),
                    lab_buf.ctypes.data_as(ctypes.c_void_p))
                if t < 0:
                    return
                yield MiniBatch(img_buf.copy(), lab_buf.copy())
        finally:
            if not self._train:
                self._lib.bt_pipeline_destroy(handle)

    def size(self) -> int:
        return len(self._images)

    def shuffle(self, seed=None):
        """No-op: the native side reshuffles each epoch from its seed."""

    def close(self):
        if getattr(self, "_handle", None):
            self._lib.bt_pipeline_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def read_idx(path: str) -> np.ndarray:
    """Read an MNIST idx/ubyte file via the native reader (reference
    models/lenet/Utils.scala raw readers). ``.gz`` files are transparently
    decompressed first (parity with the python loader in
    ``bigdl_tpu.dataset.mnist``, which stays the fallback when the native
    lib is unavailable)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    if path.endswith(".gz"):
        import gzip
        import tempfile
        with gzip.open(path, "rb") as f:
            raw = f.read()
        with tempfile.NamedTemporaryFile(suffix=".idx") as tmp:
            tmp.write(raw)
            tmp.flush()
            return read_idx(tmp.name)
    out = ctypes.c_void_p()
    dims = (ctypes.c_int64 * 8)()
    ndim = ctypes.c_int()
    total = lib.bt_read_idx(path.encode(), ctypes.byref(out), dims,
                            ctypes.byref(ndim))
    if total < 0:
        raise IOError(f"failed to read idx file {path!r}")
    try:
        shape = tuple(dims[i] for i in range(ndim.value))
        buf = ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8 * total))
        arr = np.frombuffer(buf.contents, dtype=np.uint8).reshape(shape).copy()
    finally:
        lib.bt_free(out)
    return arr


def read_cifar10(paths: Sequence[str]) -> tuple[np.ndarray, np.ndarray]:
    """Read CIFAR-10 .bin shards via the native reader; returns NHWC uint8
    images + int32 labels (reference dataset CIFAR bin format)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    per_shard = 10000
    images = np.empty((per_shard * len(paths), 32, 32, 3), np.uint8)
    labels = np.empty(per_shard * len(paths), np.int32)
    count = 0
    for p in paths:
        got = lib.bt_read_cifar10(
            p.encode(),
            images[count:].ctypes.data_as(ctypes.c_void_p),
            labels[count:].ctypes.data_as(ctypes.c_void_p),
            len(images) - count)
        if got < 0:
            raise IOError(f"failed to read cifar bin {p!r}")
        count += got
    return images[:count], labels[:count]
