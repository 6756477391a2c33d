"""ctypes bindings for the native host runtime (``native/runtime.cpp``).

The shared library is built on first use with ``make`` (g++ is in the image;
pybind11 is not, hence the C ABI + ctypes).  Every entry point has a
pure-Python fallback so the package works where no toolchain exists — the
loader then runs in numpy, losing only throughput, not behavior.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from autodist_tpu.utils import logging

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_NAME = "libautodist_runtime.so"

_lib: Optional[ctypes.CDLL] = None
_lib_lock = threading.Lock()
_build_failed = False


_SRC_NAMES = ("runtime.cpp", "tokenizer.cpp")


def _src_digest() -> str:
    """SHA-256 over the sources compiled into the library.  Kept beside
    the ``.so`` at build time and compared at load time: unlike mtimes it
    survives a copy of the tree, so a library is only ever loaded if it
    was built from the sources that are there now."""
    h = hashlib.sha256()
    for name in _SRC_NAMES:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read())
    return h.hexdigest()


def _is_stale(lib_path: str, digest: str) -> bool:
    if not os.path.exists(lib_path):
        return True
    try:
        with open(lib_path + ".srchash", encoding="ascii") as f:
            return f.read().strip() != digest
    except FileNotFoundError:
        return True


def _build_and_load() -> Optional[ctypes.CDLL]:
    lib_path = os.path.join(_NATIVE_DIR, _LIB_NAME)
    if not os.path.exists(os.path.join(_NATIVE_DIR, "runtime.cpp")):
        return None
    digest = _src_digest()
    if _is_stale(lib_path, digest):
        # Serialize concurrent builds across processes (several workers can
        # land on one host): flock a sidecar, then re-check staleness — the
        # loser of the race finds a fresh .so and skips its own make.
        import fcntl

        lock_path = os.path.join(_NATIVE_DIR, ".build.lock")
        try:
            with open(lock_path, "w") as lock_f:
                fcntl.flock(lock_f, fcntl.LOCK_EX)
                if _is_stale(lib_path, digest):
                    # -B: make's own staleness rule is the mtimes this
                    # module no longer trusts.
                    subprocess.run(["make", "-B", "-C", _NATIVE_DIR],
                                   check=True, capture_output=True)
                    tmp = f"{lib_path}.srchash.tmp.{os.getpid()}"
                    with open(tmp, "w", encoding="ascii") as f:
                        f.write(digest + "\n")
                    os.replace(tmp, lib_path + ".srchash")
        except (subprocess.CalledProcessError, OSError) as e:
            # OSError covers missing make, unwritable or read-only
            # native/ dir (EROFS), etc. — all fall back to pure Python.
            err = getattr(e, "stderr", b"") or b""
            logging.warning("native runtime build failed (%s); using "
                            "pure-Python fallback. %s", e,
                            err.decode(errors="replace")[-500:])
            return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError as e:
        logging.warning("could not load %s: %s", lib_path, e)
        return None

    _bind_signatures(lib)
    return lib


def _bind_signatures(lib: ctypes.CDLL) -> None:
    lib.ad_buffer_alloc.restype = ctypes.c_void_p
    lib.ad_buffer_alloc.argtypes = [ctypes.c_size_t, ctypes.c_size_t]
    lib.ad_buffer_free.argtypes = [ctypes.c_void_p]
    lib.ad_fp32_to_bf16.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_size_t, ctypes.c_int]
    lib.ad_loader_create.restype = ctypes.c_void_p
    lib.ad_loader_create.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_size_t,
        ctypes.c_size_t, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
        ctypes.c_int, ctypes.c_int]
    lib.ad_loader_next.restype = ctypes.c_size_t
    lib.ad_loader_next.argtypes = [ctypes.c_void_p,
                                   ctypes.POINTER(ctypes.c_void_p)]
    lib.ad_loader_release.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(ctypes.c_void_p),
                                      ctypes.c_int]
    lib.ad_loader_num_batches.restype = ctypes.c_size_t
    lib.ad_loader_num_batches.argtypes = [ctypes.c_void_p]
    lib.ad_loader_destroy.argtypes = [ctypes.c_void_p]
    # _v2: the pretokenize flag changed the arity of ad_bpe_create.
    lib.ad_bpe_create_v2.restype = ctypes.c_void_p
    lib.ad_bpe_create_v2.argtypes = [ctypes.POINTER(ctypes.c_int32),
                                     ctypes.c_int32, ctypes.c_int32]
    lib.ad_bpe_encode.restype = ctypes.c_int32
    lib.ad_bpe_encode.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                  ctypes.c_int32,
                                  ctypes.POINTER(ctypes.c_int32)]
    lib.ad_bpe_destroy.argtypes = [ctypes.c_void_p]


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it if needed; None when
    unavailable (fallback mode)."""
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is None and not _build_failed:
            if os.environ.get("AUTODIST_NO_NATIVE"):
                _build_failed = True
            else:
                _lib = _build_and_load()
                if _lib is None:
                    _build_failed = True
    return _lib


def native_available() -> bool:
    return get_lib() is not None


def fp32_to_bf16(src: np.ndarray, num_threads: int = 4) -> np.ndarray:
    """Round-to-nearest-even fp32 → bfloat16 on the host.

    Returns an array of dtype ``ml_dtypes.bfloat16`` (numpy's jax-compatible
    bf16).  Native path is multi-threaded; fallback uses numpy."""
    import ml_dtypes

    src = np.ascontiguousarray(src, dtype=np.float32)
    lib = get_lib()
    if lib is None:
        return src.astype(ml_dtypes.bfloat16)  # numpy RNE cast
    out = np.empty(src.shape, dtype=np.uint16)
    lib.ad_fp32_to_bf16(src.ctypes.data_as(ctypes.c_void_p),
                        out.ctypes.data_as(ctypes.c_void_p),
                        src.size, num_threads)
    return out.view(ml_dtypes.bfloat16)


class NativeLoader:
    """Thin RAII wrapper over the C loader. One epoch per instance."""

    def __init__(self, arrays, batch_size: int, drop_last: bool,
                 shuffle: bool, seed: int, num_threads: int,
                 prefetch_depth: int, cast_bf16_flags):
        self._lib = get_lib()
        assert self._lib is not None
        self._arrays = [np.ascontiguousarray(a) for a in arrays]  # keep alive
        n = len(self._arrays)
        arr_ptrs = (ctypes.c_void_p * n)(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in self._arrays])
        row_bytes = (ctypes.c_size_t * n)(
            *[a.strides[0] for a in self._arrays])
        casts = (ctypes.c_int * n)(*[int(c) for c in cast_bf16_flags])
        self._handle = self._lib.ad_loader_create(
            arr_ptrs, row_bytes, casts, n, self._arrays[0].shape[0],
            batch_size, int(drop_last), int(shuffle), seed & (2**64 - 1),
            num_threads, prefetch_depth)
        if not self._handle:
            raise RuntimeError("ad_loader_create failed")
        self._n = n

    @property
    def num_batches(self) -> int:
        return self._lib.ad_loader_num_batches(self._handle)

    def next(self):
        """Returns (rows, ptrs) — ptrs must be passed to release(); rows == 0
        signals end of epoch."""
        ptrs = (ctypes.c_void_p * self._n)()
        rows = self._lib.ad_loader_next(self._handle, ptrs)
        return rows, ptrs

    def release(self, ptrs) -> None:
        self._lib.ad_loader_release(self._handle, ptrs, self._n)

    def close(self) -> None:
        if self._handle:
            self._lib.ad_loader_destroy(self._handle)
            self._handle = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass
