"""Loader for the native C++ runtime library (csrc/).

Reference: BigDL's native layer is the BigDL-core JNI wrapper shipping
`libjmkl.so` inside per-OS jars, loaded lazily on first use
(tensor/Tensor.scala:688 comment; MKL.isMKLLoaded, MKL.setNumThreads).  Here
the device math lives in XLA; the native library instead accelerates the
host-side runtime: CRC32C (hardware SSE4.2 when available), BDRecord file IO,
bf16 wire conversion, and batch-assembly kernels.

Pure-Python fallbacks exist for every entry point — the framework works
without the compiled library, just slower on the host paths.  The binary is
built from csrc/ (never committed): `Engine.init()` calls :func:`build` once,
and `is_native_loaded()` says which side a process is on.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

__all__ = ["lib", "crc32c", "crc32c_extend", "is_native_loaded", "build",
           "set_num_threads",
           "get_num_threads", "f32_to_bf16", "bf16_to_f32",
           "NativeRecordWriter", "NativeRecordReader",
           "NativePrefetchReader", "has_prefetch"]

_pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_csrc_dir = os.path.join(os.path.dirname(_pkg_dir), "csrc")
_candidates = [
    os.path.join(_pkg_dir, "lib", "libbigdl_tpu_native.so"),
    os.path.join(_csrc_dir, "build", "libbigdl_tpu_native.so"),
]

lib: Optional[ctypes.CDLL] = None
crc32c = None
crc32c_extend = None


def _bind(cdll: ctypes.CDLL) -> None:
    global crc32c, crc32c_extend
    cdll.bigdl_crc32c.restype = ctypes.c_uint32
    cdll.bigdl_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    cdll.bigdl_masked_crc32c.restype = ctypes.c_uint32
    cdll.bigdl_masked_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    if hasattr(cdll, "bigdl_crc32c_extend"):
        # optional (newer than the first shipped .so): the streaming
        # continuation used by the checkpoint framer; older binaries fall
        # back to the pure-Python loop in utils/recordio.py
        cdll.bigdl_crc32c_extend.restype = ctypes.c_uint32
        cdll.bigdl_crc32c_extend.argtypes = [
            ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t]

        def crc32c_extend(crc: int, data: bytes) -> int:  # noqa: F811
            return cdll.bigdl_crc32c_extend(crc, data, len(data))
    cdll.bigdl_record_writer_open.restype = ctypes.c_void_p
    cdll.bigdl_record_writer_open.argtypes = [ctypes.c_char_p]
    cdll.bigdl_record_writer_write.restype = ctypes.c_int
    cdll.bigdl_record_writer_write.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
    cdll.bigdl_record_writer_close.restype = ctypes.c_int
    cdll.bigdl_record_writer_close.argtypes = [ctypes.c_void_p]
    cdll.bigdl_record_reader_open.restype = ctypes.c_void_p
    cdll.bigdl_record_reader_open.argtypes = [ctypes.c_char_p]
    cdll.bigdl_record_reader_next.restype = ctypes.c_int64
    cdll.bigdl_record_reader_next.argtypes = [ctypes.c_void_p]
    cdll.bigdl_record_reader_data.restype = ctypes.c_void_p
    cdll.bigdl_record_reader_data.argtypes = [ctypes.c_void_p]
    cdll.bigdl_record_reader_close.restype = None
    cdll.bigdl_record_reader_close.argtypes = [ctypes.c_void_p]
    if hasattr(cdll, "bigdl_prefetch_open"):
        # optional (newer than the first shipped .so): an older binary
        # without these symbols must still provide crc32c/record IO/hostops
        cdll.bigdl_prefetch_open.restype = ctypes.c_void_p
        cdll.bigdl_prefetch_open.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64]
        cdll.bigdl_prefetch_next.restype = ctypes.c_int64
        cdll.bigdl_prefetch_next.argtypes = [ctypes.c_void_p]
        cdll.bigdl_prefetch_data.restype = ctypes.c_void_p
        cdll.bigdl_prefetch_data.argtypes = [ctypes.c_void_p]
        cdll.bigdl_prefetch_close.restype = None
        cdll.bigdl_prefetch_close.argtypes = [ctypes.c_void_p]
    cdll.bigdl_set_num_threads.restype = None
    cdll.bigdl_set_num_threads.argtypes = [ctypes.c_int]
    cdll.bigdl_get_num_threads.restype = ctypes.c_int
    cdll.bigdl_f32_to_bf16.restype = None
    cdll.bigdl_f32_to_bf16.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
    cdll.bigdl_bf16_to_f32.restype = None
    cdll.bigdl_bf16_to_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
    cdll.bigdl_gather_rows.restype = None
    cdll.bigdl_gather_rows.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p), ctypes.c_size_t,
        ctypes.c_size_t]
    cdll.bigdl_reduce_sum_f32.restype = None
    cdll.bigdl_reduce_sum_f32.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
        ctypes.c_size_t]

    def crc32c(data: bytes) -> int:  # noqa: F811
        return cdll.bigdl_crc32c(data, len(data))


def _is_fresh(so_path: str) -> bool:
    """True when `so_path` is at least as new as every source under csrc/
    (or there are no sources to compare with: an installed wheel).  A
    binary older than its sources was built from some other tree and is
    never loaded; :func:`build` replaces it."""
    if not os.path.isdir(_csrc_dir):
        return True
    built = os.path.getmtime(so_path)
    return all(os.path.getmtime(os.path.join(_csrc_dir, f)) <= built
               for f in os.listdir(_csrc_dir)
               if f.endswith((".cc", ".h")) or f == "Makefile")


def _try_load() -> None:
    global lib
    for _p in _candidates:
        if os.path.exists(_p) and _is_fresh(_p):
            try:
                cdll = ctypes.CDLL(_p)
                _bind(cdll)
                lib = cdll
                return
            except (OSError, AttributeError):
                lib = None


_try_load()
_build_failed = False


def build(quiet: bool = True) -> bool:
    """Compile csrc/ with make and load the result.  Returns True if the
    native library is loaded afterwards (reference analog: BigDL-core's
    Maven native build producing libjmkl.so).  `Engine.init()` calls this
    once per process, so a checkout made from git — which carries no
    binary — builds its own from the committed sources; an up-to-date
    binary is loaded at import and costs nothing here.  Concurrent
    processes (test workers, fleet workers) serialise on a lock file."""
    global _build_failed
    if lib is not None:
        return True
    if _build_failed or not os.path.isdir(_csrc_dir):
        return False
    import fcntl
    _build_failed = True  # until the load below says otherwise: a host with
    # no compiler must not pay for a failing make at every Engine.init
    try:
        os.makedirs(os.path.join(_csrc_dir, "build"), exist_ok=True)
        with open(os.path.join(_csrc_dir, "build", ".lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            subprocess.run(
                ["make", "-C", _csrc_dir, "-j"],
                check=True,
                stdout=subprocess.DEVNULL if quiet else None,
                stderr=subprocess.DEVNULL if quiet else None)
    except (OSError, subprocess.CalledProcessError):
        return False
    _try_load()
    _build_failed = lib is None
    return lib is not None


def is_native_loaded() -> bool:
    """(reference: MKL.isMKLLoaded)."""
    return lib is not None


def has_prefetch() -> bool:
    """True when the loaded .so exports the bigdl_prefetch_* symbols
    (optional: older binaries predate csrc/prefetch.cc)."""
    return lib is not None and hasattr(lib, "bigdl_prefetch_open")


def set_num_threads(n: int) -> None:
    """(reference: MKL.setNumThreads via Engine/ThreadPool.setMKLThread)."""
    if lib is not None:
        lib.bigdl_set_num_threads(n)


def get_num_threads() -> int:
    """(reference: MKL.getNumThreads)."""
    return lib.bigdl_get_num_threads() if lib is not None else 1


def f32_to_bf16(arr: np.ndarray) -> np.ndarray:
    """Round-to-nearest-even float32 -> bf16 (as uint16 payload).  Host-side
    wire/checkpoint compression (reference: FP16CompressedTensor truncation,
    parameters/FP16CompressedTensor.scala:271-279 — truncate-only; we round
    like the TPU hardware does)."""
    arr = np.ascontiguousarray(arr, dtype=np.float32)
    if lib is not None and arr.size:
        out = np.empty(arr.shape, dtype=np.uint16)
        lib.bigdl_f32_to_bf16(arr.ctypes.data, out.ctypes.data, arr.size)
        return out
    import ml_dtypes  # hard transitive dep of jax
    return arr.astype(ml_dtypes.bfloat16).view(np.uint16)


def bf16_to_f32(arr: np.ndarray) -> np.ndarray:
    arr = np.ascontiguousarray(arr, dtype=np.uint16)
    if lib is not None and arr.size:
        out = np.empty(arr.shape, dtype=np.float32)
        lib.bigdl_bf16_to_f32(arr.ctypes.data, out.ctypes.data, arr.size)
        return out
    import ml_dtypes
    return arr.view(ml_dtypes.bfloat16).astype(np.float32)


def gather_rows(rows) -> np.ndarray:
    """Stack equal-shape contiguous arrays into one batch array using the
    parallel native memcpy kernel (the batching half of
    MTLabeledBGRImgToBatch); np.stack fallback."""
    rows = [np.ascontiguousarray(r) for r in rows]
    if lib is None or not rows:
        return np.stack(rows) if rows else np.empty((0,))
    if any(r.shape != rows[0].shape or r.dtype != rows[0].dtype
           for r in rows[1:]):
        # heterogeneous rows: the native memcpy would read out of bounds;
        # np.stack keeps behavior identical with and without the library
        # (promoting dtypes, raising on shape mismatch)
        return np.stack(rows)
    out = np.empty((len(rows),) + rows[0].shape, dtype=rows[0].dtype)
    ptrs = (ctypes.c_void_p * len(rows))(
        *[r.ctypes.data for r in rows])
    lib.bigdl_gather_rows(out.ctypes.data, ptrs, rows[0].nbytes, len(rows))
    return out


def reduce_sum_f32(bufs) -> np.ndarray:
    """Elementwise sum of equal-shape float32 arrays via the parallel native
    kernel (host-side analog of the reference's gradient-sum loop,
    DistriOptimizer.scala:226-250); np.sum fallback."""
    bufs = [np.ascontiguousarray(b, dtype=np.float32) for b in bufs]
    if lib is None or not bufs:
        return np.sum(bufs, axis=0, dtype=np.float32)
    if any(b.shape != bufs[0].shape for b in bufs[1:]):
        raise ValueError("reduce_sum_f32 requires equal shapes")
    out = np.empty_like(bufs[0])
    ptrs = (ctypes.c_void_p * len(bufs))(*[b.ctypes.data for b in bufs])
    lib.bigdl_reduce_sum_f32(out.ctypes.data, ptrs, len(bufs), out.size)
    return out


class NativeRecordWriter:
    """Streaming BDRecord writer over the native handle."""

    def __init__(self, path: str):
        if lib is None:
            raise RuntimeError("native library not loaded")
        self._h = lib.bigdl_record_writer_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path!r} for writing")

    def write(self, payload: bytes) -> None:
        if lib.bigdl_record_writer_write(self._h, payload, len(payload)) != 0:
            raise IOError("record write failed")

    def close(self) -> None:
        if self._h:
            rc = lib.bigdl_record_writer_close(self._h)
            self._h = None
            if rc != 0:
                raise IOError("record writer close failed (flush error)")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NativeRecordReader:
    """Streaming BDRecord reader; iterate to get payload bytes."""

    def __init__(self, path: str):
        if lib is None:
            raise RuntimeError("native library not loaded")
        self._path = path
        self._h = lib.bigdl_record_reader_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path!r}")

    def __iter__(self):
        return self

    def __next__(self) -> bytes:
        if not self._h:  # use-after-close would hand C a NULL handle
            raise StopIteration
        n = lib.bigdl_record_reader_next(self._h)
        if n == -1:
            raise StopIteration
        if n < 0:
            # typed like the Python reader so callers match on ONE error;
            # non-resumable — the C reader's stream state is undefined
            # after a frame error (skip-budget reads use the Python path)
            from .recordio import CorruptRecord
            raise CorruptRecord(
                f"corrupt record (crc mismatch) in {self._path!r}",
                path=self._path, resumable=False)
        return ctypes.string_at(lib.bigdl_record_reader_data(self._h), n)

    def close(self) -> None:
        if self._h:
            lib.bigdl_record_reader_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NativePrefetchReader:
    """Multithreaded shard prefetcher (csrc/prefetch.cc): N C++ reader
    threads stream BDRecord shards into a bounded ring buffer; iterating
    yields payload bytes.  Record order interleaves across shards (the
    Spark-partition semantics of the reference's SeqFileFolder datasets);
    single consumer only."""

    def __init__(self, paths, num_threads: int = 4, capacity: int = 256):
        if not has_prefetch():
            raise RuntimeError("native library not loaded or too old "
                               "(no bigdl_prefetch_* symbols)")
        paths = [str(p) for p in paths]
        if not paths:
            raise ValueError("no shard paths")
        arr = (ctypes.c_char_p * len(paths))(
            *[p.encode() for p in paths])
        self._h = lib.bigdl_prefetch_open(arr, len(paths), num_threads,
                                          capacity)
        if not self._h:
            raise IOError(f"cannot open prefetcher over {len(paths)} shards")

    def __iter__(self):
        return self

    def __next__(self) -> bytes:
        if not self._h:  # use-after-close would hand C a NULL handle
            raise StopIteration
        n = lib.bigdl_prefetch_next(self._h)
        if n == -1:
            raise StopIteration
        if n < 0:
            raise IOError("prefetch: IO error or corrupt record")
        return ctypes.string_at(lib.bigdl_prefetch_data(self._h), n)

    def close(self) -> None:
        if self._h:
            lib.bigdl_prefetch_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
