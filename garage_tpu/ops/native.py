"""ctypes loaders for the native C++ kernels in garage_tpu/native/.

Three libraries are loaded here:
  - gf256.cpp      → libgf256.so      (AVX2 split-nibble GF(2^8) matmul)
  - blake2s_mb.cpp → libblake2smb.so  (multi-buffer BLAKE2s-256;
    16-lane AVX-512 / 8-lane AVX2, runtime-dispatched inside the kernel)
  - directio.cpp   → libdirectio.so   (whole-file O_DIRECT reads of many
    files in one call, for utils/direct_io.py `read_files_native`)

Resolved lazily on first use (not import — short CLI invocations must not
pay for a compiler run); a failed build is cached on disk against the
source mtime so it is not retried every process start.  Callers fall back
to numpy (GF) / hashlib (BLAKE2s) when a kernel is unavailable.
"""

from __future__ import annotations

import contextlib
import ctypes
import fcntl
import logging
import os
import subprocess
import threading
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger("garage_tpu.ops.native")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_BUILD_LOCK = threading.Lock()


@contextlib.contextmanager
def build_lock():
    """Serialize build-and-load of native/*.so across threads AND
    processes (flock on the native directory): test workers and daemons
    sharing one checkout must not run two `make` over the same file, nor
    dlopen one another's half-written output."""
    with _BUILD_LOCK:
        fd = os.open(_NATIVE_DIR, os.O_RDONLY)
        try:
            fcntl.flock(fd, fcntl.LOCK_EX)
            yield
        finally:
            os.close(fd)        # closing drops the flock


def _variant(so_name: str):
    """(file to load, make target, files that target writes) for a
    plain library name.  GARAGE_NATIVE_SUFFIX=.asan/.tsan selects the
    sanitizer-instrumented variants (run the tests under the matching
    LD_PRELOAD — see native/Makefile); those are built all four at once
    by the PHONY asan/tsan targets, the plain ones by per-.so rules."""
    suffix = os.environ.get("GARAGE_NATIVE_SUFFIX", "")
    if not suffix:
        return so_name, so_name, [so_name]
    return (so_name.replace(".so", f"{suffix}.so"), suffix.lstrip("."),
            [f"lib{n}{suffix}.so"
             for n in ("gf256", "logdb", "blake2smb", "directio")])


def make_so(so_name: str) -> str:
    """(Re)build native/<so_name> (its sanitizer variant when selected)
    under a temporary name, then rename into place: a reader never maps
    a file the linker is still writing.  Returns the path to load.
    Call under build_lock()."""
    load_name, make_target, written = _variant(so_name)
    tmp = f".tmp.{os.getpid()}"
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR, "-s", "-B", make_target,
             f"SO_SUFFIX={tmp}"],
            check=True, capture_output=True, timeout=300,
        )
        for name in written:
            os.replace(os.path.join(_NATIVE_DIR, name + tmp),
                       os.path.join(_NATIVE_DIR, name))
    finally:
        for name in written:
            try:
                os.unlink(os.path.join(_NATIVE_DIR, name + tmp))
            except OSError:
                pass
    return os.path.join(_NATIVE_DIR, load_name)


def _load_or_build(so_name: str, src_name: str) -> Optional[ctypes.CDLL]:
    """Load native/<so_name>, building it (make) if missing or stale.

    A load failure of an existing .so (e.g. a stale binary built on another
    host — the Makefile uses -march=native) triggers one clean rebuild.
    A failed build writes a marker keyed on the source mtime so this exact
    source is never re-attempted."""
    # sanitizer build failures must not poison the plain build's marker
    # (or vice versa)
    suffix = os.environ.get("GARAGE_NATIVE_SUFFIX", "")
    so_path = os.path.join(_NATIVE_DIR, _variant(so_name)[0])
    src_path = os.path.join(_NATIVE_DIR, src_name)
    fail_marker = os.path.join(_NATIVE_DIR,
                               f".build_failed_{src_name}{suffix}")
    with build_lock():
        src_mtime = os.path.getmtime(src_path)
        fresh = os.path.exists(so_path) and os.path.getmtime(so_path) >= src_mtime
        if fresh:
            try:
                return ctypes.CDLL(so_path)
            except OSError:
                pass  # stale/foreign binary: fall through to a clean rebuild
        if os.environ.get("GARAGE_TPU_NO_NATIVE_BUILD"):
            return None
        if os.path.exists(fail_marker) and os.path.getmtime(fail_marker) >= src_mtime:
            return None
        try:
            return ctypes.CDLL(make_so(so_name))
        except Exception as e:
            logger.debug("native %s build failed: %s", so_name, e)
            try:
                with open(fail_marker, "w") as f:
                    f.write(str(e))
            except OSError:
                pass
            return None


# --- GF(2^8) matmul ---

_gf_resolved = False
_gf_fn: Optional[Callable[[np.ndarray, np.ndarray], np.ndarray]] = None


def get_native_gf_matmul_blocks() -> Optional[Callable]:
    """The native GF kernel, or None (numpy fallback); builds on first call."""
    global _gf_resolved, _gf_fn
    if _gf_resolved:
        return _gf_fn
    _gf_resolved = True
    lib = _load_or_build("libgf256.so", "gf256.cpp")
    if lib is None:
        return None
    try:
        lib.gf_matmul_blocks.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.gf_matmul_blocks.restype = None
    except Exception as e:
        logger.debug("native gf256 symbol resolution failed: %s", e)
        return None

    def _ptr(a: np.ndarray):
        return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))

    def fn(mat: np.ndarray, shards: np.ndarray) -> np.ndarray:
        r, k = mat.shape
        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        lead = shards.shape[:-2]
        batch = int(np.prod(lead)) if lead else 1
        s = shards.shape[-1]
        assert shards.shape[-2] == k
        out = np.zeros(lead + (r, s), dtype=np.uint8)
        mat_c = np.ascontiguousarray(mat, dtype=np.uint8)
        lib.gf_matmul_blocks(_ptr(mat_c), _ptr(shards), _ptr(out), batch, r, k, s)
        return out

    _gf_fn = fn
    return _gf_fn


# --- GF(2^8) pointer-gather matmul (scrub/put encode hot path) ---

_gfp_resolved = False
_gfp_fn: Optional[Callable] = None


def get_native_gf_matmul_ptrs() -> Optional[Callable]:
    """fn(mat (r,k) uint8, buffers: Sequence[bytes], s) → (B, r, s) uint8,
    where consecutive groups of k buffers form one codeword, each
    zero-extended to width s.  len(buffers) must be a multiple of k.
    Only available when the GFNI kernel backs it (on AVX2-only hosts,
    packing + gf_matmul_blocks is faster than the scalar gather)."""
    global _gfp_resolved, _gfp_fn
    if _gfp_resolved:
        return _gfp_fn
    _gfp_resolved = True
    lib = _load_or_build("libgf256.so", "gf256.cpp")
    if lib is None:
        return None
    try:
        if not lib.gf_ptrs_fast():
            return None
        lib.gf_matmul_ptrs.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.gf_matmul_ptrs.restype = None
    except Exception as e:
        logger.debug("native gf_matmul_ptrs unavailable: %s", e)
        return None

    def fn(mat: np.ndarray, buffers: Sequence[bytes], s: int) -> np.ndarray:
        r, k = mat.shape
        n = len(buffers)
        assert n % k == 0, (n, k)
        B = n // k
        ptrs = (ctypes.c_char_p * n)(*buffers)
        lens = (ctypes.c_uint64 * n)(*[len(b) for b in buffers])
        out = np.zeros((B, r, s), dtype=np.uint8)
        mat_c = np.ascontiguousarray(mat, dtype=np.uint8)
        lib.gf_matmul_ptrs(
            mat_c.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), ptrs, lens,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), B, r, k, s)
        return out

    _gfp_fn = fn
    return _gfp_fn


# --- multi-buffer BLAKE2s-256 ---

_b2_resolved = False
_b2_fn: Optional[Callable[[Sequence[bytes]], List[bytes]]] = None


def get_native_blake2s_multi() -> Optional[Callable[[Sequence[bytes]], List[bytes]]]:
    """Batch BLAKE2s-256 over the multi-buffer SIMD kernel (16-lane
    AVX-512 or 8-lane AVX2, dispatched inside blake2s256_multi), or None
    (hashlib fallback).  Returns a callable blocks → [32-byte digests].

    The wrapper sorts the batch by length before dispatch: lanes in one
    SIMD group advance in lock-step, so grouping similar lengths minimises
    the work wasted on lanes that finish early (compressed blocks make
    lengths non-uniform).  Output order matches the input order."""
    global _b2_resolved, _b2_fn
    if _b2_resolved:
        return _b2_fn
    _b2_resolved = True
    lib = _load_or_build("libblake2smb.so", "blake2s_mb.cpp")
    if lib is None:
        return None
    try:
        if not lib.blake2s_mb_supported():
            return None  # prebuilt binary on a pre-AVX2 host
        lib.blake2s256_multi.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.blake2s256_multi.restype = None
    except Exception as e:
        logger.debug("native blake2s symbol resolution failed: %s", e)
        return None

    def fn(blocks: Sequence[bytes]) -> List[bytes]:
        n = len(blocks)
        if n == 0:
            return []
        order = sorted(range(n), key=lambda i: len(blocks[i]))
        ptrs = (ctypes.c_char_p * n)(*[blocks[i] for i in order])
        lens = (ctypes.c_uint64 * n)(*[len(blocks[i]) for i in order])
        out = (ctypes.c_uint8 * (32 * n))()
        lib.blake2s256_multi(ptrs, lens,
                             ctypes.cast(out, ctypes.c_void_p), n)
        raw = bytes(out)
        digests: List[bytes] = [b""] * n
        for pos, i in enumerate(order):
            digests[i] = raw[pos * 32:(pos + 1) * 32]
        return digests

    _b2_fn = fn
    return _b2_fn


_b2_rows_resolved = False
_b2_rows_fn: Optional[Callable] = None


def get_native_blake2s_rows() -> Optional[Callable]:
    """Strided in-place variant of the multi-buffer kernel: hash the
    first `n` rows of a C-contiguous uint8 matrix WITHOUT materializing
    per-row bytes copies — the lane pointers index straight into the
    (lane-aligned-stride) staging buffer where the rows already lie.
    This is the CPU-floor close of the SIMD-friendly staging layout:
    c_char_p only accepts bytes, so consuming a staging buffer through
    the plain wrapper costs one full copy pass per row.

    Returns fn(arr_2d, lengths, n) -> [32-byte digests], or None when
    the kernel is unavailable (callers fall back to hashlib over row
    views, which are zero-copy too, just not multi-buffer)."""
    global _b2_rows_resolved, _b2_rows_fn
    if _b2_rows_resolved:
        return _b2_rows_fn
    _b2_rows_resolved = True
    if get_native_blake2s_multi() is None:
        return None
    lib = _load_or_build("libblake2smb.so", "blake2s_mb.cpp")

    def fn(arr, lengths, n: int) -> List[bytes]:
        if n == 0:
            return []
        assert arr.dtype.itemsize == 1 and arr.flags["C_CONTIGUOUS"]
        stride = arr.strides[0]
        base = arr.ctypes.data
        order = sorted(range(n), key=lambda i: int(lengths[i]))
        ptrs = (ctypes.c_char_p * n)()
        lens = (ctypes.c_uint64 * n)()
        for pos, i in enumerate(order):
            ptrs[pos] = ctypes.cast(base + i * stride, ctypes.c_char_p)
            lens[pos] = int(lengths[i])
        out = (ctypes.c_uint8 * (32 * n))()
        lib.blake2s256_multi(ptrs, lens,
                             ctypes.cast(out, ctypes.c_void_p), n)
        raw = bytes(out)
        digests: List[bytes] = [b""] * n
        for pos, i in enumerate(order):
            digests[i] = raw[pos * 32:(pos + 1) * 32]
        return digests

    _b2_rows_fn = fn
    return _b2_rows_fn


# --- whole-file O_DIRECT reads of many files in one call ---


class _DioFile(ctypes.Structure):
    """`struct DioFile` of native/directio.cpp."""
    _fields_ = [(name, ctypes.c_int64) for name in (
        "size", "got", "open_ns", "pread_ns", "copy_ns", "rest_ns")] + [
        (name, ctypes.c_int32) for name in ("fd", "err", "mode", "pad_")]


# files a pair of native calls: what bounds the fds a thread holds open
# between its `dio_open` and its `dio_read`
DIO_GROUP = 64

_dio_resolved = False
_dio_fn: Optional[Callable] = None
# the lane's threads all ask at once, in a process's first batch: the
# ones that wait must get what the one that builds gets
_dio_lock = threading.Lock()


def get_native_read_files() -> Optional[Callable]:
    """fn(paths: Sequence[bytes], o_direct: int, chunk: int) → one tuple
    a path, in order: (the file's bytes or None, errno, whether every
    chunk came through O_DIRECT, ns of open + fstat, of the reads, of
    the copy out of the aligned buffer, of the close); None where the
    library cannot be built (the caller reads file by file in Python).

    Two native calls a group of `DIO_GROUP` paths, each with the
    interpreter's lock dropped (ctypes `CDLL`): open and fstat them all,
    then, once Python has made one `bytes` a file of its size, read,
    copy out and close them all.  The `bytes` are made uninitialised
    (`PyBytes_FromStringAndSize(NULL, n)`, as a C extension would) and
    filled by the native code before anything else can see them: no copy
    of a file's bytes is made under the lock."""
    global _dio_resolved, _dio_fn
    if not _dio_resolved:
        with _dio_lock:
            if not _dio_resolved:
                _dio_fn = _resolve_read_files()
                _dio_resolved = True
    return _dio_fn


def _resolve_read_files() -> Optional[Callable]:
    lib = _load_or_build("libdirectio.so", "directio.cpp")
    if lib is None:
        return None
    try:
        paths_t = ctypes.POINTER(ctypes.c_char_p)
        files_t = ctypes.POINTER(_DioFile)
        lib.dio_open.argtypes = [paths_t, ctypes.c_int64, ctypes.c_int32,
                                 files_t]
        lib.dio_read.argtypes = [paths_t, ctypes.c_int64, ctypes.c_int64,
                                 paths_t, files_t]
        lib.dio_close.argtypes = [ctypes.c_int64, files_t]
        lib.dio_open.restype = lib.dio_read.restype = None
        lib.dio_close.restype = None
        new_bytes = ctypes.PYFUNCTYPE(
            ctypes.py_object, ctypes.c_char_p, ctypes.c_ssize_t)(
                ("PyBytes_FromStringAndSize", ctypes.pythonapi))
    except Exception as e:
        logger.debug("native directio symbol resolution failed: %s", e)
        return None

    def group(paths: Sequence[bytes], o_direct: int, chunk: int) -> List[Tuple]:
        n = len(paths)
        c_paths = (ctypes.c_char_p * n)(*paths)
        files = (_DioFile * n)()
        lib.dio_open(c_paths, n, o_direct, files)
        try:
            # a failed open has size 0; b"" is shared and never written
            bufs = [new_bytes(None, f.size) if f.size else b""
                    for f in files]
            dest = (ctypes.c_char_p * n)(*bufs)
        except BaseException:
            lib.dio_close(n, files)
            raise
        lib.dio_read(c_paths, n, chunk, dest, files)
        return [
            (None if f.err else buf if f.got == f.size else buf[:f.got],
             f.err, f.mode == 0, f.open_ns, f.pread_ns, f.copy_ns, f.rest_ns)
            for f, buf in zip(files, bufs)]

    def fn(paths: Sequence[bytes], o_direct: int, chunk: int) -> List[Tuple]:
        return [r for lo in range(0, len(paths), DIO_GROUP)
                for r in group(paths[lo:lo + DIO_GROUP], o_direct, chunk)]

    return fn
