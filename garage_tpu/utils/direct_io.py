"""O_DIRECT bulk file reads — the scrub/staging read path.

Why.  On this class of host (virtio disk, 1 CPU core) a BUFFERED
sequential read is kernel-CPU-bound, not device-bound: measured 0.2-0.3
GiB/s at ~92% CPU (the page-cache copy burns the core), while O_DIRECT
reads the same files at 2.9 GiB/s at ~7% CPU.  For the scrub pipeline
the difference is structural — with buffered reads, read and verify
cannot overlap on one core and sustained throughput collapses to the
harmonic mean of disk and codec (0.24 GiB/s in round 4); with O_DIRECT
the core belongs to the codec and sustained approaches the codec rate.

Bypassing the page cache is also the RIGHT semantic for scrub: a scrub
pass touches every block exactly once and must not evict the working
set the GET path depends on.  The reference's scrub reads buffered
(ref src/block/repair.rs:438-490 via block.rs read); this is a
deliberate improvement, not a parity item.

Alignment contract: O_DIRECT requires sector-aligned offsets, lengths
and destination buffers.  The destination is an anonymous mmap
(page-aligned — satisfies any sector size) sized to the file rounded up
to a page, read with ONE preadv per file from offset 0 (the kernel
splits internally; the short read at EOF may return an unaligned COUNT,
which POSIX/ext4 allow).  Data is copied out of the mmap exactly once.
Any OSError — O_DIRECT unsupported (tmpfs/overlay), mid-file EINVAL —
falls back to a buffered read of the remainder, so this is never less
available than open()/read().

Which of the two a read was, and where its time went, is not in what it
returns: a thread that wants to know installs a `ReadAccount`
(`accounting()`), and every `read_file_direct` it then makes adds its
stages and its mode there.  The scrub's I/O lane does, a slice at a
time (block/repair.py `_read_slice`); nothing else pays more than the
clock reads.

Many files at once: `read_files_native` does the same for a list of
paths inside native/directio.cpp, under the same contract and into the
same account.  Every system call of the per-file code is a return to
the interpreter and so a chance to lose its lock to another thread
(measured in a scrub pass: 1.2 ms for an `os.open` + `os.fstat`, longer
than the `preadv` of the file's MiB), and the copy out of the aligned
buffer is made with the lock held; the native calls drop the lock once
for all the files.  It is None where the library cannot be built: the
caller (block/health.py `DiskIo.read_files_direct`) then loops
`read_file_direct`.
"""

from __future__ import annotations

import contextlib
import mmap
import os
import threading
import time
from typing import Iterator, List, Optional, Sequence, Tuple, Union

_PAGE = 4096
_O_DIRECT = getattr(os, "O_DIRECT", 0)
_CHUNK = 32 << 20  # preadv request size: one huge request measured ~40%
                   # slower on first touch; ≥4 MiB requests are equal

# Per-thread destination buffer, grown geometrically and REUSED: the
# first O_DIRECT read into fresh anonymous pages pays ~65k page-pin
# faults per 256 MiB (~40% of the read time); warm pages make every
# subsequent read run at device speed.  Scrub worker threads read
# block-sized files repeatedly, so the cache converges immediately.
_local = threading.local()

READ_MODES = ("direct", "buffered")


class ReadAccount:
    """Where the `read_file_direct` calls of one thread put their time
    while it is installed, by consecutive `monotonic_ns` stamps inside
    the call: `open_ns` (`os.open` + `fstat` + the thread's buffer
    growing; a refused O_DIRECT open too), `pread_ns` (the `preadv`
    loop, or the whole buffered read where the open or a chunk fell
    back) and `copy_ns` (the one copy out of the aligned buffer).  The
    `close` between the last two is in none of them.  `files` and
    `bytes` count the reads that came back, by `READ_MODES`: `direct`,
    or `buffered` where the read fell back at the open or mid-file (or
    the platform has no O_DIRECT).  `native_calls` counts the
    `read_files_native` calls that added to it: their stages are stamped
    inside the native code, on the same clock."""

    __slots__ = ("open_ns", "pread_ns", "copy_ns", "files", "bytes",
                 "native_calls")

    def __init__(self):
        self.open_ns = self.pread_ns = self.copy_ns = self.native_calls = 0
        self.files = dict.fromkeys(READ_MODES, 0)
        self.bytes = dict.fromkeys(READ_MODES, 0)


@contextlib.contextmanager
def accounting() -> Iterator[ReadAccount]:
    """A fresh `ReadAccount` for the calling thread's reads inside the
    block.  It comes through whatever stands between the caller and
    `read_file_direct` (the manager's `DiskIo`, a `FaultyDisk`)."""
    acct = ReadAccount()
    prev = getattr(_local, "acct", None)
    _local.acct = acct
    try:
        yield acct
    finally:
        _local.acct = prev


def _dest(cap: int) -> mmap.mmap:
    buf = getattr(_local, "buf", None)
    if buf is None or len(buf) < cap:
        grow = max(cap, 2 * len(buf) if buf is not None else cap)
        buf = mmap.mmap(-1, grow)
        _local.buf = buf
    return buf


def _read_direct_raw(path: str) -> Optional[Tuple[memoryview, int, bool]]:
    """(view of a page-aligned per-thread buffer, valid byte count,
    whether every chunk came through O_DIRECT), or None if the open
    wants the buffered fallback.  The view is only valid until this
    THREAD's next _read_direct_raw call — callers copy out (once) before
    returning.  Stamps `open` and `pread` of the thread's account."""
    acct = getattr(_local, "acct", None)
    t0 = time.monotonic_ns()
    try:
        fd = os.open(path, os.O_RDONLY | _O_DIRECT)
    except OSError:
        if acct is not None:
            acct.open_ns += time.monotonic_ns() - t0
        return None
    try:
        size = os.fstat(fd).st_size
        cap = max((size + _PAGE - 1) & ~(_PAGE - 1), _PAGE)
        dest = _dest(cap)
        mv = memoryview(dest)
        t1 = time.monotonic_ns()
        direct = bool(_O_DIRECT)
        off = 0
        while off < size:
            try:
                n = os.preadv(fd, [mv[off:min(off + _CHUNK, cap)]], off)
            except OSError:
                # mid-file refusal: finish buffered into the same dest
                direct = False
                rest = _read_buffered_from(path, off, size - off)
                mv[off:off + len(rest)] = rest
                off += len(rest)
                break
            if n <= 0:
                break
            off += n
        if acct is not None:
            acct.open_ns += t1 - t0
            acct.pread_ns += time.monotonic_ns() - t1
        return mv, off, direct
    finally:
        os.close(fd)


def _read_buffered_from(path: str, offset: int, length: int) -> bytes:
    with open(path, "rb") as f:
        f.seek(offset)
        return f.read(length)


def read_file_direct(path: str) -> bytes:
    """Whole-file read via O_DIRECT with buffered fallback; exactly one
    copy out of the aligned buffer."""
    acct = getattr(_local, "acct", None)
    raw = _read_direct_raw(path)
    t0 = time.monotonic_ns()
    if raw is None:
        with open(path, "rb") as f:
            data = f.read()
        direct = False
        if acct is not None:
            acct.pread_ns += time.monotonic_ns() - t0
    else:
        mv, n, direct = raw
        data = bytes(mv[:n])
        if acct is not None:
            acct.copy_ns += time.monotonic_ns() - t0
    if acct is not None:
        mode = "direct" if direct else "buffered"
        acct.files[mode] += 1
        acct.bytes[mode] += len(data)
    return data


def read_files_native(
        paths: Sequence[str],
) -> Optional[Tuple[List[Union[bytes, OSError]], List[int]]]:
    """Whole-file reads of `paths` inside native/directio.cpp, with the
    interpreter's lock dropped for all of them at once → (one a path, in
    order: the bytes, or the `OSError` `read_file_direct` would have
    raised; the nanoseconds each spent in the call), or None where the
    library is not there.  Stages and modes go to the calling thread's
    account as `read_file_direct`'s do; a read that failed leaves the
    time it took and counts no file."""
    from ..ops.native import get_native_read_files

    native = get_native_read_files()
    if native is None:
        return None
    acct = getattr(_local, "acct", None)
    if acct is not None:
        acct.native_calls += 1
    results: List[Union[bytes, OSError]] = []
    spent: List[int] = []
    for path, (data, err, direct, open_ns, pread_ns, copy_ns, rest_ns) in zip(
            paths, native([os.fsencode(p) for p in paths], _O_DIRECT, _CHUNK)):
        spent.append(open_ns + pread_ns + copy_ns + rest_ns)
        if acct is not None:
            acct.open_ns += open_ns
            acct.pread_ns += pread_ns
            acct.copy_ns += copy_ns
        if err:
            results.append(OSError(err, os.strerror(err), path))
            continue
        if acct is not None:
            mode = "direct" if direct else "buffered"
            acct.files[mode] += 1
            acct.bytes[mode] += len(data)
        results.append(data)
    return results, spent


def read_file_direct_blocks(path: str, block_size: int) -> List[bytes]:
    """Read a file and split it into block_size pieces with one copy per
    block and NO intermediate whole-file bytes — the bulk-bench/staging
    shape (the production block store keeps one FILE per block and uses
    read_file_direct)."""
    raw = _read_direct_raw(path)
    if raw is None:
        with open(path, "rb") as f:
            data = f.read()
        return [data[i:i + block_size]
                for i in range(0, len(data), block_size)]
    mv, n, _direct = raw
    return [bytes(mv[i:min(i + block_size, n)])
            for i in range(0, n, block_size)]


def write_file_direct(path: str, data: bytes, fsync: bool = False) -> None:
    """Write a file via O_DIRECT for the sector-aligned prefix and a
    buffered write for the tail; falls back to a plain buffered write
    when O_DIRECT is unavailable.

    Why for block WRITES (the PutObject hot path): a buffered 1 MiB
    write costs ~0.4 ms of pure CPU in the page-cache copy and degrades
    to 7-8 ms under dirty-page throttling when puts are sustained,
    while the O_DIRECT write costs ~0.1 ms CPU with the transfer in
    kernel DMA (GIL released) — on a 1-core host, concurrent puts then
    overlap their writes instead of serializing on the copy.  It is
    also durability-positive: the aligned bulk is on media when the
    call returns, where the reference's data_fsync=false default leaves
    the whole block in cache (ref src/block/manager.rs:689-784).
    """
    n = len(data)
    aligned = n & ~(_PAGE - 1)
    flags = os.O_WRONLY | os.O_CREAT | os.O_TRUNC | _O_DIRECT
    fd = -1
    if aligned:
        try:
            fd = os.open(path, flags, 0o644)
        except OSError:
            fd = -1
    if fd >= 0:
        try:
            buf = _dest(aligned)
            mv = memoryview(buf)
            mv[:aligned] = data[:aligned]
            off = 0
            while off < aligned:
                w = os.pwritev(
                    fd, [mv[off:min(off + _CHUNK, aligned)]], off)
                if w <= 0:
                    raise OSError("short O_DIRECT write")
                off += w
        except OSError:
            os.close(fd)
            fd = -1  # fall through to the fully-buffered path
        else:
            os.close(fd)
            if aligned < n or fsync:
                with open(path, "r+b") as f:
                    f.seek(aligned)
                    f.write(data[aligned:])
                    if fsync:
                        f.flush()
                        os.fsync(f.fileno())
            return
    with open(path, "wb") as f:
        f.write(data)
        if fsync:
            f.flush()
            os.fsync(f.fileno())


def try_read_direct(path: str) -> Optional[bytes]:
    """read_file_direct with the scrub worker's error contract: a
    vanished/unreadable file is None, not an exception."""
    try:
        return read_file_direct(path)
    except OSError:
        return None
