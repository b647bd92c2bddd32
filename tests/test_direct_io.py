"""O_DIRECT read path (utils/direct_io.py): correctness across
alignment edges and the buffered fallback — the scrub worker's
_try_read and the sustained bench both sit on this."""

import os

import numpy as np
import pytest

from garage_tpu.utils.direct_io import (read_file_direct,
                                        read_file_direct_blocks,
                                        try_read_direct)


@pytest.mark.parametrize("size", [0, 1, 17, 4095, 4096, 4097,
                                  (1 << 20) + 777, (4 << 20) + 1])
def test_read_file_direct_matches_buffered(tmp_path, size):
    rng = np.random.default_rng(size)
    data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    p = tmp_path / "f.bin"
    p.write_bytes(data)
    assert read_file_direct(str(p)) == data


def test_read_blocks_split_and_tail(tmp_path):
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, 3 * 4096 + 123, dtype=np.uint8).tobytes()
    p = tmp_path / "f.bin"
    p.write_bytes(data)
    blocks = read_file_direct_blocks(str(p), 4096)
    assert [len(b) for b in blocks] == [4096, 4096, 4096, 123]
    assert b"".join(blocks) == data


def test_missing_file_is_none(tmp_path):
    assert try_read_direct(str(tmp_path / "nope")) is None


@pytest.mark.parametrize("size,fsync", [(0, False), (123, False),
                                        (4096, True), (4097, False),
                                        ((1 << 20) + 777, True)])
def test_write_file_direct_roundtrip(tmp_path, size, fsync):
    from garage_tpu.utils.direct_io import write_file_direct

    data = os.urandom(size)
    p = tmp_path / "w.bin"
    write_file_direct(str(p), data, fsync=fsync)
    assert p.read_bytes() == data
    # overwrite with a SHORTER payload must not leave stale bytes
    shorter = os.urandom(max(size // 2, 1))
    write_file_direct(str(p), shorter)
    assert p.read_bytes() == shorter


def test_thread_buffer_reuse_isolated(tmp_path):
    # the per-thread buffer is reused across reads: the bytes returned
    # by an earlier read must not be clobbered by a later one
    a = os.urandom(2 << 20)
    b = os.urandom(1 << 20)
    pa, pb = tmp_path / "a", tmp_path / "b"
    pa.write_bytes(a)
    pb.write_bytes(b)
    got_a = read_file_direct(str(pa))
    got_b = read_file_direct(str(pb))
    assert got_a == a and got_b == b


# --- many files in one native call (native/directio.cpp, ISSUE 42) -----------

SIZES = [0, 1, 17, 4095, 4096, 4097, (1 << 20) + 777, (4 << 20) + 1]


def _files(tmp_path, sizes=SIZES):
    rng = np.random.default_rng(42)
    paths, datas = [], []
    for i, size in enumerate(sizes):
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        p = tmp_path / f"f{i}.bin"
        p.write_bytes(data)
        paths.append(str(p))
        datas.append(data)
    return paths, datas


def _open_fds() -> int:
    return len(os.listdir("/proc/self/fd"))


@pytest.mark.parametrize("chunk", [4096, 1 << 20, 32 << 20])
def test_read_files_native_matches_the_per_file_reads(tmp_path, monkeypatch,
                                                     chunk):
    from garage_tpu.utils import direct_io

    monkeypatch.setattr(direct_io, "_CHUNK", chunk)
    paths, datas = _files(tmp_path)
    paths.insert(3, str(tmp_path / "nope"))
    before = _open_fds()
    with direct_io.accounting() as one_by_one:
        expected = [try_read_direct(p) for p in paths]
    with direct_io.accounting() as acct:
        results, spent = direct_io.read_files_native(paths)
    assert _open_fds() == before
    assert expected[3] is None and isinstance(results[3], FileNotFoundError)
    assert results[3].filename == paths[3]
    assert results[:3] + results[4:] == datas == expected[:3] + expected[4:]
    assert all(type(r) is bytes for r in results[:3] + results[4:])
    # same modes, same byte counts, and every stage stamped
    assert acct.files == one_by_one.files and acct.bytes == one_by_one.bytes
    assert sum(acct.bytes.values()) == sum(SIZES)
    assert acct.native_calls == 1 and one_by_one.native_calls == 0
    assert len(spent) == len(paths) and all(ns > 0 for ns in spent)
    assert 0 < acct.open_ns + acct.pread_ns + acct.copy_ns <= sum(spent)


def test_read_files_native_reads_more_paths_than_a_group(tmp_path):
    from garage_tpu.ops.native import DIO_GROUP
    from garage_tpu.utils import direct_io

    n = 2 * DIO_GROUP + 3
    paths, datas = _files(tmp_path, [100 + i for i in range(n)])
    before = _open_fds()
    results, spent = direct_io.read_files_native(paths)
    assert results == datas and len(spent) == n
    assert _open_fds() == before
    assert direct_io.read_files_native([]) == ([], [])


def test_without_the_library_there_is_no_native_read(tmp_path, monkeypatch):
    from garage_tpu.block.health import DiskIo
    from garage_tpu.ops import native
    from garage_tpu.utils import direct_io

    paths, datas = _files(tmp_path, [0, 5000])
    paths.append(str(tmp_path / "nope"))
    disk = DiskIo()
    disk.root_of = lambda path: str(tmp_path)
    got = disk.read_files_direct(paths)
    assert got[:2] == datas and isinstance(got[2], FileNotFoundError)
    once = disk.busy_seconds[str(tmp_path)]
    assert once > 0 and list(disk.busy_seconds) == [str(tmp_path)]

    monkeypatch.setattr(native, "get_native_read_files", lambda: None)
    assert direct_io.read_files_native(paths) is None
    singles = []
    real = disk.read_file_direct
    monkeypatch.setattr(disk, "read_file_direct",
                        lambda p: (singles.append(p), real(p))[1])
    got = disk.read_files_direct(paths)
    assert got[:2] == datas and isinstance(got[2], FileNotFoundError)
    assert singles == paths
    assert disk.busy_seconds[str(tmp_path)] > once


def test_native_reads_from_many_threads_keep_their_buffers_apart(tmp_path):
    """Each thread's aligned buffer is its own: reads of different files
    on threads that run into one another give each its own bytes."""
    import sys
    import threading

    from garage_tpu.utils import direct_io

    paths, datas = _files(tmp_path, [(64 << 10) + 13 * i for i in range(24)])
    wrong = []

    def reader(k):
        order = paths[k:] + paths[:k]
        want = datas[k:] + datas[:k]
        for _ in range(10):
            got, _spent = direct_io.read_files_native(order)
            if got != want:
                wrong.append(k)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader, args=(k,))
                   for k in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and wrong == []
