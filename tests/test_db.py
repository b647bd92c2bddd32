"""Shared DB engine conformance suite, run against every engine.

Mirrors the reference's pattern of one `test_suite(db)` applied to all
engines (ref src/db/test.rs:1-111).
"""

import threading

import pytest

from garage_tpu.db import TxAbort, open_db
from garage_tpu.db.counted_tree import CountedTree


@pytest.fixture(params=["memory", "memory-durable", "sqlite", "native"])
def db(request, tmp_path):
    if request.param == "sqlite":
        d = open_db("sqlite", str(tmp_path / "db.sqlite"))
    elif request.param == "native":
        d = open_db("native", str(tmp_path / "db.logdb"))
    elif request.param == "memory-durable":
        d = open_db("memory", str(tmp_path / "db.mem"))
    else:
        d = open_db("memory")
    yield d
    d.close()


def test_memory_durable_survives_reopen(tmp_path):
    # snapshot + WAL: committed state must be identical after close +
    # reopen, including tree-id assignment and transactional groups
    p = str(tmp_path / "db.mem")
    d = open_db("memory", p)
    ta, tb = d.open_tree("a"), d.open_tree("b")
    ta.insert(b"k1", b"v1")
    tb.insert(b"k2", b"v2")

    def tx_ops(tx):
        tx.insert(d.open_tree("a"), b"k3", b"v3")
        tx.remove(d.open_tree("b"), b"k2")

    d.transaction(tx_ops)
    # force a snapshot cycle, then more WAL on top of it
    d.backend._write_snapshot()
    ta.insert(b"k4", b"v4")
    d.close()

    d2 = open_db("memory", p)
    a2, b2 = d2.open_tree("a"), d2.open_tree("b")
    assert a2.get(b"k1") == b"v1"
    assert a2.get(b"k3") == b"v3"
    assert a2.get(b"k4") == b"v4"
    assert b2.get(b"k2") is None
    assert len(b2) == 0 and len(a2) == 3
    assert sorted(d2.list_trees()) == ["a", "b"]
    d2.close()


def test_get_insert_remove(db):
    t = db.open_tree("t")
    assert t.get(b"k") is None
    assert t.insert(b"k", b"v1") is None
    assert t.get(b"k") == b"v1"
    assert t.insert(b"k", b"v2") == b"v1"
    assert t.get(b"k") == b"v2"
    assert len(t) == 1
    assert t.remove(b"k") == b"v2"
    assert t.remove(b"k") is None
    assert len(t) == 0 and t.is_empty()


def test_ordered_iteration_and_range(db):
    t = db.open_tree("t")
    keys = [bytes([i]) for i in (5, 1, 9, 3, 7)]
    for k in keys:
        t.insert(k, k * 2)
    assert [k for k, _ in t.items()] == sorted(keys)
    assert [k for k, _ in t.items_rev()] == sorted(keys, reverse=True)
    assert [k for k, _ in t.items(bytes([3]), bytes([8]))] == [
        bytes([3]), bytes([5]), bytes([7])
    ]
    assert t.first() == (bytes([1]), bytes([1, 1]))
    assert t.get_gt(bytes([5])) == (bytes([7]), bytes([7, 7]))
    assert t.get_gt(bytes([9])) is None


def test_multiple_trees_independent(db):
    a, b = db.open_tree("a"), db.open_tree("b")
    a.insert(b"k", b"va")
    b.insert(b"k", b"vb")
    assert a.get(b"k") == b"va" and b.get(b"k") == b"vb"
    assert set(db.list_trees()) >= {"a", "b"}
    assert db.open_tree("a") is a


def test_transaction_commit(db):
    t = db.open_tree("t")
    t.insert(b"a", b"1")
    fired = []

    def txf(tx):
        assert tx.get(t, b"a") == b"1"
        tx.insert(t, b"b", b"2")
        assert tx.get(t, b"b") == b"2"
        tx.remove(t, b"a")
        tx.on_commit(lambda: fired.append(True))
        return "done"

    assert db.transaction(txf) == "done"
    assert t.get(b"a") is None and t.get(b"b") == b"2"
    assert fired == [True]


def test_transaction_abort_rolls_back(db):
    t = db.open_tree("t")
    t.insert(b"a", b"1")
    fired = []

    def txf(tx):
        tx.insert(t, b"a", b"overwritten")
        tx.insert(t, b"b", b"2")
        tx.remove(t, b"a")
        tx.on_commit(lambda: fired.append(True))
        raise TxAbort("aborted-value")

    assert db.transaction(txf) == "aborted-value"
    assert t.get(b"a") == b"1"
    assert t.get(b"b") is None
    assert fired == []


def test_transaction_exception_rolls_back_and_raises(db):
    t = db.open_tree("t")

    def txf(tx):
        tx.insert(t, b"x", b"1")
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError):
        db.transaction(txf)
    assert t.get(b"x") is None


def test_transaction_iter(db):
    t = db.open_tree("t")
    for i in range(5):
        t.insert(bytes([i]), bytes([i]))

    def txf(tx):
        return [k for k, _ in tx.iter_range(t, bytes([1]), bytes([4]))]

    assert db.transaction(txf) == [bytes([1]), bytes([2]), bytes([3])]


def test_iteration_survives_concurrent_mutation(db):
    t = db.open_tree("t")
    for i in range(100):
        t.insert(i.to_bytes(2, "big"), b"v")
    seen = []
    for k, _ in t.items():
        seen.append(k)
        if len(seen) == 50:
            t.remove((99).to_bytes(2, "big"))
            t.insert((300).to_bytes(2, "big"), b"new")
    assert len(seen) >= 99


def test_threaded_writes(db):
    t = db.open_tree("t")

    def writer(base):
        for i in range(50):
            t.insert((base + i).to_bytes(4, "big"), b"v")

    threads = [threading.Thread(target=writer, args=(n * 1000,)) for n in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(t) == 200


def test_counted_tree(db):
    t = db.open_tree("t")
    t.insert(b"pre", b"1")
    ct = CountedTree(t)
    assert len(ct) == 1
    ct.insert(b"a", b"1")
    ct.insert(b"a", b"2")  # overwrite: count unchanged
    assert len(ct) == 2
    ct.remove(b"a")
    ct.remove(b"a")
    assert len(ct) == 1 and not ct.is_empty()


def test_sqlite_snapshot(tmp_path):
    d = open_db("sqlite", str(tmp_path / "db.sqlite"))
    t = d.open_tree("t")
    t.insert(b"k", b"v")
    d.snapshot(str(tmp_path / "snap.sqlite"))
    d.close()
    d2 = open_db("sqlite", str(tmp_path / "snap.sqlite"))
    assert d2.open_tree("t").get(b"k") == b"v"
    d2.close()


# --- native engine specifics (logdb.cpp) -----------------------------------


def test_native_durability_across_reopen(tmp_path):
    p = str(tmp_path / "db.logdb")
    d = open_db("native", p)
    t = d.open_tree("t")
    for i in range(100):
        t.insert(i.to_bytes(4, "big"), b"val%d" % i)
    t.remove((7).to_bytes(4, "big"))
    d.transaction(lambda tx: (
        tx.insert(t, b"txk", b"txv"), tx.remove(t, (8).to_bytes(4, "big"))
    ))
    d.close()

    d2 = open_db("native", p)
    t2 = d2.open_tree("t")
    assert len(t2) == 99  # 100 - 2 removed + 1 tx insert
    assert t2.get((7).to_bytes(4, "big")) is None
    assert t2.get((8).to_bytes(4, "big")) is None
    assert t2.get(b"txk") == b"txv"
    assert t2.get((42).to_bytes(4, "big")) == b"val42"
    d2.close()


def test_native_torn_write_recovery(tmp_path):
    """A torn (partial) trailing group must be invisible after reopen —
    recovery truncates to the last commit record."""
    p = str(tmp_path / "db.logdb")
    d = open_db("native", p)
    t = d.open_tree("t")
    t.insert(b"good", b"committed")
    d.close()

    import struct

    with open(p, "ab") as f:
        # a valid-looking PUT record with correct CRC but NO commit after it
        body = struct.pack("<BIII", 1, 0, 4, 4) + b"torn" + b"torn"
        import zlib

        f.write(struct.pack("<I", zlib.crc32(body)) + body)
        # plus some garbage
        f.write(b"\xde\xad\xbe\xef")

    d2 = open_db("native", p)
    t2 = d2.open_tree("t")
    assert t2.get(b"good") == b"committed"
    assert t2.get(b"torn") is None
    # the file was truncated back; new writes go to the clean tail
    t2.insert(b"after", b"recovery")
    d2.close()
    d3 = open_db("native", p)
    assert d3.open_tree("t").get(b"after") == b"recovery"
    d3.close()


def test_native_compaction_preserves_data(tmp_path):
    import os

    p = str(tmp_path / "db.logdb")
    d = open_db("native", p)
    t = d.open_tree("t")
    # churn: many overwrites → mostly-dead log
    for round_ in range(20):
        for i in range(50):
            t.insert(i.to_bytes(4, "big"), os.urandom(500))
    before = os.path.getsize(p)
    d.backend.compact()
    after = os.path.getsize(p)
    assert after < before / 3
    assert len(t) == 50
    vals = dict(t.items())
    d.close()
    d2 = open_db("native", p)
    assert dict(d2.open_tree("t").items()) == vals
    d2.close()


def test_native_snapshot(tmp_path):
    p = str(tmp_path / "db.logdb")
    d = open_db("native", p)
    t = d.open_tree("t")
    t.insert(b"k", b"v")
    d.snapshot(str(tmp_path / "snap.logdb"))
    t.insert(b"k2", b"after-snapshot")
    d.close()
    d2 = open_db("native", str(tmp_path / "snap.logdb"))
    t2 = d2.open_tree("t")
    assert t2.get(b"k") == b"v" and t2.get(b"k2") is None
    d2.close()


def test_convert_db_preserves_garage_state(tmp_path):
    """convert-db sqlite→native: a node's full metadata survives the
    engine swap (ref cli/convert_db.rs)."""
    import subprocess
    import sys

    sqlite_p = str(tmp_path / "db.sqlite")
    native_p = str(tmp_path / "db.logdb")
    d = open_db("sqlite", sqlite_p)
    trees = {}
    for name in ("object:table", "bucket_v2:table", "key:table",
                 "block_local_rc"):
        t = d.open_tree(name)
        trees[name] = {}
        for i in range(25):
            k = b"%s-%d" % (name.encode(), i)
            v = b"payload-%d" % i * 3
            t.insert(k, v)
            trees[name][k] = v
    d.close()

    r = subprocess.run(
        [sys.executable, "-m", "garage_tpu", "convert-db",
         "-i", sqlite_p, "-a", "sqlite", "-o", native_p, "-b", "native"],
        capture_output=True, text=True, cwd="/root/repo",
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
        timeout=60,
    )
    assert r.returncode == 0, r.stderr
    assert "4 trees / 100 rows" in r.stdout

    d2 = open_db("native", native_p)
    for name, kv in trees.items():
        assert dict(d2.open_tree(name).items()) == kv
    d2.close()

    # refuse to overwrite non-empty output
    r2 = subprocess.run(
        [sys.executable, "-m", "garage_tpu", "convert-db",
         "-i", sqlite_p, "-a", "sqlite", "-o", native_p, "-b", "native"],
        capture_output=True, text=True, cwd="/root/repo",
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
        timeout=60,
    )
    assert r2.returncode == 1 and "not empty" in r2.stderr


def test_native_runtime_compaction_bounds_log(tmp_path):
    """Churn past the dead-bytes threshold must trigger compaction during
    normal writes, not only at reopen."""
    import os

    p = str(tmp_path / "db.logdb")
    d = open_db("native", p)
    t = d.open_tree("t")
    val = os.urandom(4096)
    # ~40 MiB of overwrites of the same 64 keys (live ≈ 256 KiB)
    for _ in range(160):
        for i in range(64):
            t.insert(i.to_bytes(4, "big"), val)
    size = os.path.getsize(p)
    assert size < 8 * (1 << 20), f"log grew unbounded: {size}"
    assert len(t) == 64
    d.close()


# --- memory-db WAL recovery diagnostics + snapshot durability ---


def _mem_wal_path(p):
    import os

    return os.path.join(p, "wal.log")


def test_memory_wal_torn_tail_warns(tmp_path, caplog):
    """A short final record (the expected kill -9 shape) must log a
    WARNING naming the truncated byte count — not truncate silently."""
    import logging
    import struct

    p = str(tmp_path / "db.mem")
    d = open_db("memory", p)
    t = d.open_tree("t")
    t.insert(b"k1", b"v1")
    d.close()
    # append a torn record: a full header promising more bytes than exist
    with open(_mem_wal_path(p), "ab") as f:
        f.write(struct.pack("<II", 1000, 0) + b"short")
    with caplog.at_level(logging.WARNING, logger="garage_tpu.db.memory"):
        d2 = open_db("memory", p)
    msgs = [r.getMessage() for r in caplog.records
            if r.name == "garage_tpu.db.memory"]
    assert any("torn tail" in m and "13" in m for m in msgs), msgs
    assert not any("ACKNOWLEDGED" in m for m in msgs)
    assert d2.open_tree("t").get(b"k1") == b"v1"
    d2.close()
    # the tail was truncated: a further clean reopen logs nothing
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="garage_tpu.db.memory"):
        d3 = open_db("memory", p)
    assert not [r for r in caplog.records
                if r.name == "garage_tpu.db.memory"]
    d3.close()


def test_memory_wal_midfile_corruption_logs_error(tmp_path, caplog):
    """A mid-file CRC mismatch FOLLOWED by parseable records is media
    corruption eating acknowledged commits — it must log an ERROR
    distinguishing it from a torn tail."""
    import logging
    import struct

    p = str(tmp_path / "db.mem")
    d = open_db("memory", p)
    t = d.open_tree("t")
    t.insert(b"k1", b"v1")
    t.insert(b"k2", b"v2")
    t.insert(b"k3", b"v3")
    d.close()
    wal = _mem_wal_path(p)
    with open(wal, "rb") as f:
        raw = f.read()
    # records: [open_tree t][insert k1][insert k2][insert k3] — walk the
    # framing to find the insert-k2 record, then corrupt its body so the
    # insert-k3 record stays parseable after it
    offs = []
    off = 8  # magic
    while off + 8 <= len(raw):
        blen, _crc = struct.unpack_from("<II", raw, off)
        offs.append((off, blen))
        off += 8 + blen
    assert len(offs) == 4, offs
    off_k2, blen_k2 = offs[2]
    body_pos = off_k2 + 8 + blen_k2 // 2
    raw = raw[:body_pos] + bytes([raw[body_pos] ^ 0xFF]) + raw[body_pos + 1:]
    with open(wal, "wb") as f:
        f.write(raw)
    with caplog.at_level(logging.WARNING, logger="garage_tpu.db.memory"):
        d2 = open_db("memory", p)
    msgs = [r.getMessage() for r in caplog.records
            if r.name == "garage_tpu.db.memory"
            and r.levelno >= logging.ERROR]
    assert any("ACKNOWLEDGED" in m and "1 parseable" in m for m in msgs), \
        [r.getMessage() for r in caplog.records]
    t2 = d2.open_tree("t")
    # only the records before the corruption replayed: k1 survives,
    # k2 (corrupt) and k3 (after the corruption) are gone
    assert t2.get(b"k1") == b"v1"
    assert t2.get(b"k2") is None and t2.get(b"k3") is None
    d2.close()


def test_memory_snapshot_fsyncs_and_is_loadable(tmp_path, monkeypatch):
    """snapshot() must fsync the copied snapshot, the stub WAL and the
    destination directory before returning (mirroring _write_snapshot),
    and the result must open as a valid db."""
    import os

    fsyncs = []
    real_fsync = os.fsync

    def counting_fsync(fd):
        fsyncs.append(fd)
        return real_fsync(fd)

    p = str(tmp_path / "db.mem")
    d = open_db("memory", p)
    t = d.open_tree("t")
    t.insert(b"k", b"v")
    dest = str(tmp_path / "snap.mem")
    monkeypatch.setattr(os, "fsync", counting_fsync)
    n0 = len(fsyncs)
    d.snapshot(dest)
    monkeypatch.setattr(os, "fsync", real_fsync)
    # _write_snapshot itself fsyncs (tmp file, dir, wal reset) — the
    # copy-out adds at least 3 more: dst snap, dst wal stub, dst dir
    assert len(fsyncs) - n0 >= 6, f"only {len(fsyncs) - n0} fsyncs"
    t.insert(b"k2", b"after-snapshot")
    d.close()
    d2 = open_db("memory", dest)
    t2 = d2.open_tree("t")
    assert t2.get(b"k") == b"v" and t2.get(b"k2") is None
    d2.close()
