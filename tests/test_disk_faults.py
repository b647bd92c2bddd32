"""Disk-fault robustness: the per-root health state machine, FaultyDisk
injection at the manager's filesystem boundary, the self-healing read
path, crash-consistent startup (janitor + kill-mid-write torture), and
the disk metric families.

The chaos proof (3-node cluster, one flaky-disk + ENOSPC node, zero
client-visible errors, disk_root_state observed degrading and
recovering) lives here marked `slow`; the standalone equivalent is
`scripts/chaos.py --phases disk` (run by scripts/test_smoke.sh)."""

import asyncio
import errno
import os

import pytest

from garage_tpu.block import DataBlock
from garage_tpu.block.health import (
    DISK_STATE_VALUES,
    DiskHealthMonitor,
    janitor_pass,
)
from garage_tpu.testing.faults import FaultyDisk, SimulatedCrash
from garage_tpu.utils.data import blake2s_sum
from garage_tpu.utils.error import (
    NoSuchBlock,
    StorageError,
    StorageFull,
    error_code,
    remote_error,
)

from tests.test_block import make_block_cluster
from tests.test_table import shutdown

pytestmark = pytest.mark.asyncio


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _mk_monitor(free=10_000, watermark=100, threshold=3, cooldown=10.0):
    """Monitor over one fake root with a controllable statvfs."""
    state = {"free": free, "err": None}

    def statvfs(path):
        if state["err"] is not None:
            raise state["err"]
        from types import SimpleNamespace

        return SimpleNamespace(f_bavail=state["free"], f_frsize=1)

    clock = FakeClock()
    mon = DiskHealthMonitor(
        ["/r"], watermark=watermark, error_threshold=threshold,
        cooldown=cooldown, statvfs=statvfs, clock=clock,
    )
    mon.cache_ttl = 0.0  # fake clock never advances between calls
    return mon, state, clock


# --- DiskHealthMonitor state machine (pure, fake clock) ---


def test_health_watermark_flips_readonly_and_recovers():
    mon, state, _clock = _mk_monitor(free=10_000, watermark=100)
    assert mon.state("/r") == "ok"
    mon.check_writable("/r", need_bytes=0)  # passes
    # free space under the watermark: read-only, typed StorageFull
    state["free"] = 50
    assert mon.state("/r") == "degraded"
    with pytest.raises(StorageFull):
        mon.check_writable("/r")
    # enough free space overall but not for THIS write
    state["free"] = 150
    with pytest.raises(StorageFull):
        mon.check_writable("/r", need_bytes=100)
    mon.check_writable("/r", need_bytes=10)
    # space recovers → ok again, no streak involved
    state["free"] = 10_000
    assert mon.state("/r") == "ok"


def test_health_statvfs_failure_counts_as_space_low():
    mon, state, _clock = _mk_monitor()
    state["err"] = OSError(errno.EIO, "io")
    assert mon.free_bytes("/r", fresh=True) is None
    assert mon.state("/r") == "degraded"
    with pytest.raises(StorageFull):
        mon.check_writable("/r")


def test_health_error_streak_degrades_then_half_open_recovers():
    mon, _state, clock = _mk_monitor(threshold=3, cooldown=10.0)
    for _ in range(3):
        mon.note_error("/r", "write", OSError(errno.EIO, "io"))
        clock.advance(1.0)
    assert mon.state("/r") == "degraded"
    with pytest.raises(StorageError):
        mon.check_writable("/r")
    assert not mon.writable("/r")
    # cooldown elapses → ONE half-open probe write is admitted
    clock.advance(10.1)
    mon.check_writable("/r")          # consumes the probe slot
    with pytest.raises(StorageError):
        mon.check_writable("/r")      # second concurrent write refused
    mon.note_ok("/r", "write")        # probe succeeded
    assert mon.state("/r") == "ok"
    mon.check_writable("/r")
    # errno-kind accounting for disk_error_total{op,kind}
    assert mon.error_counts[("write", "EIO")] == 3


def test_health_failed_latch_refuses_probe_until_success():
    mon, _state, clock = _mk_monitor(threshold=2, cooldown=1.0)
    for _ in range(8):  # 2 × DISK_FAILED_FACTOR
        mon.note_error("/r", "read", OSError(errno.EIO, "io"))
        clock.advance(1.0)
    assert mon.state("/r") == "failed"
    clock.advance(100.0)  # no cooldown walks a FAILED root back
    with pytest.raises(StorageError):
        mon.check_writable("/r")
    # only a successful op (reads still run) resets the streak
    mon.note_ok("/r", "read")
    assert mon.state("/r") == "ok"
    assert DISK_STATE_VALUES["failed"] == 2.0


def test_health_write_enospc_never_feeds_streak():
    """Full is not broken: a write-time ENOSPC the watermark missed
    (quota, reserved blocks) marks the root space-low for one cache
    TTL but must never feed the streak/breaker — a merely-full disk
    would otherwise walk itself to a latched FAILED within minutes."""
    mon, state, clock = _mk_monitor(threshold=2, cooldown=10.0)
    mon.cache_ttl = 5.0
    for _ in range(100):  # way past threshold × DISK_FAILED_FACTOR
        mon.note_error("/r", "write", OSError(errno.ENOSPC, "full"))
    assert mon.error_counts[("write", "ENOSPC")] == 100
    # space-low (typed StorageFull), NOT an error-streak degrade
    assert mon.state("/r") == "degraded"
    with pytest.raises(StorageFull):
        mon.check_writable("/r")
    assert not mon.writable("/r")
    # the TTL expires, statvfs shows space: instant recovery, no
    # cooldown, no probe — the streak never moved
    clock.advance(5.1)
    assert mon.state("/r") == "ok"
    mon.check_writable("/r")


def test_health_enospc_probe_failure_frees_the_slot():
    """A half-open probe write that fails with real ENOSPC is a verdict
    about space, not the streak: the probe slot must be released, or
    the root stays un-probeable (StorageError on every write) for a
    full extra cooldown after space recovers."""
    mon, _state, clock = _mk_monitor(threshold=2, cooldown=10.0)
    for _ in range(2):
        mon.note_error("/r", "write", OSError(errno.EIO, "io"))
        clock.advance(1.0)
    clock.advance(10.1)
    mon.check_writable("/r")          # consumes the half-open probe slot
    mon.note_error("/r", "write", OSError(errno.ENOSPC, "full"))
    # space-low for the (zero-TTL) cache window, then: the slot is free
    # again, so the very next preflight admits a new probe instead of
    # wedging until probe_at + cooldown
    mon.check_writable("/r")
    mon.note_ok("/r", "write")
    assert mon.state("/r") == "ok"


def test_health_writable_hint_admits_half_open_probe():
    """need_block's writability hint answers True once the cooldown
    admits a probe write: the solicited resync push IS the probe that
    walks the root back (answering False would starve a node with no
    direct PUT traffic of both recovery and its missing blocks)."""
    mon, _state, clock = _mk_monitor(threshold=2, cooldown=10.0)
    for _ in range(2):
        mon.note_error("/r", "write", OSError(errno.EIO, "io"))
        clock.advance(1.0)
    assert not mon.writable("/r")
    clock.advance(10.1)
    # non-consuming: repeated hints stay True and the probe slot is
    # still available for the actual write afterwards
    assert mon.writable("/r")
    assert mon.writable("/r")
    mon.check_writable("/r")          # consumes the probe slot
    mon.note_ok("/r", "write")
    assert mon.state("/r") == "ok"
    # a FAILED root keeps answering False even after any cooldown
    for _ in range(8):
        mon.note_error("/r", "write", OSError(errno.EIO, "io"))
        clock.advance(1.0)
    clock.advance(100.0)
    assert not mon.writable("/r")


def test_scrub_success_read_resets_streak(tmp_path):
    """The streak is CONSECUTIVE errors: on an archival node where the
    scrub is the only reader, its successful reads must reset the
    accounting or isolated bad sectors spread over weeks of passes
    would accumulate into a false degrade."""
    from garage_tpu.block.health import DiskIo
    from garage_tpu.block.repair import _try_read

    root = tmp_path / "data"
    d = root / "aa"
    d.mkdir(parents=True)
    f = d / ("ab" * 32)
    f.write_bytes(b"z" * 4096)
    mon = DiskHealthMonitor([str(root)], watermark=0, error_threshold=2)

    class Mgr:
        disk = DiskIo()
        health = mon

        def _root_of(self, path):
            return str(root)

    mgr = Mgr()
    for _ in range(2):
        mon.note_error(str(root), "scrub", OSError(errno.EIO, "io"))
    assert mon.state(str(root)) == "degraded"
    assert _try_read(mgr, str(f)) == b"z" * 4096
    assert mon.state(str(root)) == "ok"


def test_config_quarantine_max_files_is_a_plain_count():
    """quarantine_max_files is a file count: capacity suffixes ("1K")
    must be a config error, not a silent ×1000."""
    from garage_tpu.utils.config import ConfigError, config_from_dict

    cfg = config_from_dict({"metadata_dir": "/tmp/m", "data_dir": "/tmp/d",
                            "quarantine_max_files": 64})
    assert cfg.quarantine_max_files == 64
    for bad in ("1K", -1, True, 1.5):
        with pytest.raises(ConfigError):
            config_from_dict({"metadata_dir": "/tmp/m", "data_dir": "/tmp/d",
                              "quarantine_max_files": bad})


# --- StorageError wire codes ---


def test_storage_errors_round_trip_the_wire():
    for cls in (StorageError, StorageFull):
        e = cls("disk said no")
        code = error_code(e)
        assert code == cls.__name__
        back = remote_error(code, str(e))
        assert isinstance(back, cls)
        assert getattr(back, "remote_code", None) == code


# --- janitor (crash-consistent startup) ---


def test_janitor_pass_purges_tmp_and_bounds_quarantine(tmp_path):
    root = tmp_path / "data"
    d = root / "aa" / "bb"
    d.mkdir(parents=True)
    (d / ("ff" * 32 + ".tmp")).write_bytes(b"torn")
    (d / ("ee" * 32 + ".zst.tmp")).write_bytes(b"torn2")
    # parity sidecars are ParityStore's business: janitor must skip them
    par = root / "parity"
    par.mkdir()
    (par / "x.tmp").write_bytes(b"keep")
    hashes = []
    for i in range(4):
        hb = bytes([i]) * 32
        hashes.append(hb)
        p = d / (hb.hex() + ".corrupted")
        p.write_bytes(b"x" * 100)
        os.utime(p, (1000 + i, 1000 + i))
    summary = janitor_pass([str(root)], max_quarantine_files=2,
                           max_quarantine_bytes=10_000)
    assert summary["tmp_purged"] == 2
    assert (par / "x.tmp").exists()
    # oldest-first purge down to the budget; survivors requeue
    assert summary["quarantine_purged"] == 2
    assert summary["quarantine_kept"] == 2
    assert sorted(summary["requeue"]) == sorted(hashes[2:])
    assert not (d / (hashes[0].hex() + ".corrupted")).exists()


def test_janitor_byte_budget(tmp_path):
    root = tmp_path / "data"
    d = root / "00" / "11"
    d.mkdir(parents=True)
    for i in range(3):
        hb = bytes([16 + i]) * 32
        p = d / (hb.hex() + ".corrupted")
        p.write_bytes(b"y" * 400)
        os.utime(p, (2000 + i, 2000 + i))
    summary = janitor_pass([str(root)], max_quarantine_files=100,
                           max_quarantine_bytes=900)
    assert summary["quarantine_purged"] == 1  # 1200 → 800 bytes
    assert summary["quarantine_kept"] == 2


def test_janitor_unpurgeable_quarantine_still_requeued(tmp_path, monkeypatch):
    """A failed quarantine purge is not a purge: the surviving file
    stays counted as kept and its hash still reaches the requeue list
    (a root remounted read-only at boot must not make the janitor
    silently forget quarantined holes)."""
    import garage_tpu.block.health as health_mod

    root = tmp_path / "data"
    d = root / "aa"
    d.mkdir(parents=True)
    hashes = [bytes([32 + i]) * 32 for i in range(3)]
    for i, hb in enumerate(hashes):
        p = d / (hb.hex() + ".corrupted")
        p.write_bytes(b"x" * 100)
        os.utime(p, (3000 + i, 3000 + i))
    real_remove = os.remove

    def deny_corrupted(p):
        if str(p).endswith(".corrupted"):
            raise OSError(errno.EROFS, "read-only fs", p)
        return real_remove(p)

    monkeypatch.setattr(health_mod.os, "remove", deny_corrupted)
    summary = janitor_pass([str(root)], max_quarantine_files=1,
                           max_quarantine_bytes=10_000)
    assert summary["quarantine_purged"] == 0
    assert summary["quarantine_kept"] == 3
    assert sorted(summary["requeue"]) == sorted(hashes)


async def test_startup_janitor_requeues_quarantined_hashes(tmp_path):
    systems, managers = await make_block_cluster(tmp_path)
    mgr = managers[0]
    root = mgr.data_layout.data_dirs[0].path
    d = os.path.join(root, "ab", "cd")
    os.makedirs(d, exist_ok=True)
    hb = b"\xab" * 32
    with open(os.path.join(d, hb.hex() + ".corrupted"), "wb") as f:
        f.write(b"bad")
    with open(os.path.join(d, "deadbeef.tmp"), "wb") as f:
        f.write(b"torn")
    summary = mgr.startup_janitor()
    assert summary["tmp_purged"] == 1
    assert not os.path.exists(os.path.join(d, "deadbeef.tmp"))
    assert summary["requeue"] == [hb]
    assert mgr.resync.enqueue_counts.get("janitor") == 1
    assert mgr.resync.queue_len() == 1
    await shutdown(systems)


# --- write-path faults ---


async def test_write_eio_raises_typed_and_feeds_streak(tmp_path):
    systems, managers = await make_block_cluster(tmp_path)
    mgr = managers[0]
    # a real ENOSPC marks the root space-low for one cache TTL; expire
    # it instantly so the post-heal write below is deterministic
    mgr.health.cache_ttl = 0.0
    fd = FaultyDisk(mgr.disk)
    mgr.disk = fd
    data = os.urandom(20_000)
    h = blake2s_sum(data)
    fd.write_errno = errno.EIO
    with pytest.raises(StorageError):
        await mgr.write_block(h, DataBlock.plain(data))
    fd.write_errno = errno.ENOSPC
    with pytest.raises(StorageFull):
        await mgr.write_block(blake2s_sum(b"other"), DataBlock.plain(b"other"))
    assert mgr.health.error_counts[("write", "EIO")] == 1
    assert mgr.health.error_counts[("write", "ENOSPC")] == 1
    # heal: the write succeeds and clears the streak
    fd.clear()
    await mgr.write_block(h, DataBlock.plain(data))
    assert mgr.is_block_present(h)
    assert mgr.health.state(mgr._root_of(mgr.find_block(h)[0])) == "ok"
    await shutdown(systems)


async def test_enospc_node_rejects_but_quorum_survives(tmp_path):
    """One node at the free-space watermark goes read-only: its
    rpc_put_block rejections are typed (StorageFull) so the write quorum
    routes around it with zero caller-visible errors, need_block answers
    False (no wasted offers), and the root recovers when space does."""
    systems, managers = await make_block_cluster(tmp_path)
    victim = managers[2]
    victim.health.cache_ttl = 0.0   # deterministic statvfs freshness
    fd = FaultyDisk(victim.disk)
    victim.disk = fd
    fd.statvfs_free = 0
    root = victim.data_layout.data_dirs[0].path
    assert victim.health.state(root) == "degraded"
    data = os.urandom(60_000)
    h = blake2s_sum(data)
    await managers[0].rpc_put_block(h, data)   # quorum 2/3: succeeds
    await asyncio.sleep(0.2)                    # straggler drain
    assert not victim.is_block_present(h)
    stored = sum(1 for m in managers if m.is_block_present(h))
    assert stored == 2
    # a read-only node must not solicit block offers it would reject
    victim.db.transaction(lambda tx: victim.rc.block_incref(tx, h))
    assert not await victim.need_block(h)
    # gossiped state: peers see the node read-only in cluster stats
    st = victim.system._local_status()
    assert st.disk_state == "degraded"
    # space recovers → writable again, resync backfills the copy
    fd.clear()
    assert victim.health.state(root) == "ok"
    assert await victim.need_block(h)
    await victim.resync.resync_block(h)
    assert victim.is_block_present(h)
    await shutdown(systems)


# --- self-healing read path ---


async def test_read_eio_fails_over_quarantines_and_heals(tmp_path):
    """A read-time EIO is client-invisible: the RPC read fails over to a
    replica, the unreadable copy is quarantined, the hash goes into
    disk-error backoff (no bad-sector hammering), resync refetches with
    source=disk_error, and a later read serves the healed local copy."""
    systems, managers = await make_block_cluster(tmp_path)
    data = os.urandom(90_000)
    h = blake2s_sum(data)
    await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.2)
    victim = next(m for m in managers if m.is_block_present(h))
    path, _ = victim.find_block(h)
    fd = FaultyDisk(victim.disk)
    victim.disk = fd
    fd.read_errno = errno.EIO
    # client-facing read on the victim: correct bytes via failover
    assert await victim.rpc_get_block(h) == data
    assert os.path.exists(path + ".corrupted")
    assert victim.quarantined == 1
    assert victim.health.error_counts[("read", "EIO")] == 1
    assert victim.resync.enqueue_counts.get("disk_error") == 1
    # per-hash backoff: local read fails over instantly, disk untouched
    reads_before = fd.injected["read"]
    with pytest.raises(NoSuchBlock):
        await victim.read_block(h)
    assert fd.injected["read"] == reads_before
    # heal the disk, run the queued resync → clean local copy, served
    fd.clear()
    victim.db.transaction(lambda tx: victim.rc.block_incref(tx, h))
    await victim.resync.resync_block(h)
    assert victim.is_block_present(h)
    blk = await victim.read_block(h)
    assert blk.decompressed() == data
    await shutdown(systems)


async def test_transient_read_error_destroys_nothing(tmp_path):
    """EMFILE/ENOMEM-class read errors blame the process, not the disk:
    the read still fails over, but the healthy copy is NOT quarantined,
    the root's streak stays clean (a busy node must not mass-evict its
    own good data), and the copy serves locally again the moment the
    pressure clears — no per-hash backoff, no resync churn."""
    systems, managers = await make_block_cluster(tmp_path)
    data = os.urandom(60_000)
    h = blake2s_sum(data)
    await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.2)
    victim = next(m for m in managers if m.is_block_present(h))
    path, _ = victim.find_block(h)
    fd = FaultyDisk(victim.disk)
    victim.disk = fd
    fd.read_errno = errno.EMFILE
    assert await victim.rpc_get_block(h) == data      # failover works
    assert os.path.exists(path)                       # copy untouched
    assert not os.path.exists(path + ".corrupted")
    assert victim.quarantined == 0
    assert ("read", "EMFILE") not in victim.health.error_counts
    assert victim.resync.enqueue_counts.get("disk_error") is None
    assert victim.health.state(victim._root_of(path)) == "ok"
    fd.clear()
    blk = await victim.read_block(h)                  # no backoff armed
    assert blk.decompressed() == data
    await shutdown(systems)


async def test_scrub_read_eio_quarantines_and_feeds_health(tmp_path):
    """Scrub hitting an EIO-ing copy must not stay silent: the root's
    health accounting sees it (disk_error_total{op="scrub"}), the
    unreadable copy is quarantined, and resync refetches — while a
    vanished file stays a benign skip."""
    from garage_tpu.block.repair import ScrubWorker

    systems, managers = await make_block_cluster(tmp_path)
    data = os.urandom(40_000)
    h = blake2s_sum(data)
    await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.2)
    victim = next(m for m in managers if m.is_block_present(h))
    path, compressed = victim.find_block(h)
    fd = FaultyDisk(victim.disk)
    victim.disk = fd
    fd.read_errno = errno.EIO
    worker = ScrubWorker(victim)
    await worker.scrub_batch([(h, path, compressed)])
    assert victim.health.error_counts[("scrub", "EIO")] == 1
    assert victim.quarantined == 1
    assert os.path.exists(path + ".corrupted")
    assert victim.resync.enqueue_counts.get("scrub_corrupt") == 1
    await shutdown(systems)


async def test_concurrent_quarantine_of_same_copy_is_not_an_error(tmp_path):
    """Two readers hitting the same bad sector race quarantine_path on
    the same file: the loser's ENOENT means the copy is ALREADY
    quarantined — the desired end state — so it must not count a
    quarantine error or feed the root's streak toward degraded."""
    systems, managers = await make_block_cluster(tmp_path)
    data = os.urandom(30_000)
    h = blake2s_sum(data)
    await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.2)
    victim = next(m for m in managers if m.is_block_present(h))
    path, _ = victim.find_block(h)
    victim.quarantine_path(path)
    victim.quarantine_path(path)      # the racing loser
    assert victim.quarantined == 1
    assert victim.quarantine_errors == 0
    assert not any(op == "quarantine"
                   for op, _kind in victim.health.error_counts)
    assert os.path.exists(path + ".corrupted")
    await shutdown(systems)


async def test_quarantine_rename_failure_deletes_bad_copy(tmp_path):
    """Satellite: _move_corrupted used to swallow OSError, leaving a
    corrupt copy live and re-servable.  Now a failed quarantine rename
    is counted and the bad copy is deleted so resync refetches."""
    systems, managers = await make_block_cluster(tmp_path)
    data = os.urandom(50_000)
    h = blake2s_sum(data)
    await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.2)
    victim = next(m for m in managers if m.is_block_present(h))
    path, _ = victim.find_block(h)
    with open(path, "r+b") as f:
        f.seek(10)
        f.write(b"\x00\x01\x02\x03")

    class RenamelessDisk(FaultyDisk):
        def replace(self, src, dst):
            if dst.endswith(".corrupted"):
                raise OSError(errno.EACCES, "sealed", dst)
            return super().replace(src, dst)

    victim.disk = RenamelessDisk(victim.disk)
    with pytest.raises(Exception):
        await victim.read_block(h)
    assert victim.quarantine_errors == 1
    assert not os.path.exists(path)              # deleted, not left live
    assert not os.path.exists(path + ".corrupted")
    await shutdown(systems)


# --- kill-mid-write torture (acceptance criterion) ---


async def test_kill_mid_write_torture_no_acked_put_lost(tmp_path):
    """Crash injected at EVERY write stage — torn tmp write, before
    rename, at the directory fsync — then 'restart' (janitor pass over
    the same dirs): the data dir is consistent (no .tmp litter) and
    every acknowledged PUT is intact and verifiable."""
    for stage in ("tmp", "rename", "fsync"):
        systems, managers = await make_block_cluster(tmp_path / stage)
        mgr = managers[0]
        mgr.data_fsync = True   # exercise the fsync stage of the path
        acked = {}
        for k in range(3):
            data = os.urandom(30_000 + k)
            h = blake2s_sum(data)
            await mgr.write_block(h, DataBlock.plain(data))
            acked[h] = data
        fd = FaultyDisk(mgr.disk)
        mgr.disk = fd
        fd.crash_stage = stage
        lost = os.urandom(40_000)
        hl = blake2s_sum(lost)
        with pytest.raises(SimulatedCrash):
            await mgr.write_block(hl, DataBlock.plain(lost))
        # the PUT was NOT acknowledged; whatever is on disk is what a
        # real kill would leave.  "Restart": disk behaves again, the
        # boot janitor sweeps the roots.
        fd.clear()
        summary = mgr.startup_janitor()
        for root in (d.path for d in mgr.data_layout.data_dirs):
            for dirpath, _dirs, files in os.walk(root):
                assert not [f for f in files if f.endswith(".tmp")], \
                    (stage, dirpath, files)
        if stage in ("tmp", "rename"):
            assert summary["tmp_purged"] == 1, (stage, summary)
            assert not mgr.is_block_present(hl)
        else:
            # crash AFTER rename: the block landed; unacked-but-present
            # is a harmless duplicate, never a loss — and it verifies
            blk = await mgr.read_block(hl)
            assert blk.decompressed() == lost
        for h, data in acked.items():
            blk = await mgr.read_block(h)
            assert blk.decompressed() == data, stage
        await shutdown(systems)


async def test_fsync_failure_is_a_typed_storage_error(tmp_path):
    systems, managers = await make_block_cluster(tmp_path)
    mgr = managers[0]
    mgr.data_fsync = True
    fd = FaultyDisk(mgr.disk)
    mgr.disk = fd
    fd.fsync_errno = errno.EIO
    data = os.urandom(10_000)
    with pytest.raises(StorageError):
        await mgr.write_block(blake2s_sum(data), DataBlock.plain(data))
    assert fd.injected["fsync"] >= 1
    await shutdown(systems)


# --- metrics exposition ---


async def test_disk_metric_families_pass_promlint(tmp_path):
    from garage_tpu.utils.promlint import lint_exposition

    systems, managers = await make_block_cluster(tmp_path)
    mgr = managers[0]
    fd = FaultyDisk(mgr.disk)
    mgr.disk = fd
    # populate disk_error_total + block_quarantine_total
    data = os.urandom(30_000)
    h = blake2s_sum(data)
    await mgr.write_block(h, DataBlock.plain(data))
    fd.read_errno = errno.EIO
    with pytest.raises(NoSuchBlock):
        await mgr.read_block(h)
    fd.clear()
    body = systems[0].metrics.render()
    problems = lint_exposition(body)
    assert not problems, problems
    for fam in ("disk_root_state", "disk_free_bytes", "disk_error_total",
                "block_quarantine_total", "block_quarantine_error_total"):
        assert fam in body, fam
    root = mgr.data_layout.data_dirs[0].path
    assert f'disk_root_state{{root="{root}"}}' in body
    assert 'disk_error_total{kind="EIO",op="read"} 1' in body
    await shutdown(systems)


# --- the chaos proof (acceptance criterion; slow tier) ---


@pytest.mark.slow
async def test_chaos_flaky_disk_plus_enospc(tmp_path):
    """3-node cluster, node 2 with a flaky disk (30% EIO reads) AND a
    full filesystem: concurrent S3 PUT/GET sustains with ZERO
    client-visible errors; disk_root_state on the victim is observed
    going read-only (≥1) during the fault and back to ok after heal;
    gossip shows peers the degraded state (cluster stats data)."""
    import random
    import time as _time

    import aiohttp
    import numpy as np

    from garage_tpu.testing.local_cluster import S3, mk_cluster
    from garage_tpu.net.frame import PRIO_HIGH
    from garage_tpu.testing.faults import FAST_CHAOS_RPC, FaultInjector

    garages, server, port, kid, secret = await mk_cluster(
        tmp_path, n=3, repl="3", db="memory",
        codec_cfg={"rs_data": 0, "rs_parity": 0, "backend": "cpu"},
        rpc_cfg=FAST_CHAOS_RPC)
    inj = FaultInjector(garages)
    rng = random.Random(41)
    nprng = np.random.default_rng(23)
    try:
        victim = garages[2].block_manager
        # fast-twitch disk breaker so one test observes a full cycle
        victim.health._tun.breaker_open_secs = 1.0
        fd = inj.flaky_disk(2, prob=0.3)
        inj.fill_disk(2)
        async with aiohttp.ClientSession() as session:
            s3 = S3(session, port, kid, secret)
            st, _b, _h = await s3.req("PUT", "/dchaos")
            assert st == 200, st
            errors = []
            acked = {}
            deadline = _time.monotonic() + 6.0
            i = 0
            worst = 0.0
            while _time.monotonic() < deadline:
                i += 1
                name = f"d{i:04d}"
                body = nprng.integers(
                    0, 256, rng.randrange(4 << 10, 128 << 10),
                    dtype=np.uint8).tobytes()
                st, _b, _h = await s3.req("PUT", f"/dchaos/{name}", body)
                if st == 200:
                    acked[name] = body
                else:
                    errors.append(("PUT", name, st))
                if acked:
                    probe = rng.choice(sorted(acked))
                    st, got, _h = await s3.req("GET", f"/dchaos/{probe}")
                    if st != 200 or got != acked[probe]:
                        errors.append(("GET", probe, st))
                states = victim.health.states()
                worst = max(worst, max(
                    DISK_STATE_VALUES[s] for s in states.values()))
            assert not errors, errors[:5]
            # traffic actually flowed (low floor: CI hosts run loaded)
            assert len(acked) >= 3
            # the victim's root was observed read-only in /metrics
            assert worst >= 1.0
            body = garages[2].system.metrics.render()
            assert "disk_root_state" in body
            # gossip → peers' cluster stats: push one status exchange
            msg = {"t": "advertise_status",
                   "status": garages[2].system._local_status().pack(),
                   "peers": garages[2].system._peer_book()}
            await garages[2].system.rpc.broadcast(
                garages[2].system.endpoint, msg, prio=PRIO_HIGH,
                timeout=5.0)
            peer_view = garages[0].system.node_status[
                garages[2].system.id]
            assert peer_view.disk_state in ("degraded", "failed")
            # heal: space + disk recover; after the breaker cooldown a
            # probe write closes it and the root walks back to ok
            inj.heal_disk(2)
            await asyncio.sleep(1.2)
            recover_deadline = _time.monotonic() + 8.0
            state = None
            while _time.monotonic() < recover_deadline:
                body = nprng.integers(0, 256, 8 << 10,
                                      dtype=np.uint8).tobytes()
                st, _b, _h = await s3.req(
                    "PUT", f"/dchaos/heal-{_time.monotonic():.3f}", body)
                assert st == 200, st
                state = victim.health.worst_state()
                if state == "ok":
                    break
                await asyncio.sleep(0.3)
            assert state == "ok", state
            rendered = garages[2].system.metrics.render()
            assert 'disk_root_state{root=' in rendered
    finally:
        await server.stop()
        for g in garages:
            await g.shutdown()
