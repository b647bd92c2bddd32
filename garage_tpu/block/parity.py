"""ParityStore — local Reed-Solomon sidecars for scrub-time self-repair.

The reference repairs a corrupted block only by refetching it from a
replica (ref src/block/resync.rs:457-468); if every replica is
unreachable or equally damaged, the data is gone.  Here the scrub
worker's fused verify+encode pass (the BlockCodec north star) already
computes RS(k, m) parity over each codeword of k blocks — this module
persists that parity as a local sidecar so a corrupted or lost block can
be **reconstructed on this node alone**, with zero network, as long as
≥ k of the codeword's k+m pieces survive.  Network resync remains the
fallback.

Layout: one msgpack manifest per codeword under
`<data_dir>/parity/xx/<group_id>.par` (group_id = blake2s over the
member hashes), plus a small db tree, `block_parity_index`, mapping
block hash → group id so repair can find a block's codeword in O(1).
Data shards are the member blocks themselves (zero-padded to the
codeword width), read back from the block store and re-verified by
content hash at reconstruction time; parity shards carry their own
checksums.  Any mismatch disqualifies the piece — reconstruction either
produces a block whose hash matches, or fails loudly and the caller
falls back to the network.

**The index is the record of membership.**  A codeword keeps its members
from pass to pass: a block's place in a pass's listing decides nothing
once the index names its codeword.  An entry is the group id, followed,
for a codeword the scrub formed (k members, in id order), by the (k, m)
it was formed at; an entry of the bare 32 bytes names a codeword that
is the block's cover but not its place: a write-time codeword (members
in arrival order, possibly fewer than k), one filed by code before the
(k, m) suffix, or one the scrub dissolved.  What a pass does with each
block it reads (`ScrubMembership`, asked once a batch, off the loop):

1. *Settled*: the entry names a scrub codeword of this (k, m) whose
   sidecar is on disk.  The block stays in it.  The file gets a fresh
   mtime once a pass (what the purge keys on); nothing is hashed into a
   group id, nothing is written to the index, no parity leaves the
   device.
2. *Its sidecar is gone, its members are not*: the same k members are
   encoded again and the file comes back under the same name.  Members
   read in an earlier batch are held, at most a batch of them, until
   the last has been read.
3. *Free*: no entry, or a bare one.  Free blocks are grouped k at a
   time in listing order, with a carry across batches; a store without
   sidecars forms the codewords a listing read k at a time gives.  A
   batch goes to the device with its own lanes and no other, so that no
   program is compiled for a lane count the listing does not have: a
   row whose members were verified in two batches (rule 2's held ones,
   the carry's) is encoded on the host, where the write-time codewords
   are.  A
   bare entry's sidecar is kept fresh while its block waits for a full
   row, then left to the purge: the block is covered all the way.  k
   blocks of one batch whose bare entries name the group id of exactly
   those k in id order are an intact scrub codeword and are adopted as
   settled (how an index from before the suffix converges).
4. *A codeword that lost a member for good* (a whole pass read fewer
   than k of them, and the store has none of those it did not read: a
   read that failed this once is not a loss) is dissolved at the pass's
   end: its survivors' entries become bare, the next pass regroups
   them, and the old file ages out under the purge's grace.
5. *A full codeword is not robbed by a later one*: filing a write-time
   codeword (a PUT, a heal's `store_rebuilt`) leaves alone an entry
   that names a scrub codeword of this (k, m); the newer sidecar is
   extra parity the purge collects.

The purge's grace of one pass (`purge_stale`) protects: a settled
sidecar whose block failed this pass's verify (touched before the
verdict, and again by the heal), the cover of a free block that has not
met k − 1 others yet, and a dissolved codeword until its survivors are
regrouped.  Its prune of index entries whose sidecar is gone spares a
scrub codeword's while the block is in the store (rule 2 needs them).
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from typing import List, Optional, Sequence, Tuple

import msgpack
import numpy as np

from ..utils.data import Hash, blake2s_sum, block_hash
from .block import DataBlock

logger = logging.getLogger("garage_tpu.block.parity")

MANIFEST_VERSION = 1

# Who wrote a sidecar (`parity_sidecar_written_bytes_total{origin}`): the
# scrub pass (a codeword of its listing that had none), the write-time
# accumulator over freshly written blocks, or the accumulator over a
# codeword more than half of whose members a heal wrote back
# (`store_rebuilt`; the flush event's `healed` has the count).
SIDECAR_ORIGINS = ("scrub", "write", "heal")
# What flushed a write-time codeword: k members, the timer, a second
# member bound for a node the codeword already has (distributed
# codewords only), or the shutdown's drain.
FLUSH_CAUSES = ("full", "timeout", "node", "drain")
# What a scrub pass did with a codeword (`scrub_codewords_total{state}`):
# found it whole with its sidecar on disk, wrote its lost sidecar again
# under the same name, formed it from free blocks, or dissolved it
# because a whole pass read fewer than k of its members.
CODEWORD_STATES = ("settled", "rewritten", "formed", "dissolved")
GID_LEN = 32


def encode_codeword(manager, codec, blocks: Sequence[bytes]) -> np.ndarray:
    """Parity of one codeword's plain members, (m, maxlen), off the
    scrub's fused road: rs_encode_blocks zero-pads the member count to a
    whole codeword — exactly the partial-codeword zero-shard semantics.
    Via the codec feeder when the manager has one: concurrent codewords
    (every in-flight PUT under parity_on_write) coalesce into one ragged
    pointer-gather/device pass instead of one GF call each."""
    feeder = getattr(manager, "feeder", None)
    if feeder is not None and feeder.codec is codec:
        return feeder.encode_or_direct(blocks)[0]
    return codec.rs_encode_blocks(blocks)[0]


class ParityStore:
    def __init__(self, manager, db, codec):
        from ..db.counted_tree import CountedTree

        self.manager = manager
        self.codec = codec
        # CountedTree: the coverage gauge reads len() per metrics scrape,
        # and sqlite COUNT(*) is O(n)
        self.index = CountedTree(db.open_tree("block_parity_index"))
        # new sidecars go to the first WRITABLE data dir (a drained
        # read_only drive must not keep accumulating them); lookups and
        # the purge walk EVERY dir so sidecars written before a drain or
        # layout change stay reachable and collectable
        dirs = manager.data_layout.data_dirs
        root = next(
            (d.path for d in dirs if not d.read_only), dirs[0].path
        )
        self.dir = os.path.join(root, "parity")
        self.all_dirs = [os.path.join(d.path, "parity") for d in dirs]
        metrics = getattr(getattr(manager, "system", None), "metrics", None)
        self.m_bytes = None if metrics is None else metrics.counter(
            "parity_sidecar_bytes_total",
            "Bytes of the codewords put to the parity store, written or "
            "found and refreshed: part=parity is m x the longest member, "
            "part=covered the members' own lengths")
        self.m_written_bytes = self.m_written = self.m_purged = None
        self.m_codewords = None
        if metrics is not None:
            self.m_written_bytes = metrics.counter(
                "parity_sidecar_written_bytes_total",
                "Parity bytes (m x the longest member) of the sidecars "
                "WRITTEN, one found and touched not counted, by who wrote "
                "them: scrub | write (the write-time accumulator) | heal "
                "(the accumulator, most members written back by heals)")
            self.m_written = metrics.counter(
                "parity_codewords_written_total",
                "Sidecars written, by origin: the files behind "
                "parity_sidecar_written_bytes_total")
            self.m_purged = metrics.counter(
                "parity_purged_sidecars_total",
                "Sidecars the scrub's purge removed: refreshed by "
                "neither the pass that ended nor the one before it")
            self.m_codewords = metrics.counter(
                "scrub_codewords_total",
                "Codewords a scrub pass accounted for, by what it did with "
                "them: settled (members and sidecar as the index says) | "
                "rewritten (the same members, the lost sidecar back under "
                "its name) | formed (from free blocks) | dissolved (a "
                "whole pass read fewer than k members)")
            for origin in SIDECAR_ORIGINS:      # every series from 0
                self.m_written.inc(0, origin=origin)
                self.m_written_bytes.inc(0, origin=origin)
            for state in CODEWORD_STATES:
                self.m_codewords.inc(0, state=state)
        # what the last purge did, for the pass's `purge stale` event
        self.last_purge = {"removed": 0, "dead": 0}

    # --- write path (scrub) ------------------------------------------------

    @staticmethod
    def _gid(k: int, m: int, hashes: Sequence[Hash]) -> Hash:
        """Group id over (manifest version, k, m, member hashes).  The
        codec geometry is part of the identity: with member-hashes-only
        gids, an rs_parity config change made put_codeword mtime-touch the
        old-geometry file forever (so purge never removed it) while
        _load_manifest rejected it on its (k, m) check — silently and
        permanently losing local-repair coverage for the codeword."""
        import struct

        head = struct.pack("<III", MANIFEST_VERSION, k, m)
        return blake2s_sum(head + b"".join(bytes(h) for h in hashes))

    def _group_path(self, gid: bytes) -> str:
        """Write location for a group (the writable dir)."""
        hx = gid.hex()
        return os.path.join(self.dir, hx[:2], hx + ".par")

    def _group_paths(self, gid: bytes):
        """Where a group's sidecar may be: every data dir's parity tree."""
        hx = gid.hex()
        return (os.path.join(base, hx[:2], hx + ".par")
                for base in self.all_dirs)

    def _find_group_path(self, gid: bytes) -> Optional[str]:
        """Read location: the first of them that is there."""
        return next(filter(os.path.exists, self._group_paths(gid)), None)

    def put_codeword(
        self,
        hashes: Sequence[Hash],
        lengths: Sequence[int],
        parity: np.ndarray,
        origin: str = "scrub",
    ) -> bool:
        """Persist one codeword's parity: `hashes`/`lengths` are the j ≤ k
        member blocks in codeword order, `parity` is (m, maxlen) uint8
        encoded at the codec's (k, m) geometry.  j < k means a PARTIAL
        codeword (write-time encoding flushes one before k blocks
        accumulate): members j..k-1 are implicit all-zero shards —
        GF-linear, so the parity is identical to a k-member codeword
        whose tail members are zero, and reconstruction counts the zero
        shards as always-available pieces.  Called by the scrub worker
        (the rows `ScrubMembership.plan` asked parity for, whose
        members all verified) and the write-path accumulator (possibly
        partial; `origin` write or heal).  →
        whether a sidecar was written (False: one with this content was
        there and got a fresh mtime)."""
        # one call a codeword: in the profiler's trace, not in the ring,
        # where the scrub batch's `parity write` event stands
        with self.codec.obs.timeline.span("put codeword", "scrub-io",
                                          cat="scrub", record=False):
            return self._file(hashes, lengths, parity, origin)

    def put_straddler(self, hashes: Sequence[Hash],
                      blocks: Sequence[bytes]) -> bool:
        """A scrub codeword whose members were verified in two batches:
        encoded here, where the write-time codewords are (a shape the
        PUT path has met), and put.  → whether it was written."""
        return self.put_codeword(
            hashes, [len(b) for b in blocks],
            encode_codeword(self.manager, self.codec, blocks))

    def begin_pass(self, whole: bool) -> "ScrubMembership":
        """What one scrub pass knows of the codewords it meets.  `whole`:
        the pass reads the store from its first block in this process,
        so a codeword it saw fewer than k members of has lost one."""
        return ScrubMembership(self, whole)

    # --- the index: block id → group id [+ (k, m) of a scrub codeword] ----

    def _suffix(self) -> bytes:
        p = self.codec.params
        return bytes([p.rs_data, p.rs_parity])

    def _scrub_entry(self, gid: bytes) -> bytes:
        return gid + self._suffix()

    def _scrub_gid(self, entry: Optional[bytes]) -> Optional[bytes]:
        """The group id, where `entry` names a codeword the scrub formed
        at this (k, m): the one kind of entry that is a block's place."""
        if entry is not None and entry[GID_LEN:] == self._suffix():
            return entry[:GID_LEN]
        return None

    def _entries(self, keys: Sequence[bytes]) -> dict:
        """The index over the span of `keys`, in one range read."""
        return dict(self.index.items(min(keys), max(keys) + b"\x00"))

    def _touch(self, gid: bytes) -> bool:
        """A fresh mtime for the group's sidecar, in whichever data dir
        holds it; → whether it was there."""
        # the time is given: left to the kernel it is the tick's, which
        # can lie before the start of a pass that began within the tick
        now = time.time_ns()
        for path in self._group_paths(gid):
            try:
                os.utime(path, ns=(now, now))
                return True
            except OSError:
                continue
        return False

    def _file(self, hashes, lengths, parity: np.ndarray,
              origin: str) -> bool:
        """One codeword filed: counted, its sidecar touched — or, where
        it has none, written from `parity`, (m, maxlen), and counted to
        `origin` — and its members indexed.  → whether it was written."""
        k = self.codec.params.rs_data
        assert 0 < len(hashes) <= k, (len(hashes), k)
        self._count_bytes(int(parity.shape[0]) * int(max(lengths)),
                          int(sum(lengths)))
        gid = bytes(self._gid(k, int(parity.shape[0]), hashes))
        # gid hashes the member set AND the (version, k, m) geometry, so
        # an existing file has identical content: a fresh mtime (what the
        # purge keys on) is all it needs
        found = self._touch(gid)
        if not found:
            rows = [parity[i].tobytes() for i in range(parity.shape[0])]
            manifest = {
                "v": MANIFEST_VERSION,
                "k": k,
                "m": int(parity.shape[0]),
                "maxlen": int(parity.shape[1]),
                "hashes": [bytes(h) for h in hashes],
                "lengths": [int(n) for n in lengths],
                "parity": rows,
                "parity_sums": [bytes(blake2s_sum(row)) for row in rows],
            }
            path = self._group_path(gid)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(msgpack.packb(manifest, use_bin_type=True))
            os.replace(tmp, path)
            if self.m_written is not None:
                self.m_written.inc(origin=origin)
                self.m_written_bytes.inc(int(parity.nbytes), origin=origin)
        if origin == "scrub" and len(hashes) == k:
            entry = self._scrub_entry(gid)
            for h in hashes:
                self.index.insert(bytes(h), entry)
        else:
            # a full codeword is not robbed by a later one: a member the
            # scrub has placed keeps its place, this sidecar is extra
            for h in hashes:
                if self._scrub_gid(self.index.get(bytes(h))) is None:
                    self.index.insert(bytes(h), gid)
        return not found

    def _count_bytes(self, parity: int, covered: int) -> None:
        """A codeword put or found settled: a sidecar holds m rows as
        long as its longest member, over the members' own lengths."""
        if self.m_bytes is not None:
            self.m_bytes.inc(parity, part="parity")
            self.m_bytes.inc(covered, part="covered")

    # --- repair path -------------------------------------------------------

    def _load_manifest(self, h: Hash) -> Optional[dict]:
        entry = self.index.get(bytes(h))
        if entry is None:
            return None
        path = self._find_group_path(bytes(entry[:GID_LEN]))
        if path is None:
            return None
        try:
            with open(path, "rb") as f:
                man = msgpack.unpackb(f.read(), raw=False)
        except Exception:  # noqa: BLE001 — any bad sidecar = no coverage
            return None
        if man.get("v") != MANIFEST_VERSION or bytes(h) not in man["hashes"]:
            return None
        man["_path"] = path  # saves re-resolving for the mtime touch
        # a sidecar from an older (k, m) config cannot be decoded by the
        # current codec; the next scrub pass rewrites it
        if (man["k"] != self.codec.params.rs_data
                or man["m"] != self.codec.params.rs_parity):
            return None
        if len(man["hashes"]) > man["k"]:
            return None  # malformed
        return man

    def coverage(self, h: Hash) -> bool:
        """Is this block covered by a (possibly stale) parity sidecar?"""
        return self._load_manifest(h) is not None

    def try_reconstruct(self, h: Hash) -> Optional[bytes]:
        """Rebuild block `h` from its codeword's surviving pieces.

        Every candidate piece is verified before use (data shards by
        content hash, parity shards by stored checksum); the rebuilt
        block is verified against `h` before being returned.  Returns
        the plain block bytes, or None if fewer than k trustworthy
        pieces survive."""
        man = self._load_manifest(h)
        if man is None:
            return None
        k, m, maxlen = man["k"], man["m"], man["maxlen"]
        hashes = [Hash(x) for x in man["hashes"]]
        target_i = man["hashes"].index(bytes(h))

        pieces: List[np.ndarray] = []
        present: List[int] = []
        # data shards: re-read surviving member blocks from the store
        for i, mh in enumerate(hashes):
            if i == target_i:
                continue
            raw = self._read_verified_member(mh)
            if raw is None:
                continue
            shard = np.zeros(maxlen, dtype=np.uint8)
            shard[: len(raw)] = np.frombuffer(raw, dtype=np.uint8)
            pieces.append(shard)
            present.append(i)
            if len(present) >= k:
                break
        # implicit zero shards of a partial codeword: members j..k-1 are
        # all-zero by construction, always "present" at no cost
        if len(present) < k:
            for i in range(len(hashes), k):
                pieces.append(np.zeros(maxlen, dtype=np.uint8))
                present.append(i)
                if len(present) >= k:
                    break
        # parity shards as needed
        if len(present) < k:
            for j in range(m):
                p = np.frombuffer(man["parity"][j], dtype=np.uint8)
                if bytes(blake2s_sum(man["parity"][j])) != bytes(
                        man["parity_sums"][j]):
                    continue
                pieces.append(p)
                present.append(k + j)
                if len(present) >= k:
                    break
        if len(present) < k:
            return None

        shards = np.stack(pieces)[None, :, :]  # (1, p, maxlen)
        try:
            # rows=[target_i]: a single-block repair pays for ONE decoded
            # row, not all k (k× GF work saving).  Routed through the
            # manager's codec feeder when present: concurrent degraded
            # reads of the same loss pattern share one cached RS
            # schedule and one ragged dispatch (ops/feeder.py); a
            # closed/absent feeder decodes inline.  Guarded on identity:
            # the feeder fronts the MANAGER's codec, and this store may
            # run a different one (geometry change mid-flight, tests
            # swapping codecs) — a mismatched (k, m) must decode direct.
            feeder = getattr(self.manager, "feeder", None)
            if feeder is not None and feeder.codec is self.codec:
                # cls="bg": sidecar rebuilds run from the scrub/resync
                # heal paths — in the device transport's single queue
                # they yield to live foreground verifies/decodes
                data = feeder.decode_or_direct(
                    shards, present, rows=[target_i], cls="bg")[0]
            else:
                data = self.codec.rs_reconstruct(
                    shards, present, rows=[target_i])[0]  # (1, maxlen)
        except Exception:
            logger.exception("parity reconstruction failed for %s",
                             bytes(h).hex()[:16])
            return None
        out = data[0].tobytes()[: man["lengths"][target_i]]
        if bytes(block_hash(out, self.manager.hash_algo)) != bytes(h):
            logger.warning(
                "parity reconstruction of %s produced wrong hash "
                "(stale codeword?)", bytes(h).hex()[:16],
            )
            return None
        logger.info("locally reconstructed block %s from RS parity",
                    bytes(h).hex()[:16])
        # refresh the sidecar's mtime: its row failed verify this scrub
        # pass (that is why we are here), so the pass will not rewrite
        # it — without the touch the purge could drop it
        try:
            os.utime(man["_path"])
        except OSError:
            pass
        return out

    def _read_verified_member(self, h: Hash) -> Optional[bytes]:
        """A member block's plain bytes, only if present and intact."""
        found = self.manager.find_block(h)
        if found is None:
            return None
        path, compressed = found
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return None
        try:
            block = DataBlock(raw, compressed)
            data = block.decompressed()
        except Exception:
            return None
        if bytes(block_hash(data, self.manager.hash_algo)) != bytes(h):
            return None
        return data

    def purge_stale(self, older_than: float) -> int:
        """Delete sidecars not refreshed since `older_than` (unix time)
        and prune index entries pointing at missing files (a scrub
        codeword's only once the block is gone too).  Write-time
        codewords are folded into the scrub's and dissolved ones
        regrouped, so every completed scrub pass calls this with the
        start of the pass before it — without it, orphaned .par files
        would accumulate."""
        removed = 0
        for base in self.all_dirs:
            if not os.path.isdir(base):
                continue
            for sub in os.listdir(base):
                d = os.path.join(base, sub)
                try:
                    names = os.listdir(d)
                except OSError:
                    continue
                for name in names:
                    p = os.path.join(d, name)
                    try:
                        if os.stat(p).st_mtime < older_than:
                            os.remove(p)
                            removed += 1
                    except OSError:
                        pass
        # prune index entries whose group file is gone, but for a block's
        # place: a scrub codeword that lost its sidecar (an operator, a
        # bad sector, one removed while this runs) keeps its members as
        # long as they are in the store, and the next pass writes the
        # file again under its name
        dead = [
            k for k, entry in list(self.index.items(None, None))
            if self._find_group_path(bytes(entry[:GID_LEN])) is None
            and (self._scrub_gid(entry) is None
                 or self.manager.find_block(Hash(k)) is None)
        ]
        for k in dead:
            self.index.remove(k)
        self.last_purge = {"removed": removed, "dead": len(dead)}
        if self.m_purged is not None and removed:
            self.m_purged.inc(removed)
        if removed or dead:
            logger.info("parity purge: %d stale sidecars, %d index entries",
                        removed, len(dead))
        return removed

    def stats(self) -> dict:
        return {"indexed_blocks": len(self.index)}


# What a running pass knows of a scrub codeword it has met: its sidecar
# is on disk / is gone and is written again once the k members are read /
# has to wait for the next pass (a member failed its verify) / is not
# what the index says and loses its members' entries at the pass's end
_SETTLED, _PENDING, _BROKEN, _DISSOLVED = range(4)


class _Codeword:
    __slots__ = ("gid", "state", "members", "maxlen", "covered", "held",
                 "here")

    def __init__(self, gid: bytes, state: int):
        self.gid, self.state = gid, state
        self.members: List[bytes] = []  # the ids read so far
        self.maxlen = self.covered = 0  # of their lengths
        self.held: List[tuple] = []     # (hash, block) verified in earlier
        self.here: List[int] = []       # batches; this batch's, by index


class BatchPlan:
    """One scrub batch as it goes to the codec: its own lanes and no
    other (so its geometry is the listing's), the rows whose parity the
    device has to hand back in front (k consecutive lanes each, as the
    fused kernel encodes them), every other lane behind.  `blocks` /
    `hashes` are the submission, `want` names the front rows, `where[j]`
    is the lane of the batch's j-th block (a verdict is mapped back
    through it).  A row that needs lanes an earlier batch verified
    (`straddlers`) is not the device's: its new lanes stand behind and
    `filed` hands it over to be encoded on the host."""

    def __init__(self, hashes, blocks, order, rows, straddlers, pending,
                 tail):
        self.hashes = [hashes[j] for j in order]
        self.blocks = [blocks[j] for j in order]
        self.rows = rows            # front row r: its _Codeword, or None
        self.want = list(range(len(rows)))      # (a row of free blocks)
        self.straddlers = straddlers    # (its _Codeword | None, lanes kept
        self.pending = pending      # over, this batch's by index); rewrites
        self.tail = tail            # not whole yet; free, short of a row
        self.where = [0] * len(order)
        for lane, j in enumerate(order):
            self.where[j] = lane


class ScrubMembership:
    """Which codeword each block of one scrub pass is in, and whether
    that codeword needs its parity from the device: the five rules of
    the module's docstring.  `plan` is asked once a batch before the
    submit, `filed` names the rows to write once the verdicts are in
    (`wrote` after each), `close` ends the pass.  `plan` and `close`
    read and write the index and touch files: off the loop."""

    def __init__(self, store: ParityStore, whole: bool):
        self.store = store
        self.whole = whole
        self.k = store.codec.params.rs_data
        self.m = store.codec.params.rs_parity
        self.codewords: dict = {}       # gid → _Codeword, until settled
        self.fresh: set = set()         # gids of bare entries, touched
        self.carry: List[tuple] = []    # free (hash, block), fewer than k
        self.held = 0                   # lanes held for rewrites, at most
        self.most_held = max(self.k, store.codec.params.batch_blocks)
        self.asked = 0                  # rows whose parity was asked for,
        self.host = 0                   # those of them encoded on the host
        self.counts = dict.fromkeys(CODEWORD_STATES, 0)

    def _count(self, state: str) -> None:
        self.counts[state] += 1
        if self.store.m_codewords is not None:
            self.store.m_codewords.inc(state=state)

    def _adopt(self, keys: List[bytes], entries: dict) -> None:
        """k blocks of the batch whose bare entries name the group id of
        exactly those k in id order are an intact scrub codeword (its
        name is a hash of its members): their entries get the suffix."""
        named: dict = {}
        for key in keys:
            entry = entries.get(key)
            if entry is not None and len(entry) == GID_LEN:
                named.setdefault(entry, []).append(key)
        for gid, members in named.items():
            if len(members) == self.k and bytes(ParityStore._gid(
                    self.k, self.m, members)) == gid:
                entry = self.store._scrub_entry(gid)
                for key in members:
                    self.store.index.insert(key, entry)
                    entries[key] = entry

    def _let_go(self, cw: _Codeword, state: int) -> None:
        self.held -= len(cw.held)
        cw.held, cw.state = [], state

    def _met(self, gid: bytes, key: bytes, length: int) -> _Codeword:
        """A member of scrub codeword `gid` has been read: the first
        one touches the sidecar, which settles the codeword for this
        pass or finds it gone."""
        cw = self.codewords.get(gid)
        if cw is None:
            cw = self.codewords[gid] = _Codeword(
                gid, _SETTLED if self.store._touch(gid) else _PENDING)
        cw.members.append(key)
        cw.maxlen = max(cw.maxlen, length)
        cw.covered += length
        if cw.state == _SETTLED and len(cw.members) == self.k:
            del self.codewords[gid]
            self._count("settled")
            self.store._count_bytes(self.m * cw.maxlen, cw.covered)
        return cw

    def plan(self, hashes: Sequence[Hash], blocks: Sequence[bytes],
             unreadable: Sequence[Hash] = ()) -> BatchPlan:
        """`unreadable`: blocks of the batch that are on the disk and
        gave no bytes (a heal brings them back): members all the same,
        but a codeword cannot be written again without them."""
        store, k = self.store, self.k
        keys = [bytes(h) for h in hashes]
        lost = [bytes(h) for h in unreadable]
        entries = store._entries(keys + lost)
        self._adopt(keys, entries)
        for key in lost:
            gid = store._scrub_gid(entries.get(key))
            if gid is not None and self._met(gid, key, 0).state == _PENDING:
                self._let_go(self.codewords[gid], _BROKEN)
        free, behind, rewrites = [], [], []
        for j, key in enumerate(keys):
            entry = entries.get(key)
            gid = store._scrub_gid(entry)
            if gid is None:
                cover = entry and entry[:GID_LEN]
                if cover and cover not in self.fresh:
                    # the cover of a block that waits for a full row
                    self.fresh.add(cover)
                    store._touch(cover)
                free.append(j)
                continue
            cw = self._met(gid, key, len(blocks[j]))
            if cw.state != _PENDING:
                behind.append(j)
                continue
            if not cw.here:
                rewrites.append(cw)
            cw.here.append(j)
        rows, front, straddlers = [], [], []
        pending = [cw for cw in rewrites if len(cw.members) < k]
        for cw in rewrites:
            if len(cw.members) < k:
                continue
            named = bytes(ParityStore._gid(k, self.m, sorted(
                [bytes(h) for h, _b in cw.held]
                + [keys[j] for j in cw.here]))) == cw.gid
            if not named:       # not the members its name was made of
                self._let_go(cw, _DISSOLVED)
                behind += cw.here
            elif cw.held:
                straddlers.append((cw, cw.held, cw.here))
                self._let_go(cw, _PENDING)
                behind += cw.here
            else:
                rows.append(cw)
                front += sorted(cw.here, key=keys.__getitem__)
            cw.here = []
        # free blocks k at a time in listing order, the carry first: the
        # row the carry is in straddles, the others are this batch's own
        if self.carry and len(self.carry) + len(free) >= k:
            mine = free[:k - len(self.carry)]
            straddlers.append((None, self.carry, mine))
            self.carry, free = [], free[len(mine):]
            behind += mine
        if not self.carry:
            full = len(free) // k * k
            rows += [None] * (full // k)
            front += free[:full]
            free = free[full:]
        for cw in pending:
            behind += cw.here
        self.asked += len(rows) + len(straddlers)
        return BatchPlan(hashes, blocks, front + sorted(behind + free),
                         rows, straddlers, pending, free)

    def filed(self, plan: BatchPlan, ok) -> Tuple[list, list]:
        """The verdicts are in.  → (the front rows whose members all
        verified, to be written from the parity that came back: (row,
        its _Codeword or None, hashes, blocks); the straddling rows
        whose new members verified, to be encoded on the host:
        (codeword or None, hashes, blocks), in codeword order).  What
        waits is kept only as far as it verified: the free blocks short
        of a row, the members of a rewrite that is not whole yet."""
        k = self.k

        def lanes(js):
            return [plan.where[j] for j in js]

        def took(ls):
            return [(plan.hashes[lane], plan.blocks[lane]) for lane in ls]

        sound, host = [], []
        for r, cw in enumerate(plan.rows):
            lo = r * k
            if all(ok[lo:lo + k]):
                sound.append((r, cw, plan.hashes[lo:lo + k],
                              plan.blocks[lo:lo + k]))
            elif cw is not None:
                cw.state = _BROKEN
        for cw, kept, mine in plan.straddlers:
            mine = lanes(mine)
            if all(ok[lane] for lane in mine):
                row = sorted(kept + took(mine), key=lambda hb: bytes(hb[0]))
                host.append((cw, [h for h, _b in row], [b for _h, b in row]))
            elif cw is not None:
                cw.state = _BROKEN
        self.carry += took(lane for lane in lanes(plan.tail) if ok[lane])
        for cw in plan.pending:
            mine = lanes(cw.here)
            cw.here = []
            if all(ok[lane] for lane in mine):
                cw.held += took(mine)
                self.held += len(mine)
            else:
                self._let_go(cw, _BROKEN)
        for cw in self.codewords.values():
            # held for longer than a batch of lanes: the codeword's
            # members are not where a listing keeps them together
            if self.held <= self.most_held:
                break
            if cw.held:
                self._let_go(cw, _DISSOLVED)
        return sound, host

    def wrote(self, cw: Optional[_Codeword], host: bool = False) -> None:
        """A row `filed` named has been put to the store, its parity the
        device's or (`host`) the write-time codewords' road's."""
        self.host += host
        if cw is None:
            self._count("formed")
        else:
            del self.codewords[cw.gid]
            self._count("rewritten")

    @property
    def unsettled(self) -> int:
        """Codewords met and not settled: what `close` has to look at."""
        return len(self.codewords)

    def close(self) -> None:
        """The pass has read its last block.  A codeword a whole pass
        read fewer than k members of has lost one for good, unless a
        member it did not read is still in the store (a read that failed
        this once: the index names the members, the store says whether
        they are there): its survivors' entries become bare, so the next
        pass regroups them while the old sidecar covers them."""
        store, index = self.store, self.store.index
        short = {cw.gid: cw for cw in self.codewords.values()
                 if cw.state != _DISSOLVED and self.whole
                 and len(cw.members) < self.k}
        if short:
            for key, entry in index.items(None, None):
                cw = short.get(store._scrub_gid(entry))
                if (cw is not None and key not in cw.members
                        and store.manager.find_block(Hash(key)) is not None):
                    del short[cw.gid]       # not read, and not gone
        for cw in self.codewords.values():
            if cw.state != _DISSOLVED and cw.gid not in short:
                continue
            for key in cw.members:
                if store._scrub_gid(index.get(key)) == cw.gid:
                    index.insert(key, cw.gid)
            self._count("dissolved")
        self.codewords, self.carry, self.held = {}, [], 0


# Distributed parity shards carry an 8-byte header {magic, salt}: the
# salt is searched so the shard's CONTENT HASH — which is its identity
# and therefore its ring placement — lands on a node carrying no other
# piece of the codeword.  Without it, hash-random placement can stack
# several pieces on one node and a single node loss can exceed m.  With
# it (and the accumulator's distinct-member-node invariant), a codeword
# of k+m pieces occupies k+m distinct nodes whenever the cluster has
# that many — deterministic m-node-loss tolerance, not probabilistic.
PARITY_SHARD_MAGIC = b"GTPS"
PARITY_SHARD_HEADER = 8
_SALT_TRIES = 32


def pack_parity_shard(shard: bytes, salt: int) -> bytes:
    import struct

    return PARITY_SHARD_MAGIC + struct.pack("<I", salt) + shard


def unpack_parity_shard(blob: bytes) -> Optional[bytes]:
    if blob[:4] != PARITY_SHARD_MAGIC:
        return None
    return blob[PARITY_SHARD_HEADER:]


class ParityDistributor:
    """Cross-node half of write-time parity: stores each parity shard as
    an ordinary refcounted BLOCK (ring-placed on the cluster, fetched via
    rpc_get_block, scrubbed like any block) and records the codeword in
    the replicated parity index table, sharded by member hash.  See
    model/parity_index_table.py for the durability economics vs the
    reference's replication-only model."""

    def __init__(self, manager, parity_index_table):
        self.manager = manager
        self.table = parity_index_table
        self.codewords_distributed = 0

    def holds_index_for(self, h: Hash) -> bool:
        """Is this node an index replica for member `h`?  locally_covered
        is only authoritative on such nodes — with data factor > meta
        factor a storing node may NOT hold the index partition, and a
        local miss there means nothing (refreshing from it would mint a
        fresh codeword every scrub pass, forever)."""
        from ..table.schema import hash_partition_key

        me = bytes(self.manager.system.id)
        ph = hash_partition_key(bytes(h))
        return any(bytes(n) == me
                   for n in self.table.replication.read_nodes(ph))

    def locally_covered(self, h: Hash) -> bool:
        """Any live parity-index row for member `h` in the LOCAL store.
        The index is sharded by member hash with the same ring walk as
        block placement, so (when data factor ≤ meta factor) a node
        storing the block also holds its index rows — a local read is
        authoritative once table sync has converged.  Used by the scrub
        worker's coverage refresh: blocks that lost distributed coverage
        (failed distribution, a wrongly-tombstoned codeword, pre-EC
        data) are re-fed to the write accumulator, making coverage
        CONVERGENT instead of write-time-or-never.  Callers must gate on
        holds_index_for (see its docstring) and run this off-loop for
        batches (synchronous DB iteration)."""
        from ..table.schema import hash_partition_key

        data = self.table.data
        prefix = bytes(hash_partition_key(bytes(h)))
        for k, raw in data.store.items(prefix, None):
            if k[:32] != prefix:
                break
            try:
                ent = data.decode_entry(raw)
            except Exception:
                continue
            if not ent.is_tombstone():
                return True
        return False

    def _salted(self, shard: bytes, taken: set) -> tuple:
        """(blob, hash) for the first salt whose placement avoids nodes
        already carrying a piece of this codeword; best-effort after
        _SALT_TRIES (small clusters can't always avoid overlap)."""
        best = None
        for salt in range(_SALT_TRIES):
            blob = pack_parity_shard(shard, salt)
            ph = block_hash(blob, self.manager.hash_algo)
            nodes = self.manager.replication.write_nodes(ph)
            node = bytes(nodes[0]) if nodes else b""
            if node not in taken:
                taken.add(node)
                return blob, ph
            if best is None:
                best = (blob, ph, node)
        blob, ph, node = best
        taken.add(node)
        return blob, ph

    async def distribute(self, hashes: Sequence[Hash],
                         lengths: Sequence[int],
                         parity: np.ndarray) -> None:
        from ..model.parity_index_table import ParityIndexEntry
        from ..utils.crdt import now_msec

        m = int(parity.shape[0])
        k = self.manager.codec.params.rs_data
        # Salted gid: DISTRIBUTED codeword ids must be unique per encode,
        # not deterministic — a revert after a failed index insert leaves
        # a sticky or-merged tombstone under the gid, and a deterministic
        # id would make any later re-encode of the same member set merge
        # into that tombstone and silently yield zero coverage.  (The
        # LOCAL sidecar store keeps the deterministic _gid: its files are
        # refreshed in place each scrub pass and carry no CRDT.)  Cost:
        # two writers racing the same group create two independent
        # codewords — double parity until GC, never wrong coverage.
        gid = blake2s_sum(
            bytes(ParityStore._gid(k, m, hashes)) + os.urandom(8))
        taken = set()
        for h in hashes:
            nodes = self.manager.replication.write_nodes(Hash(h))
            if nodes:
                taken.add(bytes(nodes[0]))
        blobs, phashes = [], []
        for j in range(m):
            blob, ph = self._salted(parity[j].tobytes(), taken)
            blobs.append(blob)
            phashes.append(ph)
        # parity blocks first, index second: the index's member-0 entry
        # refs the parity hashes, and a ref to a not-yet-written block
        # would trigger spurious resync fetches
        for ph, b in zip(phashes, blobs):
            await self.manager.rpc_put_block(ph, b, is_parity=True)
        ts = now_msec()
        entries = [
            ParityIndexEntry(
                member=Hash(h), gid=gid, timestamp=ts, k=k, m=m,
                member_index=i,
                members=[bytes(x) for x in hashes],
                lengths=[int(n) for n in lengths],
                parity_hashes=[bytes(p) for p in phashes],
            )
            for i, h in enumerate(hashes)
        ]
        # The shards are on disk cluster-wide but carry rc only once the
        # index's member-0 row lands (parity_index_table.updated).  If
        # the insert is lost the shards are orphans nothing reclaims, so
        # retry, then on terminal failure mark them Deletable through
        # the ordinary ref machinery (incref+decref → GC delay → reclaim).
        for attempt in range(3):
            try:
                await self.table.insert_many(entries)
                break
            except Exception:
                if attempt == 2:
                    logger.exception(
                        "parity index insert failed for gid %s; "
                        "tombstoning the codeword", bytes(gid).hex()[:16])
                    await self._revert_codeword(entries)
                    return
                await asyncio.sleep(0.5 * (attempt + 1))
        self.codewords_distributed += 1

    async def _revert_codeword(self, entries) -> None:
        """Best-effort revert after a terminal index-insert failure.

        Tombstone the INDEX rows, not the parity block-refs: a quorum
        failure can be a partial success, and a minority node that
        applied a live member-0 row would anti-entropy it cluster-wide
        later.  The or-merged tombstone neutralizes any such row (its
        updated() hook then performs the decref that reclaims the
        shards); if no row was applied anywhere, the shards simply have
        rc = 0 and phase 2 of `repair blocks` hands them to resync,
        which deletes unreferenced local blocks."""
        for e in entries:
            e.deleted.set()
        try:
            await self.table.insert_many(entries)
        except Exception:
            logger.warning(
                "codeword revert insert also failed; shards are rc-less "
                "orphans until the next `repair blocks` pass")


class WriteParityAccumulator:
    """Write-time RS encoding: parity exists from first write, not from
    the first scrub pass 25 days later.

    The reference's put path offers no erasure protection at all — a
    freshly-PUT block is guarded only by replication
    (ref src/api/s3/put.rs:286-360 writes, src/rpc/replication_mode.rs
    durability) — and the scrub-generated sidecars above leave a window
    between write and first scrub.  This accumulator closes the window:
    blocks join an in-progress codeword; when k members accumulate (or
    `flush_after` seconds pass — partial codewords encode against
    implicit zero shards) the parity is encoded OFF the write path (one
    to_thread hop through the codec's gather kernel).  PutObject latency
    is unaffected: the put path only appends bytes it already holds.

    Two deployments with DIFFERENT grouping invariants:
      - storing-node side (`store` set): every block this node stores
        joins a codeword persisted as a LOCAL sidecar — co-location is
        the point (zero-network local repair).
      - writer side (`distributor` set, hooked into rpc_put_block):
        codewords group blocks bound for DISTINCT nodes — add() flushes
        early rather than admit two members placed on the same node, so
        RS(k, m) deterministically survives m member-node losses.
        Grouping on the storing side instead would co-locate all k
        members on the dying node, reducing node-loss tolerance to
        codewords with ≤ m members.

    All mutation happens on the event loop; the encode runs on a
    snapshot in a worker thread.  Blocks deleted before their codeword's
    other members merely cost decode head-room (the sidecar holds m
    parity shards), and the next scrub pass re-groups survivors."""

    def __init__(self, store: Optional[ParityStore], codec,
                 flush_after: float = 5.0,
                 distributor: Optional[ParityDistributor] = None,
                 manager=None):
        self.store = store
        self.codec = codec
        self.flush_after = flush_after
        self.distributor = distributor
        self.manager = manager if manager is not None else (
            store.manager if store is not None else None)
        self._pending: List[tuple] = []  # (hash, DataBlock, healed?)
        self._pending_nodes: set = set()  # primary data node per member
        self._timer: Optional[object] = None  # asyncio.TimerHandle
        self._tasks: set = set()
        self._flushed = asyncio.Event()   # set by every flush (settled)
        metrics = getattr(getattr(self.manager, "system", None), "metrics",
                          None)
        self.m_flushes = None if metrics is None else metrics.counter(
            "write_parity_flushes_total",
            "Write-time codewords flushed to their encode, by cause: "
            "full (k members) | timeout (the flush timer) | node (a "
            "second member bound for one node) | drain (shutdown)")
        if self.m_flushes is not None:
            for cause in FLUSH_CAUSES:          # every series from 0
                self.m_flushes.inc(0, cause=cause)
        # writer-side re-PUT dedup: an OrderedDict-as-LRU of hashes this
        # writer recently wrapped into codewords (bounded; cross-writer
        # repeats still duplicate, which the ref-driven GC cleans up)
        from collections import OrderedDict

        self._recent: "OrderedDict[bytes, None]" = OrderedDict()
        self._recent_cap = 4096
        self.codewords_encoded = 0

    def recently_added(self, h: Hash) -> bool:
        return bytes(h) in self._recent

    def add(self, h: Hash, block: "DataBlock", healed: bool = False) -> None:
        """Register a freshly-written block (`healed`: written back by a
        heal, which its codeword's sidecar is counted to).  Event loop
        only; the block is held as stored (possibly compressed) and
        decompressed on the encode thread, so the write path pays
        nothing."""
        k = self.codec.params.rs_data
        if k <= 0:
            return
        if self.distributor is not None and self.manager is not None:
            self._recent[bytes(h)] = None
            self._recent.move_to_end(bytes(h))
            while len(self._recent) > self._recent_cap:
                self._recent.popitem(last=False)
            # distinct-node invariant for distributed codewords
            nodes = self.manager.replication.write_nodes(h)
            node = bytes(nodes[0]) if nodes else b""
            if node in self._pending_nodes:
                self._flush("node")
            self._pending_nodes.add(node)
        self._pending.append((h, block, healed))
        if len(self._pending) >= k:
            self._flush("full")
        elif self._timer is None:
            loop = asyncio.get_running_loop()
            self._timer = loop.call_later(self.flush_after, self._flush,
                                          "timeout")

    def _flush(self, cause: str) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if not self._pending:
            return
        group, self._pending = self._pending, []
        self._pending_nodes = set()
        if self.m_flushes is not None:
            self.m_flushes.inc(cause=cause)
        task = asyncio.get_running_loop().create_task(
            self._encode_and_store(group, cause)
        )
        # keep a strong ref (create_task results are weakly held)
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)
        self._flushed.set()

    async def _encode_and_store(self, group: List[tuple],
                                cause: str) -> None:
        try:
            hashes = [h for h, _b, _healed in group]
            healed = sum(healed for _h, _b, healed in group)
            origin = "heal" if 2 * healed > len(group) else "write"
            k = self.codec.params.rs_data

            def encode_and_store():
                blocks = [b.decompressed() for _h, b, _healed in group]
                parity = encode_codeword(self.manager, self.codec, blocks)
                if self.store is not None:
                    self.store.put_codeword(
                        hashes, [len(b) for b in blocks], parity, origin)
                return parity, [len(b) for b in blocks]

            def flush():
                # the flush as one section of the encode thread: in the
                # ring (track `write-parity`) and, under `gt:`, in a
                # profile, from the first inflate to the sidecar filed
                with self.codec.obs.timeline.span(
                        "write parity flush", "write-parity", cat="parity",
                        members=len(group), partial=len(group) < k,
                        cause=cause, healed=healed):
                    return encode_and_store()

            parity_row, lengths = await asyncio.to_thread(flush)
            self.codewords_encoded += 1
            if self.distributor is not None:
                await self.distributor.distribute(hashes, lengths, parity_row)
        except Exception:  # noqa: BLE001 — write-path parity is best-effort
            logger.exception("write-time parity encode failed")

    async def settled(self) -> None:
        """Until no block is pending and no encode is in flight.  A
        partial codeword is waited for, its timer's flush and the encode
        after it, not forced (`drain` forces it)."""
        while self._pending or self._tasks:
            if self._tasks:
                await asyncio.gather(*list(self._tasks),
                                     return_exceptions=True)
            else:
                self._flushed.clear()
                await self._flushed.wait()

    async def drain(self) -> None:
        """Flush the partial codeword and wait for in-flight encodes
        (shutdown path — a clean stop must not lose the tail)."""
        self._flush("drain")
        while self._tasks:
            await asyncio.gather(*list(self._tasks), return_exceptions=True)
