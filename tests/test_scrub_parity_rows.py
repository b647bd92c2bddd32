"""Parity leaves the device only for the codewords that lack a sidecar
(ISSUE 33): the store says which rows those are and puts them in front
(ISSUE 44: by what the parity index says of each block, not by the
block's place in the batch), the submission names them, the transport
brings back those rows and no others, and the rest of the batch is
settled without its parity.

Every parity compared is held to RS(k, m) written from the field's
definition (`benchmarks/reference.py`, which imports nothing of the
program); what crossed the link is the synthetic device's own count of
the bytes its `scrub_collect` handed back.
"""

import hashlib
import os
import time
import types

import msgpack
import numpy as np
import pytest

from benchmarks import reference
from garage_tpu.block import DataBlock
from garage_tpu.block.parity import ParityStore
from garage_tpu.block.repair import ScrubWorker
from garage_tpu.db import open_db
from garage_tpu.ops.cpu_codec import CpuCodec
from garage_tpu.ops.transport import DeviceTransport, TransportItem
from garage_tpu.testing.synthetic_device import SyntheticLinkCodec
from garage_tpu.utils.data import Hash
from garage_tpu.utils.metrics import MetricsRegistry
from tests.test_device_pool import K, M, _blocks, _params


def _reference_row(blocks, row, k=K, m=M):
    members = blocks[row * k:(row + 1) * k]
    return reference.codeword_parity(members, max(map(len, members)), k, m)


# --- (a) the store's answer ----------------------------------------------


def _store(tmp_path, dirs=("d0",), k=K, m=M, metrics=None):
    """A ParityStore over plain directories: no cluster, the CPU codec."""
    layout = types.SimpleNamespace(data_dirs=[
        types.SimpleNamespace(path=str(tmp_path / d), read_only=False)
        for d in dirs])
    manager = types.SimpleNamespace(
        data_layout=layout, system=types.SimpleNamespace(metrics=metrics))
    return ParityStore(manager, open_db("memory"),
                       CpuCodec(_params(rs_data=k, rs_parity=m)))


def _sorted_blocks(n, seed):
    """Blocks in id order, as a pass's listing gives them."""
    pairs = sorted(zip(*_blocks(n, seed=seed)[::-1]),
                   key=lambda hb: bytes(hb[0]))
    return [b for _h, b in pairs], [h for h, _b in pairs]


def _put_rows(store, blocks, hashes, rows):
    k = store.codec.params.rs_data
    for r in rows:
        members = blocks[r * k:(r + 1) * k]
        assert store.put_codeword(
            hashes[r * k:(r + 1) * k], [len(b) for b in members],
            store.codec.rs_encode_blocks(members)[0])


def _front(plan, k=K):
    """The front rows of a plan, as their members' ids."""
    return [[bytes(h) for h in plan.hashes[r * k:(r + 1) * k]]
            for r in plan.want]


def _plan_with_a_carry(tmp_path):
    """Free blocks short of a row wait, verified, and are never
    submitted again: a batch goes to the codec with its own lanes and
    no other.  The free block that makes their row whole stands behind
    like a settled lane, and the row is handed over to be encoded on
    the host once that block has verified."""
    store = _store(tmp_path)
    blocks, hashes = _sorted_blocks(4 * K, seed=1)
    _put_rows(store, blocks, hashes, [1, 2])
    pass_ = store.begin_pass(True)
    first = pass_.plan(hashes[:K - 1], blocks[:K - 1])
    assert first.want == [] and first.hashes == hashes[:K - 1]
    assert pass_.filed(first, [True] * (K - 1)) == ([], [])
    # K settled blocks, then the free block that makes the carry a row
    batch_h = hashes[K:2 * K] + hashes[K - 1:K]
    batch_b = blocks[K:2 * K] + blocks[K - 1:K]
    plan = pass_.plan(batch_h, batch_b)
    assert plan.want == [] and plan.hashes == batch_h
    assert plan.where == list(range(K + 1))
    sound, ((cw, row_h, row_b),) = pass_.filed(plan, [True] * (K + 1))
    assert sound == [] and cw is None
    assert (row_h, row_b) == (hashes[:K], blocks[:K])
    assert store.put_straddler(row_h, row_b)
    pass_.wrote(cw)
    assert pass_.counts == {"settled": 1, "rewritten": 0, "formed": 1,
                            "dissolved": 0} and pass_.carry == []
    assert store.begin_pass(True).plan(hashes[:K], blocks[:K]).want == []
    # a free block that failed its verify is not carried
    plan = pass_.plan(hashes[3 * K:3 * K + 2], blocks[3 * K:3 * K + 2])
    pass_.filed(plan, [True, False])
    assert [h for h, _b in pass_.carry] == hashes[3 * K:3 * K + 1]


def _plan_with_a_partial_last_row(tmp_path):
    """The trailing free blocks are no codeword yet: never named,
    whatever is on disk; a free row is k free blocks in listing order,
    the settled ones between them skipped."""
    store = _store(tmp_path)
    blocks, hashes = _sorted_blocks(3 * K + 3, seed=3)
    pass_ = store.begin_pass(True)
    plan = pass_.plan(hashes, blocks)
    assert plan.want == [0, 1, 2] and plan.hashes == hashes
    assert plan.tail == [3 * K, 3 * K + 1, 3 * K + 2]
    _put_rows(store, blocks, hashes, [1])
    plan = store.begin_pass(True).plan(hashes, blocks)
    free = hashes[:K] + hashes[2 * K:]
    assert _front(plan) == [[bytes(h) for h in free[:K]],
                            [bytes(h) for h in free[K:2 * K]]]
    assert store.begin_pass(True).plan(
        hashes[:K - 1], blocks[:K - 1]).want == []


def _plan_with_a_second_data_dir(tmp_path):
    """A sidecar written before the layout changed, in a dir that is no
    longer the one written to, is present: touched there, its codeword
    settled, no row asked for."""
    old = _store(tmp_path, dirs=("d1",))
    blocks, hashes = _sorted_blocks(2 * K, seed=4)
    _put_rows(old, blocks, hashes, [1])
    store = _store(tmp_path, dirs=("d0", "d1"))
    store.index = old.index
    assert store.dir.startswith(str(tmp_path / "d0"))
    (path,) = [os.path.join(d, n) for d, _s, ns in os.walk(old.dir)
               for n in ns]
    os.utime(path, (1, 1))
    pass_ = store.begin_pass(True)
    plan = pass_.plan(hashes, blocks)
    assert _front(plan) == [[bytes(h) for h in hashes[:K]]]
    assert pass_.counts["settled"] == 1 and os.stat(path).st_mtime > 1


def _plan_after_a_geometry_change(tmp_path):
    """An entry that names a codeword of another (k, m) is no place:
    its block is free, and regrouped at the geometry of now."""
    blocks, hashes = _sorted_blocks(2 * K, seed=5)
    was = _store(tmp_path)
    _put_rows(was, blocks, hashes, [0, 1])
    assert was.begin_pass(True).plan(hashes, blocks).want == []
    for kw, rows in (({"m": M + 1}, 2), ({"k": K // 2}, 4)):
        store = _store(tmp_path, **kw)
        store.index = was.index
        plan = store.begin_pass(True).plan(hashes, blocks)
        assert plan.want == list(range(rows)) and plan.hashes == hashes


def _plan_with_an_unreadable_member(tmp_path):
    """A block that is on the disk and gave no bytes (the heal brings it
    back) is a member read all the same: its codeword is settled, not
    dissolved at the pass's end; one whose sidecar is gone too waits for
    the next pass."""
    store = _store(tmp_path)
    blocks, hashes = _sorted_blocks(2 * K, seed=8)
    _put_rows(store, blocks, hashes, [0, 1])
    os.remove(store._find_group_path(bytes(store._gid(K, M, hashes[K:]))))
    index = dict(store.index.items(None, None))
    pass_ = store.begin_pass(True)
    plan = pass_.plan(hashes[1:K + 2] + hashes[K + 3:],
                      blocks[1:K + 2] + blocks[K + 3:],
                      [hashes[0], hashes[K + 2]])
    assert plan.want == [] and len(plan.hashes) == 2 * K - 2
    assert pass_.filed(plan, [True] * len(plan.hashes)) == ([], [])
    pass_.close()
    assert pass_.counts == {"settled": 1, "rewritten": 0, "formed": 0,
                            "dissolved": 0}
    assert dict(store.index.items(None, None)) == index


@pytest.mark.parametrize("case", [
    _plan_with_a_carry, _plan_with_a_partial_last_row,
    _plan_with_a_second_data_dir, _plan_after_a_geometry_change,
    _plan_with_an_unreadable_member],
    ids=lambda f: f.__name__[6:])
def test_the_plan_of_a_batch(tmp_path, case):
    case(tmp_path)


def test_settled_counts_and_touches_like_a_put(tmp_path):
    """A codeword found settled against `put_codeword` over the same
    rows on two stores: the same bytes counted (the parity's from the
    lengths), a fresh mtime on every file, the index as it was; and a
    file that is gone is asked for, in front, and written there with
    the reference's bytes."""
    blocks, hashes = _sorted_blocks(3 * K, seed=6)
    regs = MetricsRegistry(), MetricsRegistry()
    by_put = _store(tmp_path / "a", metrics=regs[0])
    by_plan = _store(tmp_path / "b", metrics=regs[1])
    for store in (by_put, by_plan):
        _put_rows(store, blocks, hashes, [0, 1, 2])
    counted = [{p: r.counter("parity_sidecar_bytes_total").get(part=p)
                for p in ("parity", "covered")} for r in regs]
    assert counted[0] == counted[1]
    files = sorted(os.path.join(d, n) for d, _s, ns in os.walk(
        by_plan.dir) for n in ns)
    assert len(files) == 3
    for f in files:
        os.utime(f, (1, 1))
    gone = by_plan._find_group_path(bytes(by_plan._gid(
        K, M, hashes[K:2 * K])))
    os.remove(gone)
    index = dict(by_plan.index.items(None, None))
    # the second filing of the same rows, either way
    for r in range(3):
        members = blocks[r * K:(r + 1) * K]
        assert not by_put.put_codeword(
            hashes[r * K:(r + 1) * K], [len(b) for b in members],
            by_put.codec.rs_encode_blocks(members)[0])
    pass_ = by_plan.begin_pass(True)
    plan = pass_.plan(hashes, blocks)
    assert _front(plan) == [[bytes(h) for h in hashes[K:2 * K]]]
    ((row, cw, row_h, row_b),), _none = pass_.filed(
        plan, [True] * len(hashes))
    assert by_plan.put_codeword(
        row_h, [len(b) for b in row_b],
        by_plan.codec.rs_encode_blocks(row_b)[0])
    pass_.wrote(cw)
    assert pass_.counts == {"settled": 2, "rewritten": 1, "formed": 0,
                            "dissolved": 0} and not pass_.unsettled
    for reg, first in zip(regs, counted):
        c = reg.counter("parity_sidecar_bytes_total")
        assert {p: c.get(part=p) for p in first} == {
            p: 2 * n for p, n in first.items()}
    assert counted[0]["parity"] == sum(
        M * max(map(len, blocks[r * K:(r + 1) * K])) for r in range(3))
    assert all(os.stat(f).st_mtime > 1 for f in files)
    assert by_plan.purge_stale(time.time() - 60) == 0
    assert dict(by_plan.index.items(None, None)) == index
    assert all(by_plan.coverage(h) for h in hashes)
    with open(gone, "rb") as f:
        man = msgpack.unpackb(f.read(), raw=False)
    assert man["lengths"] == [len(b) for b in blocks[K:2 * K]]
    assert np.array_equal(
        np.stack([np.frombuffer(p, np.uint8) for p in man["parity"]]),
        _reference_row(blocks, 1))


# --- (b) the transport brings back the rows named, and no others ---------


def _transport(metrics=None, raises_at_collect=False):
    p = _params()

    class _Device(SyntheticLinkCodec):
        def scrub_collect(self, out, parity_rows):
            if raises_at_collect:
                raise RuntimeError("device gone at collect")
            return super().scrub_collect(out, parity_rows)

    dev = _Device(p, link_gibs=100.0, compute_real=True)
    return DeviceTransport(dev, p, fallback=CpuCodec(p),
                           metrics=metrics), dev


def _items(wants, n=5 * K + 3):
    out = []
    for i, want in enumerate(wants):
        blocks, hashes = _blocks(n, seed=40 + i)
        out.append(TransportItem("scrub", (blocks, hashes), n,
                                 sum(map(len, blocks)), want_parity=want))
    return out


def _check_rows(item, parity):
    """Exactly the rows the item named, each the reference's parity of
    its members at the row's own width; → their bytes."""
    blocks = item.payload[0]
    assert sorted(parity) == sorted(item.want_parity)
    for r, got in parity.items():
        assert got.dtype == np.uint8 and got.flags.c_contiguous
        assert np.array_equal(got, _reference_row(blocks, r)), r
    return sum(int(a.nbytes) for a in parity.values())


def _rows_of_one_item(cut):
    """One item, whole or cut into parts at multiples of k: a part's
    rows are the item's less lo ÷ k, and come back under the item's
    numbers.  The ragged last row (3 of k members) is a row like any."""
    reg = MetricsRegistry()
    tr, dev = _transport(metrics=reg)
    if cut:
        tr.chunk_bytes, tr.budget_bytes = 8 << 10, 32 << 10
    (it,) = _items([[5, 0, 3]])
    tr.submit_items("scrub", [it])
    ok, parity = it.future.result(timeout=60)
    assert ok.all() and (tr.chunks_split > 0) == cut
    # a row crosses at its batch's width and is trimmed on the host
    assert _check_rows(it, parity) <= dev.bytes_fetched <= 3 * M * 4096
    assert cut or dev.bytes_fetched == 3 * M * 4096
    rows = reg.counter("scrub_parity_rows_total")
    assert (rows.get(fetch="fetched"), rows.get(fetch="left")) == (3, 3)
    collects = [e["args"] for e in tr.obs.timeline.snapshot()
                if e["name"] == "collect scrub"]
    assert sum(c["parity_rows"] for c in collects) == 3
    assert sum(c["parity_bytes"] for c in collects) == dev.bytes_fetched
    tr.shutdown()


def _rows_of_two_items_in_one_batch(_cut):
    """Two items coalesced into one dispatch, a third that names none
    and a fourth with no use for parity: each is answered for its own
    rows, under its own numbers."""
    reg = MetricsRegistry()
    tr, dev = _transport(metrics=reg)
    items = _items([[1, 4], [0, 5], [], False])
    tr.submit_items("scrub", items)
    got = [it.future.result(timeout=60) for it in items]
    assert dev.array_submissions == 1, "the items were not coalesced"
    assert all(ok.all() for ok, _p in got)
    assert got[2][1] is None and got[3][1] is None
    assert sum(_check_rows(it, par) for it, (_ok, par)
               in zip(items[:2], got)) <= dev.bytes_fetched == 4 * M * 4096
    rows = reg.counter("scrub_parity_rows_total")
    # 6 rows an item; the item without a use for parity counts nothing
    assert (rows.get(fetch="fetched"), rows.get(fetch="left")) == (4, 14)
    tr.shutdown()


def _no_row_wanted(_cut):
    """No row named: the batch is collected as one that wants no parity
    (`rep3-1m.scrub`'s road) and not a byte of it crosses."""
    seen = []
    tr, dev = _transport()
    collect = dev.scrub_collect
    dev.scrub_collect = lambda out, rows: seen.append(rows) or collect(
        out, rows)
    for want in ([], False):
        (it,) = _items([want])
        tr.submit_items("scrub", [it])
        ok, parity = it.future.result(timeout=60)
        assert ok.all() and parity is None
    assert seen == [[], []] and dev.bytes_fetched == 0
    tr.shutdown()


def _every_row_wanted(_cut):
    """Every row named, and `want_parity=True`: the array, as before;
    the rows named one by one hold the same bytes."""
    tr, dev = _transport()
    named, whole = _items([list(range(6)), True])
    whole.payload = named.payload
    for it in (named, whole):
        tr.submit_items("scrub", [it])
    (_ok, by_row), (_ok2, array) = (it.future.result(timeout=60)
                                    for it in (named, whole))
    blocks = named.payload[0]
    assert array.shape == (6, M, max(map(len, blocks)))
    assert np.array_equal(
        array, CpuCodec(_params()).rs_encode_blocks(blocks))
    _check_rows(named, by_row)
    for r, row in by_row.items():
        assert np.array_equal(row, array[r][:, :row.shape[1]])
        assert not array[r][:, row.shape[1]:].any()
    tr.shutdown()


def _device_fails_at_collect(cut):
    """(e) The device dies under the batch at collect: the floor
    encodes the rows named, of every part, and answers in the same
    shape."""
    tr, dev = _transport(raises_at_collect=True)
    if cut:
        tr.chunk_bytes, tr.budget_bytes = 8 << 10, 32 << 10
    items = _items([[5, 2], True, []])
    tr.submit_items("scrub", items)
    got = [it.future.result(timeout=60) for it in items]
    assert tr.fallbacks > 0 and dev.bytes_fetched == 0
    assert all(ok.all() for ok, _p in got)
    _check_rows(items[0], got[0][1])
    assert np.array_equal(got[1][1], CpuCodec(_params()).rs_encode_blocks(
        items[1].payload[0]))
    assert got[2][1] is None
    tr.shutdown()


@pytest.mark.parametrize("case,cut", [
    (_rows_of_one_item, False), (_rows_of_one_item, True),
    (_rows_of_two_items_in_one_batch, False), (_no_row_wanted, False),
    (_every_row_wanted, False), (_device_fails_at_collect, False),
    (_device_fails_at_collect, True)],
    ids=lambda v: v.__name__[1:] if callable(v) else ("", "cut")[v])
def test_transport_brings_back_the_rows_named(case, cut):
    case(cut)


@pytest.mark.parametrize("codec", ["cpu", "tpu"])
def test_the_floor_and_the_device_codec_answer_in_one_shape(codec):
    """`scrub_encode_batch` with rows named, on the CPU floor (which
    encodes those rows and no others) and on the device codec's
    synchronous road: a dict of the rows, the reference's bytes; a row
    out of range is refused, not clamped."""
    p = _params()
    if codec == "tpu":
        from garage_tpu.ops.tpu_codec import TpuCodec

        c = TpuCodec(p)
    else:
        c = CpuCodec(p)
        encoded = []
        real = c.rs_encode_blocks
        c.rs_encode_blocks = lambda bl: encoded.append(len(bl)) or real(bl)
    blocks, hashes = _blocks(3 * K + 2, seed=7)
    blocks[1] = blocks[1][:-1] + bytes([blocks[1][-1] ^ 1])
    ok, parity = c.scrub_encode_batch(blocks, hashes, [3, 1])
    assert ok.tolist() == [i != 1 for i in range(len(blocks))]
    assert sorted(parity) == [1, 3]
    for r, got in parity.items():
        width = max(map(len, blocks[r * K:(r + 1) * K]))
        assert np.array_equal(got[:, :width], _reference_row(blocks, r))
        assert not got[:, width:].any()
    if codec == "cpu":
        assert encoded == [K + 2]       # row 1 and the ragged row 3
    assert c.scrub_encode_batch(blocks, hashes, [])[1] is None
    with pytest.raises(ValueError):
        c.scrub_encode_batch(blocks, hashes, [4])


# --- (c), (d) the worker: what a pass leaves on disk ---------------------


async def _pass(worker):
    worker.send_command("start")
    while (await worker.work()).name in ("BUSY", "THROTTLED"):
        pass


def _sidecar_files(store):
    return sorted(os.path.join(d, n) for d, _s, ns in os.walk(store.dir)
                  for n in ns if n.endswith(".par"))


def _held_to_the_reference(path, blocks):
    with open(path, "rb") as f:
        man = msgpack.unpackb(f.read(), raw=False)
    raws = [blocks[bytes(h)] for h in man["hashes"]]
    assert man["lengths"] == [len(r) for r in raws]
    assert man["maxlen"] == max(man["lengths"])
    assert np.array_equal(
        np.stack([np.frombuffer(p, np.uint8) for p in man["parity"]]),
        reference.codeword_parity(raws, man["maxlen"], 8, 4))


async def test_a_pass_files_by_the_batch_on_the_floor(tmp_path):
    """Three passes over one store through the feeder on the CPU floor.
    The first names every row and writes every sidecar.  Before the
    second two are removed: it names those two, writes them again under
    their names with the reference's bytes and touches the rest,
    counting the bytes the first counted (so the purge spares what it
    spared).  Before the third a block is flipped and its codeword's
    sidecar stays: no row is named, the block heals from that sidecar
    and every byte is counted again."""
    from tests.test_block import make_block_cluster
    from tests.test_table import shutdown

    systems, (m,) = await make_block_cluster(tmp_path, n=1, mode="1")
    m.blocks_reconstructed = 0
    store = m.parity_store = ParityStore(m, open_db("memory"), m.codec)
    k = m.codec.params.rs_data
    blocks = {}
    for i in range(5 * k + 3):
        d = os.urandom(3000 + 211 * (i % 7))
        h = hashlib.blake2s(d, digest_size=32).digest()
        blocks[h] = d
        await m.write_block(Hash(h), DataBlock.plain(d))
    counter = systems[0].metrics.counter("parity_sidecar_bytes_total")

    def counted():
        return {p: counter.get(part=p) for p in ("parity", "covered")}

    def events(name):
        return [e["args"] for e in m.codec.obs.timeline.snapshot()
                if e["name"] == name]

    worker = ScrubWorker(m)
    await _pass(worker)
    files = _sidecar_files(store)
    first = counted()
    assert len(files) == 5
    assert sum(a["lacking"] for a in events("parity ask")) == 5
    assert first["covered"] == sum(
        len(blocks[h]) for h in sorted(blocks)[:5 * k])
    for f in files:
        _held_to_the_reference(f, blocks)

    # two sidecars gone before the pass
    for f in files:
        os.utime(f, (1, 1))
    os.remove(files[1])
    os.remove(files[3])
    t0 = time.time()
    asks, writes = len(events("parity ask")), len(events("parity write"))
    await _pass(worker)
    assert sum(a["lacking"] for a in events("parity ask")[asks:]) == 2
    assert _sidecar_files(store) == files
    assert all(os.stat(f).st_mtime >= t0 - 1 for f in files)
    assert counted() == {p: 2 * n for p, n in first.items()}
    for f in (files[1], files[3]):
        _held_to_the_reference(f, blocks)
    assert (sum(w["written"] for w in events("parity write")[writes:]),
            sum(w["touched"] for w in events("parity write")[writes:])) == (
        2, 0)
    assert {s: events("scrub pass")[-1][s] for s in (
        "settled", "rewritten", "formed")} == {
        "settled": 3, "rewritten": 2, "formed": 0}

    # a flipped block: nothing is named, its codeword stays and heals it
    victim = sorted(blocks)[2 * k + 1]
    with open(m.find_block(Hash(victim))[0], "r+b") as f:
        f.write(bytes([blocks[victim][0] ^ 1]))
    asks = len(events("parity ask"))
    await _pass(worker)
    assert sum(a["lacking"] for a in events("parity ask")[asks:]) == 0
    assert _sidecar_files(store) == files
    assert counted() == {p: 3 * n for p, n in first.items()}
    assert all(store.coverage(Hash(h)) for h in sorted(blocks)[:5 * k])
    assert worker.state.corruptions == 1 == m.blocks_reconstructed
    with open(m.find_block(Hash(victim))[0], "rb") as f:
        assert f.read() == blocks[victim]
    if m.feeder is not None:
        m.feeder.shutdown()
    await shutdown(systems)
