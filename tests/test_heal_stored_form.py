"""A block rebuilt on this node goes back to disk in its stored form
(ISSUE 35): the four heal sites write through `BlockManager.
store_rebuilt`, which takes the decision `DataBlock.from_buffer` takes
for a PUT; `_push_row` takes it before its put.  And what the scrub
counts of a store whose blocks are of two forms: the bytes it read by
form, the seconds and bytes of its decompressions, the heals by the
form they took, and which compressor ran."""

import base64
import hashlib
import os
import types

import pytest

from garage_tpu.block import DataBlock
from garage_tpu.block.rebuild import RebuildScheduler
from garage_tpu.block.repair import ScrubWorker
from garage_tpu.utils.data import Hash, blake2s_sum
from garage_tpu.utils.zstd_compat import COMPRESSOR, HAVE_ZSTD, zstandard

N = 48 * 1024


def _content(make: str) -> bytes:
    raw = os.urandom(N)
    return base64.b64encode(raw)[:N] if make == "base64" else raw


class _Sidecars:
    """A parity store that rebuilds what it is told it can."""

    def __init__(self, held: dict):
        self.held = held

    def try_reconstruct(self, h):
        return self.held.get(bytes(h))


async def _scrub_heal(m, h, data):
    await m.write_block(h, DataBlock.from_buffer(data, m.compression_level))
    m.parity_store = _Sidecars({bytes(h): data})
    path, _ = m.find_block(h)
    with open(path, "r+b") as f:
        f.seek(len(data) // 3)
        f.write(b"\xff\x00\xff\x00")
    assert await ScrubWorker(m)._quarantine(h, path) == "local_sidecar"


async def _resync_sidecar(m, h, data):
    m.parity_store = _Sidecars({bytes(h): data})
    m.db.transaction(lambda tx: m.rc.block_incref(tx, h))
    assert await m.resync.resync_block(h) == len(data)


async def _resync_decode(m, h, data):
    # no sidecar, no replica that answers, no displaced copy: the
    # distributed decode
    async def reconstructor(_h):
        return data

    async def unreachable(*_a, **_kw):
        raise ConnectionError("replicas unreachable")

    m.parity_store = None
    m.rpc_get_raw_block = unreachable
    m.parity_reconstructor = reconstructor
    m.db.transaction(lambda tx: m.rc.block_incref(tx, h))
    assert await m.resync.resync_block(h) == len(data)


async def _rebuild(m, h, data):
    async def decode(_h, _ent):
        return data

    ent = types.SimpleNamespace(k=1, m=1, member_index=0, members=[bytes(h)],
                                lengths=[len(data)], parity_hashes=[])
    sched = RebuildScheduler(m, m.resync, decode_fallback=decode)
    assert await sched._rebuild_codeword(h, ent) == len(data)


SITES = {"scrub_sidecar": (_scrub_heal, "local_sidecar"),
         "resync_sidecar": (_resync_sidecar, "local_sidecar"),
         "resync_decode": (_resync_decode, "distributed_decode"),
         "rebuild": (_rebuild, "rebuild")}
# (what the content is made of, the node's level) → the form on disk
CASES = {"compressible": ("base64", 1, "zst"),
         "incompressible": ("random", 1, "plain"),
         "compression_off": ("base64", None, "plain")}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("site", sorted(SITES))
async def test_a_rebuilt_block_takes_its_stored_form(tmp_path, site, case):
    from tests.test_block import make_block_cluster
    from tests.test_table import shutdown

    make, level, form = CASES[case]
    heal, source = SITES[site]
    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    m.compression_level = level
    m.blocks_reconstructed = 0
    data = _content(make)
    h = blake2s_sum(data)
    await heal(m, h, data)
    path, compressed = m.find_block(h)
    name = os.path.basename(path)
    assert name == bytes(h).hex() + (".zst" if form == "zst" else "")
    assert compressed == (form == "zst")
    raw = open(path, "rb").read()
    if form == "zst":
        assert len(raw) < len(data)
        raw = zstandard.ZstdDecompressor().decompress(raw)
    assert hashlib.blake2s(raw, digest_size=32).digest() == bytes(h)
    assert m.heal_counts == {source: 1}
    assert m.m_heal_stored.get(form=form) == 1
    assert m.m_heal_stored.get(form="plain" if form == "zst" else "zst") == 0
    # and the block is served as what it was
    assert (await m.read_block(h)).decompressed() == data
    await shutdown(systems)


@pytest.mark.parametrize("case", sorted(CASES))
async def test_a_pushed_row_is_sent_in_its_stored_form(tmp_path, case):
    """`_push_row`: the owner stores what it is sent, so the sender
    decides the form, as `rpc_put_block` does."""
    from tests.test_block import make_block_cluster
    from tests.test_table import shutdown

    make, level, form = CASES[case]
    systems, managers = await make_block_cluster(tmp_path, n=2, mode="2")
    src, dst = managers
    src.compression_level = level
    data = _content(make)
    h = blake2s_sum(data)
    sched = RebuildScheduler(src, src.resync)
    assert await sched._push_row(h, data, dst.system.id)
    path, compressed = dst.find_block(h)
    assert compressed == (form == "zst")
    assert os.path.basename(path).endswith(".zst") == (form == "zst")
    assert (await dst.read_block(h)).decompressed() == data
    assert src.find_block(h) is None        # pushed, not kept
    await shutdown(systems)


async def _one_pass(worker):
    worker.send_command("start")
    while (await worker.work()).name in ("BUSY", "THROTTLED"):
        pass


async def test_a_pass_over_a_mixed_store_counts_what_the_files_say(tmp_path):
    from garage_tpu.block.parity import ParityStore
    from garage_tpu.db import open_db
    from tests.test_block import make_block_cluster
    from tests.test_table import shutdown

    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    m.blocks_reconstructed = 0
    m.parity_store = ParityStore(m, open_db("memory"), m.codec)
    contents = {}
    for i in range(16):
        d = _content("base64" if i % 2 else "random")
        contents[bytes(blake2s_sum(d))] = d
        await m.write_block(blake2s_sum(d), DataBlock.from_buffer(d, 1))
    on_disk = {"zst": 0, "plain": 0}
    inflated = 0
    for hb, d in contents.items():
        path, compressed = m.find_block(Hash(hb))
        on_disk["zst" if compressed else "plain"] += os.path.getsize(path)
        inflated += len(d) * compressed
    assert on_disk["zst"] and on_disk["plain"]

    w = ScrubWorker(m)
    await _one_pass(w)
    assert w.state.corruptions == 0
    assert w.m_read.get(form="zst") == on_disk["zst"]
    assert w.m_read.get(form="plain") == on_disk["plain"]
    assert w.m_inflate_bytes.get(dir="in") == on_disk["zst"]
    assert w.m_inflate_bytes.get(dir="out") == inflated
    assert w.m_bytes.get() == sum(map(len, contents.values()))
    # the decompressions ran on the I/O lane's threads, beside the reads:
    # in the read-ahead's `read files` events, in no segment of the worker
    assert w.m_inflate_s.get() > 0
    assert w.m_segments.get(segment="decompress") == 0
    evs = [e["args"] for e in m.codec.obs.timeline.snapshot()
           if e["name"] == "read files"]
    assert sum(a["blocks"] for a in evs) == 16
    assert sum(a["inflated"] for a in evs) == 8
    assert sum(a["bytes"] for a in evs) == sum(on_disk.values())
    assert abs(sum(a["inflate_ms"] for a in evs) / 1e3
               - w.m_inflate_s.get()) < 1e-3 * len(evs)

    # one block of each form corrupted: each heals back into its form
    for want in (True, False):
        hb = next(k for k in contents if m.find_block(Hash(k))[1] == want)
        path, _ = m.find_block(Hash(hb))
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) // 2)
            b = f.read(1)
            f.seek(os.path.getsize(path) // 2)
            f.write(bytes([b[0] ^ 0x40]))
    n_events = len(m.codec.obs.timeline.snapshot())
    await _one_pass(w)
    assert w.state.corruptions == 2
    # the frame that did not decode kept its lane: no codeword after it
    # changed its members, so none was encoded and written anew
    second = m.codec.obs.timeline.snapshot()[n_events:]
    asks = [e["args"] for e in second if e["name"] == "parity ask"]
    assert asks and sum(a["lacking"] for a in asks) == 0
    assert not [e for e in second if e["name"] == "parity write"]
    assert m.m_heal_stored.get(form="zst") == 1
    assert m.m_heal_stored.get(form="plain") == 1
    for hb, d in contents.items():
        assert (await m.read_block(Hash(hb))).decompressed() == d
    now = {"zst": 0, "plain": 0}
    for hb in contents:
        path, compressed = m.find_block(Hash(hb))
        now["zst" if compressed else "plain"] += os.path.getsize(path)
    assert now == on_disk       # the disk holds what it held
    await shutdown(systems)


def test_a_header_that_states_an_absurd_size_is_a_frame_that_does_not_decode():
    """Bit 6 of a frame's fifth byte widens its content-size field: the
    wheel raises MemoryError there, not ZstdError."""
    from garage_tpu.block.repair import _try_decompress

    text = base64.b64encode(os.urandom(1 << 20))[:1 << 20]     # 4-byte size
    frame = bytearray(DataBlock.from_buffer(text, 1).inner)
    assert _try_decompress(bytes(frame)) is not None
    frame[4] ^= 0x40
    assert _try_decompress(bytes(frame)) is None


async def test_codec_info_names_the_compressor(tmp_path):
    from garage_tpu.admin import AdminRpcHandler
    from garage_tpu.model import Garage
    from garage_tpu.utils.config import config_from_dict

    g = Garage(config_from_dict({
        "metadata_dir": str(tmp_path / "meta"),
        "data_dir": str(tmp_path / "data"),
        "replication_mode": "none",
        "rpc_bind_addr": "127.0.0.1:0",
        "rpc_secret": "x",
        "bootstrap_peers": [],
        "codec": {"backend": "cpu"},
    }))
    try:
        admin = AdminRpcHandler(g, register_endpoint=False)
        info = await admin._cmd_codec_info({})
    finally:
        await g.shutdown()
    assert info["compressor"] == COMPRESSOR
    if HAVE_ZSTD:
        import zstandard as wheel

        assert COMPRESSOR == f"zstandard {wheel.__version__}"
    else:
        assert COMPRESSOR == "zlib-fallback"
