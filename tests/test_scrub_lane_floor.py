"""The lane floor of a scrub batch (ISSUE 40): where the fused Pallas
road will take it, a batch under 128 lanes is padded ON THE DEVICE to a
row the Pallas kernels tile, and the host stages, sends and budgets the
rows it staged before.

The Pallas road is forced on the CPU's devices: the codec is told its
device is one Mosaic compiles for, and its fused program is built with
the hash kernel in the Pallas interpreter.  Every verdict and parity is
held to the same batch at today's geometry (the XLA fused program) and
to the CPU codec.
"""

import hashlib

import jax
import numpy as np
import pytest

from garage_tpu.ops.cpu_codec import CpuCodec
from garage_tpu.ops.device_pool import DevicePool
from garage_tpu.ops.tpu_codec import (SCRUB_LANE_FLOOR, TpuCodec,
                                      scrub_fused_pallas_step)
from garage_tpu.ops.transport import DeviceTransport, TransportItem
from garage_tpu.testing.synthetic_device import SyntheticLinkCodec
from garage_tpu.utils.data import Hash
from garage_tpu.utils.metrics import MetricsRegistry
from tests.test_device_pool import K, M, _blocks, _params, _scrub

N = 61                  # blocks: 64 lanes at today's geometry
FLIPPED, SHORT = 17, 40


def _force_pallas(codec: TpuCodec) -> TpuCodec:
    codec._mosaic_device = lambda: True
    codec._scrub_pallas_jit = jax.jit(
        scrub_fused_pallas_step(None, interpret=True), static_argnums=(4,))
    return codec


def _transport(device: str, pooled: bool, floored: bool):
    p = _params()
    reg = MetricsRegistry()
    if device == "tpu":
        dev = TpuCodec(p, metrics=reg)
        if floored:
            _force_pallas(dev)
        obs = dev.obs
    else:
        dev = SyntheticLinkCodec(p, link_gibs=100.0, compute_real=True)
        if floored:
            dev.scrub_device_lanes = lambda lanes: max(lanes,
                                                       SCRUB_LANE_FLOOR)
        obs = None
    pool = DevicePool(dev, pool_bytes=1 << 20, page_bytes=1024,
                      metrics=reg) if pooled else None
    tr = DeviceTransport(dev, p, fallback=CpuCodec(p), observer=obs,
                         metrics=reg, pool=pool)
    return tr, pool, reg


def _batch():
    """61 blocks: one whose bytes are not its id's, one short."""
    blocks, hashes = _blocks(N, seed=40, sizes=(1024,))
    blocks[FLIPPED] = bytes([blocks[FLIPPED][0] ^ 1]) + blocks[FLIPPED][1:]
    blocks[SHORT] = blocks[SHORT][:77]
    hashes[SHORT] = Hash(hashlib.blake2s(blocks[SHORT],
                                         digest_size=32).digest())
    return blocks, hashes


def _events(tr, name):
    return [e for e in tr.obs.timeline.snapshot()
            if e["name"] == name]


def _lanes_counted(reg):
    c = reg.counter("scrub_device_lanes_total")
    return c.get(part="content"), c.get(part="pad")


@pytest.mark.parametrize("pooled", (True, False),
                         ids=("pooled", "unpooled"))
@pytest.mark.parametrize("device", ("tpu", "synthetic"))
def test_a_tail_batch_under_the_floor_gives_todays_answers(device, pooled):
    blocks, hashes = _batch()
    want = [0, 3, (N - 1) // K]          # rows named; the last is partial
    got = {}
    for floored in (False, True):
        tr, pool, reg = _transport(device, pooled, floored)
        try:
            ok, parity = _scrub(tr, blocks, hashes, want_parity=want,
                                timeout=120)
            (submit,), (compute,) = (_events(tr, "submit scrub"),
                                     _events(tr, "compute scrub"))
            got[floored] = dict(
                ok=ok.tolist(),
                parity={r: np.asarray(parity[r]).tobytes() for r in want},
                resident=sorted(i for i, h in enumerate(hashes)
                                if pool and pool.contains(bytes(h))),
                stats=pool.stats() if pool else None,
                submit=submit["args"], compute=compute["args"],
                lanes=_lanes_counted(reg), staged=tr.staged_bytes,
                est=tr.max_staged_bytes_seen)
            if pool:
                # a second pass: the lanes the pool serves verify again
                ok2, _ = _scrub(tr, blocks, hashes, want_parity=False,
                                timeout=120)
                assert ok2.tolist() == got[floored]["ok"]
        finally:
            tr.shutdown()
    today, floor = got[False], got[True]
    rok, rpar = CpuCodec(_params()).scrub_encode_batch(blocks, hashes, want)
    assert floor["ok"] == today["ok"] == rok.tolist()
    assert floor["ok"] == [i != FLIPPED for i in range(N)]
    assert floor["parity"] == today["parity"] == {
        r: np.asarray(rpar[r]).tobytes() for r in want}
    # the pool took the verified blocks and nothing else: no pad lane
    assert floor["resident"] == today["resident"] == (
        [i for i in range(N) if i != FLIPPED] if pooled else [])
    if pooled:
        for key in ("resident_blocks", "resident_bytes", "miss_bytes",
                    "hit_bytes"):
            assert floor["stats"][key] == today["stats"][key], key
    # the host staged, sent and budgeted the same rows
    assert floor["staged"] == today["staged"]
    assert floor["est"] == today["est"] == 64 * 1024
    assert (floor["submit"]["staged_bytes"]
            == today["submit"]["staged_bytes"] == 64 * 1024)
    # what the device hashed, and by which program
    assert today["submit"]["lanes"] == today["submit"]["shape"][0] == 64
    assert floor["submit"]["lanes"] == floor["submit"]["shape"][0] == 128
    assert floor["compute"]["lanes"] == 128
    assert floor["submit"]["content_lanes"] == N
    assert today["submit"]["content_lanes"] == N
    assert today["lanes"] == (N, 64 - N)
    assert floor["lanes"] == (N, 128 - N)
    if device == "tpu":
        assert today["submit"]["variant"] == today["compute"]["variant"] \
            == "xla"
        assert floor["submit"]["variant"] == floor["compute"]["variant"] \
            == "pallas"


@pytest.mark.parametrize("pooled", (True, False),
                         ids=("pooled", "unpooled"))
def test_a_latch_that_falls_under_a_floored_batch(pooled):
    """The floor is applied before the first Pallas attempt; where that
    attempt fails (here: no Mosaic on the CPU) the batch runs the XLA
    program and the next batch has today's geometry."""
    blocks, hashes = _batch()
    tr, pool, reg = _transport("tpu", pooled, floored=False)
    tr.device._mosaic_device = lambda: True
    try:
        ok, parity = _scrub(tr, blocks, hashes, want_parity=[3], timeout=120)
        ok2, _ = _scrub(tr, blocks[:9], hashes[:9], want_parity=False,
                        timeout=120)
        first, second = _events(tr, "submit scrub")
    finally:
        tr.shutdown()
    rok, rpar = CpuCodec(_params()).scrub_encode_batch(blocks, hashes, [3])
    assert ok.tolist() == rok.tolist() and ok2.all()
    assert np.asarray(parity[3]).tobytes() == np.asarray(rpar[3]).tobytes()
    assert not tr.device._pallas_fused_ok
    assert first["args"]["variant"] == second["args"]["variant"] == "xla"
    assert first["args"]["lanes"] == 128
    assert second["args"]["lanes"] == 16 and tr.fallbacks == 0


def _wide_item(n=8, width=512 << 10):
    blocks, hashes = _blocks(n, seed=7, sizes=(width,))
    return TransportItem("scrub", (blocks, hashes), n, n * width)


def test_the_budget_counts_the_rows_the_host_stages():
    """`_staged_est`, `_cut_points` and `_plan` go by staging_geometry,
    which the floor leaves alone: a small batch of wide rows is cut as
    before, and the device batch is not raised past the budget."""
    plans = {}
    for floored in (False, True):
        p = _params(block_size=512 << 10, max_device_staging_mib=4)
        dev = TpuCodec(p)
        if floored:
            _force_pallas(dev)
        tr = DeviceTransport(dev, p, fallback=CpuCodec(p), observer=dev.obs)
        try:
            it = _wide_item()
            plans[floored] = (
                tr.chunk_bytes, tr._cut_points("scrub", it, K),
                [(b.blocks, b.staged_est) for b in tr._plan("scrub", [it])],
                tr._geometry(8, 512 << 10, "scrub"))
            # 128 lanes of 512 KiB are 64 MiB on the device: over the
            # 2 MiB a slot may hold, so the batch keeps its 8 lanes
            assert tr._device_lanes(8, 512 << 10) == 8
            assert tr._device_lanes(8, 1024) == (128 if floored else 8)
        finally:
            tr.shutdown()
    assert plans[True] == plans[False]
    chunk, cuts, batches, geom = plans[True]
    assert chunk == 2 << 20 and geom == (8, 512 << 10)
    assert [(lo, hi) for lo, hi, *_ in cuts] == [(0, 4), (4, 8)]
    assert batches == [(4, 8 * (512 << 10))] * 2
