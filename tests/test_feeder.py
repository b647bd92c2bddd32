"""CodecFeeder — continuous ragged batching for the foreground path.

Covers the PR-6 contract (ISSUE 6): the SLO deadline is honored for a
lone submit (it never waits for a full batch), ragged shapes (mixed
4 KiB–1 MiB blocks in one batch) compute correctly, results route back
to the correct waiter, cancellation/shutdown drain without losing
accepted work, the ragged codec entry points are bit-identical to their
serial equivalents, and the new codec_batch_* metric families pass the
strict Prometheus lint.
"""

import concurrent.futures
import hashlib
import threading
import time

import numpy as np
import pytest

from garage_tpu.ops import make_codec
from garage_tpu.ops.feeder import CodecFeeder, FeederClosed
from garage_tpu.utils.metrics import MetricsRegistry

K, M = 4, 2


def _codec():
    return make_codec("cpu", rs_data=K, rs_parity=M, batch_blocks=64)


def _b2s(b: bytes) -> bytes:
    return hashlib.blake2s(b, digest_size=32).digest()


def test_lone_submit_honors_deadline():
    """A lone put never waits for a full batch: with an effectively
    unreachable max_batch_blocks, one submission must dispatch on the
    SLO deadline, not hang."""
    f = CodecFeeder(_codec(), slo_ms=20.0, max_batch_blocks=10_000)
    try:
        blocks = [b"\x07" * 4096]
        t0 = time.perf_counter()
        got = f.submit_hash(blocks).result(timeout=5)
        dt = time.perf_counter() - t0
        assert [bytes(h) for h in got] == [_b2s(blocks[0])]
        # deadline (20 ms) + dispatch; 2 s of slack for CI scheduler noise
        assert dt < 2.0, f"lone submit took {dt:.3f}s — deadline not honored"
        assert f.stats()["dispatch_reasons"].get("deadline", 0) >= 1
    finally:
        f.shutdown()


def test_provably_lone_submit_skips_deadline():
    """An explicit peers=1 hint (the S3 layer saw no concurrent put)
    dispatches immediately — well under the long SLO — with reason
    `lone`."""
    f = CodecFeeder(_codec(), slo_ms=5_000.0, max_batch_blocks=10_000)
    try:
        with f.request_scope():
            assert f.inflight_requests == 1
            t0 = time.perf_counter()
            got = f.submit_hash([b"solo" * 256],
                                peers=f.inflight_requests).result(timeout=5)
            dt = time.perf_counter() - t0
        assert bytes(got[0]) == _b2s(b"solo" * 256)
        assert dt < 2.0, f"peers=1 submit waited {dt:.3f}s for the SLO"
        assert f.stats()["dispatch_reasons"].get("lone", 0) >= 1
        assert f.inflight_requests == 0
    finally:
        f.shutdown()


def test_peers_hint_ends_wait_when_all_arrive():
    """With every submitter hinting peers=N, the batch goes out as soon
    as N submissions are queued (reason `peers`) instead of sleeping the
    full SLO."""
    n = 3
    f = CodecFeeder(_codec(), slo_ms=5_000.0, max_batch_blocks=10_000)
    try:
        barrier = threading.Barrier(n)
        results = {}

        def submit(i):
            blocks = [bytes([i + 1]) * 2048]
            barrier.wait()
            results[i] = (blocks, f.submit_hash(blocks, peers=n))

        t0 = time.perf_counter()
        ths = [threading.Thread(target=submit, args=(i,)) for i in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        for i, (blocks, fut) in results.items():
            got = fut.result(timeout=5)
            assert [bytes(h) for h in got] == [_b2s(b) for b in blocks], i
        # 5 s SLO never slept: all three arrived and released the batch
        assert time.perf_counter() - t0 < 4.0
        st = f.stats()
        assert st["dispatch_reasons"].get("peers", 0) >= 1, st
    finally:
        f.shutdown()


def test_full_batch_dispatches_before_deadline():
    """Reaching max_batch_blocks dispatches immediately (reason=full)
    even with a long SLO."""
    f = CodecFeeder(_codec(), slo_ms=10_000.0, max_batch_blocks=8)
    try:
        futs = [f.submit_hash([bytes([i]) * 1024 for _ in range(4)])
                for i in range(2)]
        t0 = time.perf_counter()
        for fut in futs:
            fut.result(timeout=5)
        assert time.perf_counter() - t0 < 5.0
        assert f.stats()["dispatch_reasons"].get("full", 0) >= 1
    finally:
        f.shutdown()


def test_ragged_shapes_route_to_correct_waiter():
    """Mixed 4 KiB–1 MiB submissions coalesce into one batch and every
    waiter gets exactly its own digests back."""
    f = CodecFeeder(_codec(), slo_ms=25.0, max_batch_blocks=4096)
    try:
        shapes = [
            [4096], [1 << 20], [4096, 1 << 20, 12345], [1], [1 << 18] * 5,
        ]
        results = {}
        barrier = threading.Barrier(len(shapes))

        def submit(i):
            blocks = [bytes([i]) * n for n in shapes[i]]
            barrier.wait()
            results[i] = (blocks, f.submit_hash(blocks))

        ths = [threading.Thread(target=submit, args=(i,))
               for i in range(len(shapes))]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        for i, (blocks, fut) in results.items():
            got = fut.result(timeout=10)
            assert [bytes(h) for h in got] == [_b2s(b) for b in blocks], i
        st = f.stats()
        # the barrier makes the submits near-simultaneous: they must have
        # coalesced into fewer dispatches than submissions
        assert st["dispatches"] < st["submits"], st
    finally:
        f.shutdown()


def test_encode_ragged_matches_serial():
    codec = _codec()
    f = CodecFeeder(codec, slo_ms=10.0, max_batch_blocks=4096)
    try:
        rng = np.random.default_rng(3)
        groups = [
            [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
             for n in sizes]
            for sizes in ([500], [4096] * K, [1000, 2000, 3000],
                          [1 << 16] * (K + 1))
        ]
        futs = [f.submit_encode(g) for g in groups]
        for g, fut in zip(groups, futs):
            got = fut.result(timeout=10)
            want = codec.rs_encode_blocks(g)
            assert got.shape == want.shape
            assert (got == want).all()
    finally:
        f.shutdown()


def test_decode_ragged_shares_schedule_and_matches_serial():
    codec = _codec()
    f = CodecFeeder(codec, slo_ms=10.0, max_batch_blocks=4096)
    try:
        rng = np.random.default_rng(4)
        # two submissions with the SAME loss pattern (one schedule), one
        # with a different pattern and width
        items = []
        for width in (512, 512, 300):
            data = rng.integers(0, 256, (2, K, width), dtype=np.uint8)
            parity = codec.rs_encode(data)
            surv = np.concatenate(
                [data[:, [0, 2, 3], :], parity[:, :1, :]], axis=1)
            items.append((data, surv, [0, 2, 3, K], [1]))
        futs = [f.submit_decode(surv, present, rows)
                for _data, surv, present, rows in items]
        for (data, surv, present, rows), fut in zip(items, futs):
            got = fut.result(timeout=10)
            want = codec.rs_reconstruct(surv, present, rows)
            assert (got == want).all()
            assert (got[:, 0, :] == data[:, 1, :]).all()
        # the decode-schedule cache must have been populated (and shared)
        assert codec._dec_cache, "CPU decode schedule cache unused"
        assert len(codec._dec_cache) <= 2
    finally:
        f.shutdown()


def test_cpu_decode_schedule_cache_bit_identical():
    """Cached schedule reuse must not change results (same survivor
    pattern decoded twice, then a different pattern)."""
    codec = _codec()
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (3, K, 777), dtype=np.uint8)
    parity = codec.rs_encode(data)
    surv = np.concatenate([data[:, [0, 1, 3], :], parity[:, :1, :]], axis=1)
    a = codec.rs_reconstruct(surv, [0, 1, 3, K], rows=[2])
    b = codec.rs_reconstruct(surv, [0, 1, 3, K], rows=[2])
    assert (a == b).all() and (a[:, 0, :] == data[:, 2, :]).all()
    surv2 = np.concatenate([data[:, [1, 2, 3], :], parity[:, 1:2, :]], axis=1)
    c = codec.rs_reconstruct(surv2, [1, 2, 3, K + 1], rows=[0])
    assert (c[:, 0, :] == data[:, 0, :]).all()
    assert len(codec._dec_cache) == 2


def test_cancellation_and_shutdown_drain():
    """A cancelled future is skipped; shutdown drains accepted work
    (nothing acked is lost) and later submissions raise FeederClosed
    while the *_or_direct fallbacks keep working."""
    codec = _codec()
    f = CodecFeeder(codec, slo_ms=2_000.0, max_batch_blocks=10_000)
    try:
        keep = f.submit_hash([b"keep" * 1000])
        victim = f.submit_hash([b"dead" * 1000])
        assert victim.cancel()
        f.shutdown()  # drains: the pending batch dispatches now
        got = keep.result(timeout=5)
        assert bytes(got[0]) == _b2s(b"keep" * 1000)
        assert victim.cancelled()
        with pytest.raises(FeederClosed):
            f.submit_hash([b"late"])
        # closed-feeder fallbacks go direct, not error
        assert bytes(f.hash_or_direct([b"late"])[0]) == _b2s(b"late")
        g = [b"\x01" * 100] * K
        assert (f.encode_or_direct(g) == codec.rs_encode_blocks(g)).all()
    finally:
        f.shutdown()


def test_feeder_error_fans_out_and_survives():
    """A failing submission resolves its future with the exception and
    the dispatcher keeps serving later batches."""
    codec = _codec()
    f = CodecFeeder(codec, slo_ms=5.0, max_batch_blocks=4096)
    try:
        bad = f.submit_encode([])  # empty encode group: asserts in codec
        with pytest.raises(BaseException):
            bad.result(timeout=5)
        ok = f.submit_hash([b"alive"])
        assert bytes(ok.result(timeout=5)[0]) == _b2s(b"alive")
    finally:
        f.shutdown()


def test_async_wrappers():
    import asyncio

    codec = _codec()
    f = CodecFeeder(codec, slo_ms=5.0, max_batch_blocks=4096)

    async def drive():
        hs = await f.hash_async([b"abc", b"d" * 9000])
        assert [bytes(h) for h in hs] == [_b2s(b"abc"), _b2s(b"d" * 9000)]
        rng = np.random.default_rng(6)
        data = rng.integers(0, 256, (1, K, 64), dtype=np.uint8)
        parity = codec.rs_encode(data)
        surv = np.concatenate(
            [data[:, [0, 1, 2], :], parity[:, :1, :]], axis=1)
        got = await f.decode_async(surv, [0, 1, 2, K], [3])
        assert (got[:, 0, :] == data[:, 3, :]).all()

    try:
        asyncio.run(drive())
    finally:
        f.shutdown()


def test_hybrid_ragged_routes_cpu_when_gated():
    """A hybrid codec with no device (or a gated link) must route ragged
    batches to the CPU floor; results stay bit-identical."""
    from garage_tpu.ops.codec import CodecParams
    from garage_tpu.ops.hybrid_codec import HybridCodec

    hy = HybridCodec(CodecParams(rs_data=K, rs_parity=M),
                     build_device=False)
    assert hy.ragged_side() == "cpu"
    f = CodecFeeder(hy, slo_ms=5.0, max_batch_blocks=4096)
    try:
        blocks = [b"\x11" * 4096, b"\x22" * (1 << 16)]
        got = f.submit_hash(blocks).result(timeout=5)
        assert [bytes(h) for h in got] == [_b2s(b) for b in blocks]
    finally:
        f.shutdown()


def test_hybrid_ragged_routes_unmetered_device():
    """A scripted device with no probe_link hook and no metered_link
    marker is 'unmetered' — _probe_link treats it as a healthy link and
    ragged_side() must agree (regression: the unmetered verdict never
    enters the probe cache, so reading only _link_rate routed every
    feeder batch to the CPU forever)."""
    from garage_tpu.ops.codec import CodecParams
    from garage_tpu.ops.cpu_codec import CpuCodec
    from garage_tpu.ops.hybrid_codec import HybridCodec

    params = CodecParams(rs_data=K, rs_parity=M)

    class _BareDevice(CpuCodec):
        """CPU math posing as a device: no probe_link, no metered_link."""

    hy = HybridCodec(params, device_codec=_BareDevice(params),
                     build_device="sync")
    assert hy.ragged_side() == "tpu"
    blocks = [b"\x33" * 4096, b"\x44" * (1 << 16)]
    assert [bytes(h) for h in hy.hash_ragged([blocks])[0]] \
        == [_b2s(b) for b in blocks]


def test_feeder_metric_families_pass_promlint():
    from garage_tpu.utils.promlint import lint_exposition

    reg = MetricsRegistry()
    codec = _codec()
    f = CodecFeeder(codec, slo_ms=1.0, max_batch_blocks=64, metrics=reg)
    try:
        rng = np.random.default_rng(7)
        f.submit_hash([b"x" * 4096]).result(timeout=5)
        f.submit_encode(
            [rng.integers(0, 256, 256, dtype=np.uint8).tobytes()]
        ).result(timeout=5)
        data = rng.integers(0, 256, (1, K, 64), dtype=np.uint8)
        parity = codec.rs_encode(data)
        surv = np.concatenate(
            [data[:, [0, 1, 2], :], parity[:, :1, :]], axis=1)
        f.submit_decode(surv, [0, 1, 2, K], [3]).result(timeout=5)
    finally:
        f.shutdown()
    body = reg.render()
    problems = lint_exposition(body)
    assert not problems, problems
    for fam in ("codec_feeder_depth", "codec_batch_wait_seconds",
                "codec_batch_size", "codec_batch_dispatch_total",
                "codec_batch_submit_total"):
        assert fam in body, f"family {fam} missing"
    # all three kinds must have landed samples
    for kind in ("hash", "encode", "decode"):
        assert f'kind="{kind}"' in body, kind


async def test_put_path_rides_feeder(tmp_path):
    """End-to-end: a daemon cluster's PUT must submit block-id hashing
    through the gateway's feeder (dispatches observed), serve the object
    back bit-identically, and expose codec_batch_* on /metrics."""
    import asyncio
    import os
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from test_s3_api import make_api_cluster, stop_all

    garages, server, client, _key = await make_api_cluster(tmp_path)
    try:
        g = garages[0]
        assert g.block_manager.feeder is not None
        st, _, _ = await client.req("PUT", "/feederbkt")
        assert st == 200
        bodies = [os.urandom((1 << 20) + i) for i in range(4)]

        async def put(i):
            st, _, _ = await client.req("PUT", f"/feederbkt/obj-{i}",
                                        body=bodies[i])
            assert st == 200, st

        await asyncio.gather(*[put(i) for i in range(len(bodies))])
        for i, body in enumerate(bodies):
            st, _, got = await client.req("GET", f"/feederbkt/obj-{i}")
            assert st == 200 and got == body, i
        stats = g.block_manager.feeder.stats()
        assert stats["submits"] >= len(bodies), stats
        assert stats["dispatches"] >= 1, stats
        rendered = g.system.metrics.render()
        assert "codec_batch_size" in rendered
        assert "codec_batch_dispatch_total" in rendered
    finally:
        await stop_all(garages, server)


async def test_get_path_verify_rides_feeder(tmp_path):
    """ROADMAP feeder follow-through (ISSUE 8 satellite): the GET-path
    read verify submits its content hash through the codec feeder, and
    K concurrent read verifies COALESCE into one ragged multi-buffer
    hash batch (until now only PUT hash / parity encode / degraded
    decode rode the feeder)."""
    import asyncio
    import os
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from test_s3_api import make_api_cluster, stop_all

    from garage_tpu.block.block import DataBlock
    from garage_tpu.utils.data import block_hash

    garages, server, client, _key = await make_api_cluster(tmp_path)
    try:
        mgr = garages[0].block_manager
        feeder = mgr.feeder
        assert feeder is not None
        bodies = [os.urandom(256 << 10) for _ in range(8)]
        hs = [block_hash(b, mgr.hash_algo) for b in bodies]
        for h, b in zip(hs, bodies):
            await mgr.write_block(h, DataBlock.plain(b))

        groups_seen = []
        orig = feeder.codec.hash_ragged

        def recording(groups):
            groups_seen.append(len(groups))
            return orig(groups)

        feeder.codec.hash_ragged = recording
        try:
            for _ in range(3):
                blocks = await asyncio.gather(
                    *[mgr.read_block(h) for h in hs])
                for blk, body in zip(blocks, bodies):
                    assert blk.inner == body
        finally:
            feeder.codec.hash_ragged = orig
        assert groups_seen, "read verify never dispatched via the feeder"
        # the coalescing claim itself: at least one ragged hash batch
        # carried more than one GET verify
        assert max(groups_seen) > 1, groups_seen
        assert feeder.stats()["submits"] >= 24
    finally:
        await stop_all(garages, server)
