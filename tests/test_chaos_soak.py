"""Randomized chaos soak (VERDICT r4 #8): node crash/revive, block
drop/corrupt, and a layout change, composed over a 6-node erasure-coded
cluster UNDER concurrent client load, asserting the durability
invariants at the end:

  1. every ACKNOWLEDGED, non-deleted object reads back bit-identical
     after the cluster heals,
  2. deleted objects stay deleted,
  3. the object counters recount clean (totals match a live listing),
  4. the cluster converges (all revived, resync queues drain).

Analogue of the reference's manual kill-9 dev-cluster method (SURVEY §5
fault injection) made into a repeatable in-tree rig on top of
garage_tpu/testing/faults.py.  CI default is a short soak
(~40 s of chaos); GARAGE_SOAK_SECONDS=1800 runs the 30-min version
out-of-band — results recorded in docs/ROUND5_NOTES.md.  Emits a
summary artifact (soak_summary.json under the test tmpdir; printed to
stdout for the out-of-band run).
"""

import asyncio
import json
import os
import random
import time

import numpy as np
import pytest

from garage_tpu.rpc.layout import ClusterLayout, NodeRole
from garage_tpu.testing.faults import FaultInjector
from garage_tpu.testing.local_cluster import S3, mk_cluster

SOAK_S = float(os.environ.get("GARAGE_SOAK_SECONDS", "40"))
HEAL_CAP_S = max(180.0, SOAK_S / 2)
BLOCK = 1 << 20


async def _drain_resync(garages, deadline):
    while time.monotonic() < deadline:
        depths = [g.block_resync.queue_len() for g in garages]
        if all(d == 0 for d in depths):
            return True
        await asyncio.sleep(2.0)
    return False


@pytest.mark.slow
async def test_chaos_soak(tmp_path):
    import aiohttp

    garages, server, port, kid, secret = await mk_cluster(
        tmp_path, n=6, repl="3", data_repl="none", db="sqlite",
        codec_cfg={
            "rs_data": 2, "rs_parity": 2,
            "store_parity": True, "parity_on_write": True,
            "parity_distribute": True, "backend": "cpu",
        })
    inj = FaultInjector(garages)
    rng = random.Random(1234)
    nprng = np.random.default_rng(99)

    acked = {}      # name -> payload bytes
    deleted = set()
    maybe_deleted = set()  # DELETE outcome unknown (timed out mid-chaos)
    stats = {"puts_ok": 0, "puts_failed": 0, "gets_ok": 0,
             "gets_failed": 0, "deletes": 0, "crashes": 0,
             "revives": 0, "drops": 0, "corruptions": 0,
             "layout_changes": 0}
    stop = asyncio.Event()

    async def client_loop(s3):
        i = 0
        while not stop.is_set():
            i += 1
            name = f"o{i:05d}"
            body = nprng.integers(
                0, 256, rng.randrange(64 << 10, 2 << 20),
                dtype=np.uint8).tobytes()
            try:
                st, _b, _h = await asyncio.wait_for(
                    s3.req("PUT", f"/soak/{name}", body), 30)
            except Exception:
                st = 0
            if st == 200:
                acked[name] = body
                stats["puts_ok"] += 1
            else:
                stats["puts_failed"] += 1
            # read-back probe of a random acked object (tolerate
            # failures mid-chaos; the END-state check is the invariant)
            if acked and rng.random() < 0.4:
                probe = rng.choice(sorted(acked))
                try:
                    st, got, _h = await asyncio.wait_for(
                        s3.req("GET", f"/soak/{probe}"), 30)
                    if st == 200 and got == acked[probe]:
                        stats["gets_ok"] += 1
                    else:
                        stats["gets_failed"] += 1
                except Exception:
                    stats["gets_failed"] += 1
            if acked and rng.random() < 0.05:
                victim = rng.choice(sorted(acked))
                try:
                    st, _b, _h = await asyncio.wait_for(
                        s3.req("DELETE", f"/soak/{victim}"), 30)
                    if st in (200, 204):
                        del acked[victim]
                        deleted.add(victim)
                        stats["deletes"] += 1
                except Exception:
                    # the DELETE may or may not have landed: the object
                    # can no longer be asserted either way
                    acked.pop(victim, None)
                    maybe_deleted.add(victim)
            await asyncio.sleep(0.05)

    async def chaos_loop():
        # node 0 is the S3 gateway: never crashed.  Keep >= 4 alive so
        # meta quorum (2/3) and RS(2,2) data (any 2 of 4 pieces) hold.
        t_end = time.monotonic() + SOAK_S
        while time.monotonic() < t_end:
            await asyncio.sleep(rng.uniform(2.0, 5.0))
            action = rng.choice(
                ["crash", "revive", "drop", "corrupt", "layout"])
            try:
                if action == "crash" and len(inj.dead) < 2:
                    victim = rng.choice(
                        [i for i in range(1, 6) if i not in inj.dead])
                    await inj.crash(victim)
                    stats["crashes"] += 1
                elif action == "revive" and inj.dead:
                    i = rng.choice(sorted(inj.dead))
                    await inj.revive(i)
                    stats["revives"] += 1
                elif action == "drop":
                    live = [i for i in range(1, 6) if i not in inj.dead]
                    i = rng.choice(live)
                    blocks = inj.list_blocks(i)
                    if blocks:
                        inj.drop_block(i, rng.choice(blocks))
                        stats["drops"] += 1
                elif action == "corrupt":
                    live = [i for i in range(1, 6) if i not in inj.dead]
                    i = rng.choice(live)
                    blocks = inj.list_blocks(i)
                    if blocks:
                        inj.corrupt_block(i, rng.choice(blocks))
                        stats["corruptions"] += 1
                elif action == "layout":
                    # capacity change on a random live node → ring
                    # shuffle → automatic refs-only sweep on every node
                    live = [i for i in range(1, 6) if i not in inj.dead]
                    i = rng.choice(live)
                    g0 = inj.garages[0]
                    lay = ClusterLayout.decode(g0.system.layout.encode())
                    cap = rng.choice((500_000_000, 2_000_000_000))
                    lay.stage_role(
                        bytes(inj.garages[i].system.id),
                        NodeRole("dc1", cap))
                    lay.apply_staged_changes()
                    enc = lay.encode()
                    for j in range(6):
                        if j in inj.dead:
                            continue
                        gg = inj.garages[j]
                        gg.system.layout = ClusterLayout.decode(enc)
                        gg.system._rebuild_ring()
                    stats["layout_changes"] += 1
            except Exception as e:  # noqa: BLE001 — chaos must not
                stats.setdefault("chaos_errors", []).append(repr(e))
        stop.set()

    async with aiohttp.ClientSession() as session:
        s3 = S3(session, port, kid, secret)
        st, _b, _h = await s3.req("PUT", "/soak")
        assert st == 200
        await asyncio.gather(client_loop(s3), chaos_loop())

        # --- heal: revive everyone, drain, then check invariants ---
        for i in sorted(inj.dead):
            await inj.revive(i)
            stats["revives"] += 1
        garages = inj.garages
        for g in garages:
            if g.block_manager.ec_accumulator is not None:
                await g.block_manager.ec_accumulator.drain()
            g.block_resync.set_n_workers(4)
        # bounded drain wait — items in error backoff (a dropped block
        # whose re-fetch keeps failing until repair finds it) legally
        # keep the queue non-empty, so this must NOT consume the verify
        # budget
        await _drain_resync(garages, time.monotonic() + min(60.0,
                                                            HEAL_CAP_S))

        # invariant 1: every acked object reads bit-identical (retry
        # through the heal window — corrupt copies route around via
        # resync + RS decode)
        deadline = time.monotonic() + HEAL_CAP_S
        pending = dict(acked)
        while pending and time.monotonic() < deadline:
            for name in list(pending):
                try:
                    st, got, _h = await asyncio.wait_for(
                        s3.req("GET", f"/soak/{name}"), 30)
                except Exception:
                    continue
                if st == 200 and got == pending[name]:
                    del pending[name]
            if pending:
                await asyncio.sleep(3.0)
        assert not pending, (
            f"{len(pending)}/{len(acked)} acked objects unreadable "
            f"after heal: {sorted(pending)[:5]} (stats {stats})")

        # invariant 2: deleted stay deleted
        for name in sorted(deleted)[:10]:
            st, _b, _h = await s3.req("GET", f"/soak/{name}")
            assert st == 404, (name, st)

        # invariant 3: counters match GROUND TRUTH (a full listing) —
        # client bookkeeping is not the truth: a timed-out PUT may have
        # landed anyway, which the counter rightly counts
        listed = set()
        start_after = ""
        while True:
            # paginate via start-after (plain object keys — the bench
            # S3 client signs unreserved chars only; continuation
            # tokens are base64 and exercise percent-encoding paths
            # covered by tests/test_s3_list_semantics.py instead)
            q = [("list-type", "2"), ("max-keys", "100")]
            if start_after:
                q.append(("start-after", start_after))
            st, body, _h = await s3.req("GET", "/soak", query=q)
            assert st == 200, st
            import re as _re

            page = _re.findall(r"<Key>([^<]+)</Key>", body.decode())
            listed.update(page)
            if len(page) < 100:
                break
            start_after = max(page)
        assert set(acked) <= listed, (
            f"acked objects missing from listing: "
            f"{sorted(set(acked) - listed)[:5]}")
        assert not (listed & deleted), (
            f"deleted objects resurfaced: {sorted(listed & deleted)[:5]}")
        g0 = garages[0]
        bucket_id = await g0.helper().resolve_global_bucket_name("soak")
        assert bucket_id is not None
        totals = await g0.object_counter.get_totals(bytes(bucket_id))
        n_objects = totals.get("objects", 0)
        assert n_objects == len(listed), (
            f"counter says {n_objects} objects, listing has "
            f"{len(listed)}")

    summary = {"soak_seconds": SOAK_S, "acked_objects": len(acked),
               **{k: v for k, v in stats.items()
                  if not isinstance(v, list)}}
    (tmp_path / "soak_summary.json").write_text(json.dumps(summary))
    print("SOAK SUMMARY " + json.dumps(summary))

    await server.stop()
    for g in garages:
        await g.shutdown()
