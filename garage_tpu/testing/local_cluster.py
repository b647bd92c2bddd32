"""An in-process cluster and a SigV4 client for what drives the product
from outside: the fault and soak tests, the drills of `scripts/` and
`chip_smoke.py`."""

from __future__ import annotations

import asyncio
import time


async def mk_cluster(tmp, n=1, repl="none", codec_cfg=None, data_repl=None,
                     db="native", rpc_cfg=None, health_cfg=None,
                     block_size=None):
    """n in-process Garage daemons with an applied layout + one S3 server
    on node 0; returns (garages, server, port, key_id, secret)."""
    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.model import Garage
    from garage_tpu.rpc.layout import ClusterLayout, NodeRole
    from garage_tpu.utils.config import config_from_dict

    garages = []
    for i in range(n):
        cfg = {
            "metadata_dir": str(tmp / f"n{i}" / "meta"),
            "data_dir": str(tmp / f"n{i}" / "data"),
            "replication_mode": repl,
            "rpc_bind_addr": "127.0.0.1:0",
            "rpc_secret": "bench",
            "db_engine": db,
            "bootstrap_peers": [],
        }
        if data_repl is not None:
            cfg["data_replication_mode"] = data_repl
        if codec_cfg:
            cfg["codec"] = dict(codec_cfg)
        if rpc_cfg:
            cfg["rpc"] = dict(rpc_cfg)
        if health_cfg:
            cfg["health"] = dict(health_cfg)
        if block_size is not None:
            cfg["block_size"] = block_size
        garages.append(Garage(config_from_dict(cfg)))
    for g in garages:
        await g.system.netapp.listen("127.0.0.1:0")
    ports = [g.system.netapp._server.sockets[0].getsockname()[1]
             for g in garages]
    for i, a in enumerate(garages):
        for j, b in enumerate(garages):
            if i < j:
                await a.system.netapp.connect(
                    f"127.0.0.1:{ports[j]}", expected_id=b.system.id)
        a.system.config.rpc_public_addr = f"127.0.0.1:{ports[i]}"
    lay = garages[0].system.layout
    for g in garages:
        lay.stage_role(bytes(g.system.id), NodeRole("dc1", 1000))
    lay.apply_staged_changes()
    enc = lay.encode()
    for g in garages:
        g.system.layout = ClusterLayout.decode(enc)
        g.system._rebuild_ring()
        # persist as the product update path would (system.py
        # update_cluster_layout): a restarted node must find the
        # applied layout on disk, not come up ringless
        g.system.save_layout()
        g.spawn_workers()

    helper = garages[0].helper()
    key = await helper.create_key("bench")
    key.params().allow_create_bucket.update(True)
    await garages[0].key_table.insert(key)
    server = S3ApiServer(garages[0])
    await server.start("127.0.0.1:0")
    return garages, server, server.port, key.key_id, key.params().secret_key


class S3:
    """Minimal SigV4 client against the in-process server."""

    def __init__(self, session, port, kid, secret,
                 honor_retry_after=False, retry_after_cap=2.0):
        self.session, self.port, self.kid, self.secret = (
            session, port, kid, secret)
        # opt-in 503 Retry-After honoring (clamped): a production-shaped
        # client pauses before its NEXT request instead of hammering a
        # shedding gateway.  Off by default — the overload/noisy drills
        # calibrate their offered load with a fixed post-shed backoff
        # and must keep it, or "4x capacity" stops meaning 4x.
        self.honor_retry_after = honor_retry_after
        self.retry_after_cap = retry_after_cap
        self._backoff_until = 0.0

    async def req(self, method, path, body=b"", query=()):
        import yarl

        from garage_tpu.api.signature import sign_request, uri_encode

        if self.honor_retry_after:
            wait = self._backoff_until - time.monotonic()
            if wait > 0:
                await asyncio.sleep(min(wait, self.retry_after_cap))
        headers = {"host": f"127.0.0.1:{self.port}"}
        headers.update(sign_request(
            self.kid, self.secret, "garage", method, path, list(query),
            headers, body, path_is_raw=True,
        ))
        # wire query must equal the signed canonical encoding (values
        # like continuation tokens carry '=' and '+')
        qs = "&".join(f"{uri_encode(k)}={uri_encode(v)}" for k, v in query)
        url = yarl.URL(
            f"http://127.0.0.1:{self.port}{path}" + (f"?{qs}" if qs else ""),
            encoded=True)
        async with self.session.request(
            method, url, data=body, headers=headers,
        ) as r:
            rb = await r.read()
            if r.status == 503 and self.honor_retry_after:
                try:
                    ra = float(r.headers.get("Retry-After", 1))
                except (TypeError, ValueError):
                    ra = 1.0
                self._backoff_until = time.monotonic() + min(
                    max(ra, 0.0), self.retry_after_cap)
            return r.status, rb, r.headers
