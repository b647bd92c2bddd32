"""The in-process cluster and the operator's surface the benchmark reads.

Copied from `chip_smoke.py` (PR 22) so that later changes to the smoke
cannot move the yardstick: `make_cluster`, `Admin`, `metric_sum`,
`block_files`.  From the program this takes only the system under test
(`Garage`, the S3 server) and its counters.
"""

import asyncio
import os
import pathlib
import time

MIB = 1 << 20
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build_native() -> dict:
    """Load the CPU kernels, which builds them here where they are
    missing or older than their source (the program's own loader; the
    libraries are `-march=native`, are not committed and never travel).
    No `make -B` per run: the second run of a checkout reuses them."""
    from garage_tpu.db import native_adapter
    from garage_tpu.ops import native as nat

    native_adapter._load()
    libs = {"libgf256": nat.get_native_gf_matmul_blocks() is not None,
            "libblake2smb": nat.get_native_blake2s_multi() is not None,
            "liblogdb": True}
    if not all(libs.values()):
        raise SystemExit(f"benchmarks: native CPU kernels missing: {libs}")
    return libs


async def make_cluster(tmp: pathlib.Path, n: int, repl: str, codec_cfg: dict,
                       block_size: int):
    """n in-process Garage nodes with an applied layout and one S3
    server on node 0 — the assembly server.py performs, minus the
    sockets nobody dials here."""
    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.model import Garage
    from garage_tpu.rpc.layout import ClusterLayout, NodeRole
    from garage_tpu.utils.config import config_from_dict

    garages = []
    for i in range(n):
        garages.append(Garage(config_from_dict({
            "metadata_dir": str(tmp / f"n{i}" / "meta"),
            "data_dir": str(tmp / f"n{i}" / "data"),
            "replication_mode": repl,
            "block_size": block_size,
            "rpc_bind_addr": "127.0.0.1:0",
            "rpc_secret": "benchmark",
            "bootstrap_peers": [],
            "codec": dict(codec_cfg),
        })))
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
        g.system.save_layout()
        g.spawn_workers()
    key = await garages[0].helper().create_key("benchmark")
    key.params().allow_create_bucket.update(True)
    await garages[0].key_table.insert(key)
    server = S3ApiServer(garages[0])
    await server.start("127.0.0.1:0")
    return garages, server, key.key_id, key.params().secret_key


class Admin:
    """The operator's commands, through the handler the CLI reaches."""

    def __init__(self, garage):
        from garage_tpu.admin import AdminRpcHandler

        self.garage = garage
        self.rpc = AdminRpcHandler(garage, register_endpoint=False)

    async def cmd(self, cmd: str, **msg):
        out, _ = await self.rpc._handle(None, {"cmd": cmd, **msg}, None)
        if "err" in out:
            raise RuntimeError(f"admin {cmd}: {out['err']}")
        return out["ok"]

    def metrics(self) -> dict:
        """{series: value} of the text /metrics serves."""
        from garage_tpu.api.admin_server import metrics_body

        out = {}
        for line in metrics_body(self.garage).splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                try:
                    out[name] = float(val)
                except ValueError:
                    pass
        return out


def metric_sum(metrics: dict, family: str, **labels) -> float:
    total = 0.0
    for series, v in metrics.items():
        name, _, rest = series.partition("{")
        if name == family and all(f'{k}="{val}"' in rest
                                  for k, val in labels.items()):
            total += v
    return total


async def wait_attached(admins, timeout: float = 180.0) -> None:
    """The device codec attaches on a background thread; nothing is
    loaded or judged before it is up, with its transport, on every node."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        infos = [await a.cmd("codec_info") for a in admins]
        if all(i.get("device_attached") and i.get("transport")
               for i in infos):
            return
        for a in admins:
            for e in await a.cmd("codec_events"):
                if e["kind"] == "device_attach" and e["reason"] != "ok":
                    raise RuntimeError(f"device attach failed: {e}")
        await asyncio.sleep(0.1)
    raise RuntimeError("device codec did not attach")


async def scrub_pass(cluster, node: int, poll_s: float,
                     timeout: float = 900.0) -> dict:
    """One operator-started scrub pass (`launch_repair scrub start`) on
    one node, to its end.  → its seconds and the corruptions it found."""
    sw = cluster.garages[node].scrub_worker
    start0 = sw.state.time_last_start
    t0 = time.monotonic()
    await cluster.admins[node].cmd("launch_repair", what="scrub",
                                   scrub_cmd="start")
    while sw.state.time_last_start == start0 or sw.state.running:
        if time.monotonic() - t0 > timeout:
            raise RuntimeError("scrub pass did not finish")
        await asyncio.sleep(poll_s)
    return {"seconds": time.monotonic() - t0,
            "found": sw.state.corruptions}


def block_files(data_dir: str):
    """[(hash hex, path)] of the plain block files under a data dir."""
    out = []
    for d1 in sorted(os.listdir(data_dir)):
        p1 = os.path.join(data_dir, d1)
        if len(d1) != 2 or not os.path.isdir(p1):
            continue
        for d2 in sorted(os.listdir(p1)):
            p2 = os.path.join(p1, d2)
            if len(d2) != 2 or not os.path.isdir(p2):
                continue
            for name in sorted(os.listdir(p2)):
                if len(name) == 64:
                    out.append((name, os.path.join(p2, name)))
    return out


async def gather_bounded(n: int, coros):
    sem = asyncio.Semaphore(n)

    async def one(c):
        async with sem:
            return await c

    return await asyncio.gather(*[one(c) for c in coros])


def object_bytes(seed: int, index: int, n: int) -> bytes:
    """Every object's content is a function of (seed, index)."""
    import numpy as np

    return np.random.default_rng([seed, index]).bytes(n)


class Cluster:
    """A started deployment: the nodes, their operator surfaces, the S3
    endpoint on node 0 and its credentials."""

    def __init__(self, config, garages, server, key_id, secret, tmp):
        self.config = config
        self.garages, self.server = garages, server
        self.key_id, self.secret = key_id, secret
        self.admins = [Admin(g) for g in garages]
        self.data_dirs = [str(tmp / f"n{i}" / "data")
                          for i in range(len(garages))]
        self.params = None

    async def stop(self) -> None:
        await self.server.stop()
        for g in self.garages:
            await g.shutdown()


async def start_cluster(config: dict, tmp: pathlib.Path) -> Cluster:
    """The deployment the configuration file describes, with the device
    codec attached on every node and its published shape confirmed."""
    garages, server, kid, secret = await make_cluster(
        tmp, config["nodes"], config["replication_mode"], config["codec"],
        config["block_size"])
    cl = Cluster(config, garages, server, kid, secret, tmp)
    await wait_attached(cl.admins)
    info = await cl.admins[0].cmd("codec_info")
    cl.params = info["params"]
    got = (info["backend"], cl.params["rs_data"], cl.params["rs_parity"])
    want = (config["codec_backend"], config["rs_data"], config["rs_parity"])
    if got != want:
        raise RuntimeError(f"the program's codec {got} is not the "
                           f"configuration's {want}")
    return cl


async def load_objects(s3, bucket: str, plan, seed: int, conc: int) -> None:
    """PUT [(key, index, nbytes)] through the S3 endpoint."""
    st, _h, body = await s3.req("PUT", f"/{bucket}")
    if st != 200:
        raise RuntimeError(f"create bucket: {st} {body[:200]!r}")

    async def put(key, idx, n):
        st, _h, body = await s3.req("PUT", f"/{bucket}/{key}",
                                    object_bytes(seed, idx, n))
        if st != 200:
            raise RuntimeError(f"load PUT {key}: {st} {body[:200]!r}")

    await gather_bounded(conc, [put(*p) for p in plan])


async def wait_blocks(data_dirs, want: int, timeout: float = 120.0) -> None:
    """Every replica has every block once the write quorum's stragglers
    land; a scrub judges what is on disk."""
    deadline = time.monotonic() + timeout
    while True:
        counts = [len(await asyncio.to_thread(block_files, d))
                  for d in data_dirs]
        if all(c >= want for c in counts):
            return
        if time.monotonic() > deadline:
            raise RuntimeError(f"blocks per node {counts}, want {want}")
        await asyncio.sleep(0.1)
