"""Traffic kind `scrub_passes_stored`: the passes of `scrub_passes` over
a store whose block files are of two forms, `<id>` and `<id>.zst`: the
deployment compresses a block where that shrinks it, and the
configuration's payloads are of makes that do and do not shrink.

Mix parameters: those of `scrub_passes`, which this kind loads and
hands `setup`, `window`, `check`, `control` and `shutdown` to, and
    corrupt_zst_per_pass   of `corrupt_per_pass`, the flips that go into
                           `.zst` files; the others go into plain files

The configuration, beside what `scrub_passes` reads of it:
    compression_level      the level the nodes have to run: confirmed
                           from `/metrics` (`block_compression_level`)
                           on every node before a byte is loaded
    payload.make           the makes of object bytes, handed to objects
                           by index (`"by": "index parity"`); the
                           configuration's reference makes them
                           (`payload(seed, index, n, make)`)

What it puts in the base kind's way is every place where a block was
taken to be a file named by its id that holds its content:
  - the listing: `<id>` and `<id>.zst`, and a block looked up by id in
    whichever form it has now, so that a program that heals a block
    into the other form is counted (`form_wrong`) and never waited for;
  - the load and the read-back: payloads by make;
  - THE BYTES OF A PASS ARE CONTENT BYTES: `scrub_mib_s` is content
    verified over the window's seconds, what the codec hashes and what
    `scrub_verified_bytes_total` counts; the bytes of files go into the
    notes;
  - a plant in a `.zst` file: a flip at a seeded offset of the file
    which the reference no longer reads as the block's content (else
    the next offset of the same draw);
  - the checks: a block and a codeword's member are read through the
    reference's `content(file_bytes, name)`, the `zstandard` wheel
    called directly, and `form_wrong` counts the block files on the
    node under test after the window whose form is not the reference's
    `stored_form` of their content.
The run's earlier lines say which compressor the program ran, and where
it is not the wheel (`zlib-fallback`) set-up refuses: no result.  Set-up
ends with warm passes of its own until one builds no program
(`MORE_WARM_PASSES`).
"""

import asyncio
import collections
import os
import pathlib
import time

import numpy as np
import zstandard

from benchmarks import cluster as cl
from benchmarks import harness

base = harness.load_module(
    pathlib.Path(__file__).with_name("scrub_passes.py"),
    "bench_kind_scrub_passes_under_stored")
control, shutdown = base.control, base.shutdown
ZST = ".zst"
HEAL_WAIT_S = 30.0
# Warm passes of this kind's own, after the base kind's two, until one
# builds or loads no program: the read-ahead's prefetch takes the plain
# half of a batch, a geometry of its own whose closed set of programs a
# warm pass busy compiling can leave unmet (one cold run in three built
# 8 programs in its window's first pass: PERF.md, PR 35).
MORE_WARM_PASSES = 3
# what the wheel raises on a frame that is not one: a flipped header bit
# can state a content size that no allocation serves
NOT_A_FRAME = (zstandard.ZstdError, MemoryError)


def form_of(path: str) -> str:
    return "zst" if path.endswith(ZST) else "plain"


def block_files(data_dir: str):
    """[(id hex, path)] of the block files under a data dir, of either
    form; an id held in both forms is listed twice."""
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
                stem = name[:-len(ZST)] if name.endswith(ZST) else name
                if len(stem) == 64:
                    out.append((stem, os.path.join(p2, name)))
    return out


def path_now(st, h: str):
    """Where block `h` is now on the node under test, in whichever form
    (the compressed copy first, as the program prefers it), or None."""
    stem = os.path.join(st.dir_of[h], h)
    for path in (stem + ZST, stem):
        if os.path.exists(path):
            return path
    return None


def make_of(config: dict, index: int) -> str:
    makes = config["payload"]["make"]
    return makes[index % len(makes)]


def planned_forms(config: dict) -> dict:
    """Blocks of the plan by the form the deployment holds them in: a
    base64 block shrinks under the level, a random one does not."""
    forms = collections.Counter()
    for _key, idx, n in base.object_plan(config["store"],
                                         config["block_size"]):
        form = "zst" if make_of(config, idx) == "base64" else "plain"
        forms[form] += n // config["block_size"]
    return dict(forms)


class StoredCluster:
    """`benchmarks.cluster` as the base kind sees it, but for the
    listing of block files, the objects' bytes, and the deployment's
    level and compressor, confirmed before the load."""

    def __init__(self, config: dict, reference):
        self.config, self.reference = config, reference
        self.cluster = None
        self.compressor = None

    def __getattr__(self, name):
        return getattr(cl, name)

    block_files = staticmethod(block_files)

    def object_bytes(self, seed: int, index: int, n: int) -> bytes:
        return self.reference.payload(seed, index, n,
                                      make_of(self.config, index))

    async def start_cluster(self, config, tmp):
        """The started deployment, which carries the cell's reference to
        where the base kind hands on nothing but its state."""
        self.cluster = await cl.start_cluster(config, tmp)
        self.cluster.reference = self.reference
        return self.cluster

    async def confirm_deployment(self) -> None:
        want = self.config["compression_level"] or 0
        levels = [int(cl.metric_sum(a.metrics(), "block_compression_level"))
                  for a in self.cluster.admins]
        info = await self.cluster.admins[0].cmd("codec_info")
        self.compressor = info.get("compressor")
        harness.log(f"compressor the program runs: "
                    f"{self.compressor or 'not said (no `compressor` in codec info)'}"
                    f"; compression_level on the nodes {levels}")
        refusal = None
        if any(lv != want for lv in levels):
            refusal = (f"the nodes run compression_level {levels}, the "
                       f"configuration states {want}")
        elif self.compressor is not None and not self.compressor.startswith(
                "zstandard "):
            refusal = (f"the program compresses through "
                       f"{self.compressor!r}, not the zstandard wheel")
        if refusal:
            await self.cluster.stop()
            raise RuntimeError(f"benchmarks: {refusal}: no result")

    async def load_objects(self, s3, bucket, plan, seed, conc) -> None:
        await self.confirm_deployment()
        st, _h, body = await s3.req("PUT", f"/{bucket}")
        if st != 200:
            raise RuntimeError(f"create bucket: {st} {body[:200]!r}")

        async def put(key, idx, n):
            st, _h, body = await s3.req("PUT", f"/{bucket}/{key}",
                                        self.object_bytes(seed, idx, n))
            if st != 200:
                raise RuntimeError(f"load PUT {key}: {st} {body[:200]!r}")

        await cl.gather_bounded(conc, [put(*p) for p in plan])

    async def wait_blocks(self, data_dirs, want: int,
                          timeout: float = 120.0) -> None:
        deadline = time.monotonic() + timeout
        while True:
            counts = [len(await asyncio.to_thread(block_files, d))
                      for d in data_dirs]
            if all(c >= want for c in counts):
                return
            if time.monotonic() > deadline:
                raise RuntimeError(f"blocks per node {counts}, want {want}")
            await asyncio.sleep(0.1)


def survey_blocks(st) -> None:
    """Set-up's listing of the block files of the node under test: each
    id with its directory and the bytes of its CONTENT, read through the
    reference.  The store has to be the plan's: as many `.zst` files as
    the plan has blocks that shrink, as many plain as it has others."""
    st.files = block_files(st.data_dir)
    st.dir_of = {h: os.path.dirname(p) for h, p in st.files}
    st.size_of = {}
    forms, on_disk = collections.Counter(), collections.Counter()
    for h, path in st.files:
        with open(path, "rb") as f:
            raw = f.read()
        st.size_of[h] = len(st.cluster.reference.content(raw, path))
        forms[form_of(path)] += 1
        on_disk[form_of(path)] += len(raw)
    st.store_bytes = sum(st.size_of.values())
    st.stored = {"files": dict(forms), "file_bytes": dict(on_disk),
                 "content_bytes": st.store_bytes}
    harness.log(f"stored forms on node {st.node}: {st.stored}")
    planned = planned_forms(st.cluster.config)
    if dict(forms) != planned or len(st.size_of) != len(st.files):
        raise RuntimeError(f"the store is not the configuration's: block "
                           f"files by form {dict(forms)}, the plan's "
                           f"{planned}")


def flip(path: str, h: str, rng, ref) -> int:
    """One bit of one byte at a seeded offset of the file.  In a `.zst`
    file the flip has to be one the reference no longer reads as the
    block's content: else it is taken back and the same draw gives the
    next offset.  → the offset."""
    with open(path, "r+b") as f:
        raw = bytearray(f.read())
        while True:
            off = int(rng.integers(0, len(raw)))
            raw[off] ^= 0x40
            if form_of(path) == "plain":
                break
            try:
                intact = ref.block_id(ref.content(bytes(raw), path)) == h
            except NOT_A_FRAME:
                intact = False
            if not intact:
                break
            raw[off] ^= 0x40
        f.seek(off)
        f.write(bytes([raw[off]]))
    return off


def plant(st, seed: int, pass_no: int, since: float) -> dict:
    """The faults of one pass, as `scrub_passes` plants them, over files
    of two forms: of `corrupt_per_pass` flips `corrupt_zst_per_pass` go
    into `.zst` files and the others into plain ones, each block taken
    in the form it has now.  The bytes of the pass are the content of
    the blocks on the node, whichever form holds it."""
    mix = st.mix
    rng = np.random.default_rng([seed, 7, pass_no])
    pending = {h for h, _p in st.victims if path_now(st, h) is None}
    want = {"zst": mix["corrupt_zst_per_pass"],
            "plain": mix["corrupt_per_pass"] - mix["corrupt_zst_per_pass"]}
    victims = []
    for i in rng.permutation(len(st.files)):
        if not any(want.values()):
            break
        h = st.files[int(i)][0]
        path = path_now(st, h)
        if path is None or not want[form_of(path)]:
            continue
        flip(path, h, rng, st.cluster.reference)
        want[form_of(path)] -= 1
        victims.append((h, path))
    removed = []
    if st.parity and not pending:
        hit = {h for h, _p in victims}
        for i in rng.permutation(len(st.sidecars)):
            if len(removed) >= mix["sidecars_per_pass"]:
                break
            path, members = st.sidecars[int(i)]
            if not hit.isdisjoint(members):
                continue
            try:
                fresh = os.path.getmtime(path) >= since
            except FileNotFoundError:
                continue
            if fresh:
                os.remove(path)
                removed.append(path)
    st.victims += victims
    return {"victims": victims, "removed": removed,
            "blocks": len(st.size_of) - len(pending),
            "bytes": st.store_bytes - sum(st.size_of[h] for h in pending)}


def read_content(ref, path: str):
    """A block file's content through the reference, or None where the
    reference cannot read it."""
    with open(path, "rb") as f:
        raw = f.read()
    try:
        return ref.content(raw, path)
    except NOT_A_FRAME:
        return None


def check_store(st, ids: set) -> int:
    """Block files on the node under test that are not what the
    reference says the store holds: content that is not the id's,
    missing, extra, or held twice.  And, for `form_wrong`, those whose
    content is sound and whose form is not the reference's."""
    ref = st.cluster.reference
    files = block_files(st.data_dir)
    bad = 0
    st.form_wrong = 0
    for h, path in files:
        data = read_content(ref, path)
        if data is None or ref.block_id(data) != h:
            bad += 1
        elif ref.stored_form(data) != form_of(path):
            st.form_wrong += 1
            if st.form_wrong <= 4:
                print(f"block held in the wrong form: {path} "
                      f"({len(data)} bytes of content)", flush=True)
    have = {h for h, _p in files}
    return bad + len(ids - have) + len(have - ids) + len(files) - len(have)


def check_sidecars(st, removed, reference, k: int, m: int):
    """`scrub_passes`'s comparison of the sidecars a pass wrote again,
    over the members' CONTENT.  → (wrong or not rewritten, compared)."""
    by_hash = dict(block_files(st.data_dir))
    wrong = 0
    for path in removed:
        if not os.path.exists(path):
            print(f"sidecar not written again: {path}", flush=True)
            wrong += 1
            continue
        man = base.read_sidecar(path)
        members = [bytes(h).hex() for h in man["hashes"]]
        if any(h not in by_hash for h in members):
            print(f"sidecar of a block the store lacks: {path}", flush=True)
            wrong += 1
            continue
        raws = [read_content(reference, by_hash[h]) for h in members]
        if any(raw is None for raw in raws):
            print(f"sidecar of a block that does not read: {path}",
                  flush=True)
            wrong += 1
            continue
        ref = reference.codeword_parity(raws, man["maxlen"], k, m)
        got = np.stack([np.frombuffer(p, np.uint8) for p in man["parity"]])
        wrong += not np.array_equal(ref, got)
    return wrong, len(removed)


async def setup(ctx):
    base.cl = StoredCluster(ctx.config, ctx.cell.reference)
    base.survey_blocks, base.plant = survey_blocks, plant
    base.check_store, base.check_sidecars = check_store, check_sidecars
    st = await base.setup(ctx)
    st.compressor = base.cl.compressor
    with ctx.setup_item("warm"):
        for n in range(MORE_WARM_PASSES):
            built = cl.metric_sum(st.admin.metrics(), "codec_compiles_total")
            t_wall = time.time()
            planted = await asyncio.to_thread(
                plant, st, ctx.seed, base.WARM_PASS + base.WARM_PASSES + n,
                st.last_pass_wall - 0.05)
            st.warm.append({**planted, **await base.one_pass(st)})
            st.last_pass_wall = t_wall
            await asyncio.to_thread(base.survey_sidecars, st)
            if cl.metric_sum(st.admin.metrics(),
                             "codec_compiles_total") == built:
                break
        harness.log(f"warm passes: {len(st.warm)}, seconds each "
                    f"{[round(w['seconds'], 2) for w in st.warm]}")
    return st


def grown(before: dict, after: dict, family: str, label: str) -> dict:
    """{label value: growth} of one family's series between two
    `/metrics` readings; empty on a program without the family."""
    out = {}
    for series, v in after.items():
        name, _, rest = series.partition("{")
        if name == family and f'{label}="' in rest:
            value = rest.split(f'{label}="', 1)[1].split('"', 1)[0]
            out[value] = out.get(value, 0.0) + v - before.get(series, 0.0)
    return out


async def window(ctx, st, seconds: float) -> dict:
    """`scrub_passes`'s window; its bytes are content (`plant`).  The
    notes carry what the files were: their bytes by form when set-up
    listed them, what the scrub read by form and what the heals wrote
    by form (the program's counters, where it has them), and the forms
    on the node when the window ended."""
    before = st.admin.metrics()
    win = await base.window(ctx, st, seconds)
    after = st.admin.metrics()
    forms = collections.Counter(
        form_of(p) for _h, p in await asyncio.to_thread(
            block_files, st.data_dir))
    compiled = {s: round(v - before.get(s, 0.0), 3)
                for s, v in sorted(after.items())
                if s.startswith("codec_compile") and v > before.get(s, 0.0)}
    win["notes"] += [
        "programs compiled or loaded inside the window, by the span they "
        f"ran under: {compiled or 'none'}",
        f"compressor the program ran: {st.compressor or 'not said'}",
        f"the store as set-up listed it: {st.stored}; block files by form "
        f"when the window ended {dict(forms)}",
        "bytes of block files the scrub read in the window, by form: "
        f"{grown(before, after, 'scrub_read_bytes_total', 'form') or 'not counted by this program'}"
        f"; content verified {win['bytes_verified']}",
        "blocks the heals wrote in the window, by form: "
        f"{grown(before, after, 'block_heal_stored_total', 'form') or 'not counted by this program'}",
    ]
    return win


def largest_of_each_make(plan, config: dict):
    """What a read-back has to cover whatever the base kind's sample
    drew: the largest object of each make."""
    out = []
    for make in config["payload"]["make"]:
        of_make = [p for p in plan if make_of(config, p[1]) == make]
        if of_make:
            out.append(max(of_make, key=lambda p: p[2]))
    return out


async def check(ctx, st, win: dict) -> dict:
    # a healed block is back under its id, in either form: which one is
    # `form_wrong`'s to count, not the wait's to sit out
    deadline = time.monotonic() + HEAL_WAIT_S
    while time.monotonic() < deadline:
        if all(path_now(st, h) is not None for h, _p in st.victims):
            break
        await asyncio.sleep(0.05)
    held, st.victims = st.victims, []       # the base waits for paths
    try:
        compared = await base.check(ctx, st, win)
    finally:
        st.victims = held
    compared["form_wrong"] = {"value": st.form_wrong, "limit": 0}
    for key, idx, n in largest_of_each_make(st.plan, ctx.config):
        status, _h, body = await st.s3.req("GET", f"/{base.BUCKET}/{key}")
        compared["gets_wrong"]["value"] += not (
            status == 200 and body == base.cl.object_bytes(ctx.seed, idx, n))
    return compared
