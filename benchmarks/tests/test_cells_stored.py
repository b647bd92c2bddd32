"""The `ec84-zst` deployment: its payloads and plan, the plants of its
kind, and its cell end to end at a tiny size on the CPU: a traced run
comes out correct and reads what the deployment added; a program that
heals into the other form, one whose verdicts are forced good, nodes
with compression off and a program that compresses through the fallback
are each seen.  (`test_cells.py` runs the cell's untraced run and its
control, as every cell's.)"""

import time
import types

import numpy as np
import pytest
import zstandard

from benchmarks import harness
from benchmarks.tests.tiny import run, tiny

CELL = "ec84-zst.scrub"
NEW = ("scrub_decompress_ms_per_gib", "scrub_decompress_self_ms_per_gib",
       "scrub_disk_share.scrub")


def node_managers(st):
    return [g.block_manager for g in st.cluster.garages]


@pytest.mark.parametrize("n", [1 << 20, 1 << 16])
def test_a_payload_is_its_seeds_and_base64_shrinks_to_three_quarters(n):
    ref = harness.Cell(CELL).reference
    seed = 2**31 + 5
    for make in ("random", "base64"):
        a = ref.payload(seed, 3, n, make)
        assert a == ref.payload(seed, 3, n, make) and len(a) == n
        assert a != ref.payload(seed, 4, n, make)
        assert a != ref.payload(seed + 1, 3, n, make)
    # `random` is every other configuration's object
    from benchmarks.cluster import object_bytes

    assert ref.payload(seed, 3, n, "random") == object_bytes(seed, 3, n)
    text = ref.payload(seed, 3, n, "base64")
    assert b"\n" not in text and text.isascii()
    frame = zstandard.ZstdCompressor(
        level=1, write_checksum=True, write_content_size=True).compress(text)
    assert 0.74 <= len(frame) / n <= 0.77
    assert ref.stored_form(text) == "zst"
    assert ref.stored_form(ref.payload(seed, 3, n, "random")) == "plain"
    assert ref.content(frame, "ab" * 32 + ".zst") == text
    assert ref.content(frame, "ab" * 32) == frame
    with pytest.raises(ValueError):
        ref.payload(seed, 3, n, "gzip")


def test_the_plan_is_288_of_each_form_whatever_the_seed():
    cell = harness.Cell(CELL)
    kind, config = cell.kind, cell.config
    assert kind.planned_forms(config) == {"zst": 288, "plain": 288}
    assert config["compression_level"] == cell.reference.LEVEL == 1
    plan = kind.base.object_plan(config["store"], config["block_size"])
    makes = [kind.make_of(config, idx) for _k, idx, _n in plan]
    assert makes.count("base64") == makes.count("random") == 256 + 2
    largest = kind.largest_of_each_make(plan, config)
    assert [n for _k, _i, n in largest] == [16 << 20, 16 << 20]
    assert {kind.make_of(config, i) for _k, i, _n in largest} == {
        "random", "base64"}
    # the forms are the makes', not a seed's luck: every block of a tiny
    # plan, on two seeds, through the reference
    tiny(cell)
    block = config["block_size"]
    for seed in (7, 2**31 + 11):
        forms = {"zst": 0, "plain": 0}
        for _key, idx, n in kind.base.object_plan(config["store"], block):
            body = cell.reference.payload(seed, idx, n,
                                          kind.make_of(config, idx))
            for o in range(0, n, block):
                forms[cell.reference.stored_form(body[o:o + block])] += 1
        assert forms == kind.planned_forms(config) == {"zst": 20,
                                                       "plain": 28}


def test_a_flip_the_reference_still_reads_as_the_content_is_taken_back(
        tmp_path):
    """A plant in a `.zst` file has to be one the reference no longer
    reads as the block's content: else the same draw's next offset."""
    kind = harness.Cell(CELL).kind
    h = "ab" * 32
    path = tmp_path / (h + ".zst")
    path.write_bytes(bytes(64))
    asked = []

    def content(raw, _name):
        asked.append(bytes(raw))
        if len(asked) < 3:
            return b"intact"        # the first two flips change nothing
        raise zstandard.ZstdError("checksum")

    ref = types.SimpleNamespace(content=content, block_id=lambda data: h)
    off = kind.flip(str(path), h, np.random.default_rng(5), ref)
    draws = np.random.default_rng(5)
    offsets = [int(draws.integers(0, 64)) for _ in range(3)]
    assert off == offsets[2] and len(asked) == 3
    now = path.read_bytes()
    assert [i for i, b in enumerate(now) if b] == [off] and now[off] == 0x40
    # a plain file takes its first offset and asks nobody
    plain = tmp_path / h
    plain.write_bytes(bytes(64))
    assert kind.flip(str(plain), h, np.random.default_rng(5),
                     ref) == offsets[0]
    assert len(asked) == 3


def test_a_traced_run_is_correct_and_reads_what_the_deployment_added():
    cell = tiny(harness.Cell(CELL))
    assert set(NEW) <= {m["name"] for m in cell.per_layer()}
    for other in ("ec84-1m.scrub", "rep3-1m.scrub", "ec84-warp.scrub"):
        assert not set(NEW) & {m["name"] for m in
                               harness.Cell(other).per_layer()}
    res = run(cell, seed=2**31 + 19, trace=True)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["compared"]) == {"blocks_wrong", "form_wrong",
                                    "plants_missed", "parity_wrong",
                                    "gets_wrong"}
    got = {name: res["metrics"][name]["value"] for name in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    # 20 of tiny's 48 blocks are `.zst` files of 0.754 their content
    assert 85.0 < got["scrub_disk_share.scrub"] < 95.0
    # the seconds inside the decompressions lie inside their segment
    assert (got["scrub_decompress_self_ms_per_gib"]
            < got["scrub_decompress_ms_per_gib"])
    # every metric `ec84-1m.scrub` reports is reported here, where the
    # device took the passes (the CPU's gate may hold through a tiny
    # run; the profiler's trace has no device plane on the CPU)
    if res["metrics"]["tpu_byte_share.scrub"]["value"] == 100.0:
        assert {m["name"]
                for m in harness.Cell("ec84-1m.scrub").per_layer()
                if m["source"] != "device_trace"} <= set(res["metrics"])


def test_heals_forced_plain_are_counted_and_never_waited_for():
    """The program before the seam: a block that was `<id>.zst` comes
    back as `<id>`.  Seen as `form_wrong`, and the comparison does not
    sit out the wait for a `.zst` file that never returns."""
    cell = tiny(harness.Cell(CELL))
    took = []
    real_check = cell.kind.check

    async def timed(ctx, st, win):
        t0 = time.monotonic()
        try:
            return await real_check(ctx, st, win)
        finally:
            took.append(time.monotonic() - t0)

    cell.kind.check = timed

    def install(st):
        from garage_tpu.block import DataBlock

        mgr = node_managers(st)[st.node]

        async def plain(h, content):
            await mgr.write_block(h, DataBlock.plain(content))

        mgr.store_rebuilt = plain

    res = run(cell, seed=2**31 + 23, after_cluster=install)
    assert not res["correct"]
    assert res["compared"]["form_wrong"]["value"] > 0
    assert res["compared"]["blocks_wrong"]["value"] == 0
    assert res["compared"]["plants_missed"]["value"] == 0
    assert took and took[0] < cell.kind.HEAL_WAIT_S / 2


def test_a_scrub_that_returns_every_block_as_good_is_seen():
    cell = tiny(harness.Cell(CELL))

    def install(st):
        feeder = node_managers(st)[st.node].feeder
        real = feeder.scrub_async

        async def all_good(blocks, hashes, want_parity=True):
            ok, parity = await real(blocks, hashes, want_parity)
            return [True] * len(ok), parity

        feeder.scrub_async = all_good

    res = run(cell, seed=31, after_cluster=install)
    assert not res["correct"]
    assert res["compared"]["plants_missed"]["value"] > 0


def test_nodes_with_compression_off_are_refused_in_set_up():
    cell = tiny(harness.Cell(CELL))

    def install(st):
        for mgr in node_managers(st):
            mgr.compression_level = None

    with pytest.raises(RuntimeError, match="compression_level"):
        run(cell, seed=5, after_cluster=install)


def test_the_fallback_compressor_is_refused_in_set_up(monkeypatch):
    import garage_tpu.utils.zstd_compat as compat

    monkeypatch.setattr(compat, "COMPRESSOR", "zlib-fallback")
    cell = tiny(harness.Cell(CELL))
    with pytest.raises(RuntimeError, match="zstandard wheel"):
        run(cell, seed=5)
