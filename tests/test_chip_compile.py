"""The main path's device programs, compiled at real widths by the TPU
compiler for a DESCRIBED v5e (no chip attached, nothing runs).  What
interpret mode cannot show — tiles the chip's compiler refuses, programs
that do not fit HBM, kernels that cannot be partitioned — fails here,
at no chip time.  A compile that passes is not a chip run.

The topology is described inside a fixture of this file, never while a
module is imported: only one process may hold the TPU library, and every
xdist worker imports every test file."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from garage_tpu.ops import gf256, tpu_blake2s
from garage_tpu.ops.codec import CodecParams
from garage_tpu.ops.pallas_blake2s import (blake2s_batch_pallas,
                                           lanes_supported)
from garage_tpu.ops.pallas_gf import PallasGf
from garage_tpu.ops.tpu_codec import TpuCodec, scrub_step_kernel

MIB = 1 << 20
HBM_BYTES = 16 * 10**9          # one v5e chip
S = jax.ShapeDtypeStruct


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _scrub_shapes(lanes, cols, codec, sh, const_sh=None):
    return (S((lanes, cols), jnp.uint8, sharding=sh),
            S((lanes,), jnp.int32, sharding=sh),
            S((lanes, 8), jnp.uint32, sharding=sh),
            S(codec._K_enc.shape, codec._K_enc.dtype,
              sharding=const_sh or sh))


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)


def test_pallas_blake2s_256_lanes(one_chip):
    c = jax.jit(blake2s_batch_pallas).lower(
        S((256, MIB), jnp.uint8, sharding=one_chip),
        S((256,), jnp.int32, sharding=one_chip)).compile()
    assert c.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("k,m", [(8, 4), (4, 2)])
def test_pallas_gf_one_mib_shards(one_chip, k, m):
    pg = PallasGf(gf256.rs_parity_matrix(k, m))
    c = jax.jit(pg.__call__).lower(
        S((256 // k, k, MIB // 4), jnp.uint32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_fused_scrub_fits_the_chip_beside_the_pools(one_chip):
    """The transport keeps transport_staging_slots submissions in
    flight beside the device pool: together they must fit one chip."""
    params = CodecParams(rs_data=8, rs_parity=4)
    codec = TpuCodec(params)
    c = codec._scrub_pallas().lower(
        *_scrub_shapes(256, MIB, codec, one_chip), 8).compile()
    assert c.as_text().count("tpu_custom_call") == 2
    need = (params.transport_staging_slots * _device_bytes(c)
            + (params.pool_mib << 20))
    assert need < HBM_BYTES, need


def _one_chip_codec() -> TpuCodec:
    """The codec as it is on one chip: under rehearsal its device is the
    CPU's, so the test says what the chip would (ISSUE 40)."""
    codec = TpuCodec(CodecParams(rs_data=8, rs_parity=4))
    codec._mosaic_device = lambda: True
    return codec


@pytest.mark.parametrize("lanes,cols", [(256, MIB), (64, MIB),
                                        (64, 64 << 10), (128, MIB),
                                        (128, 64 << 10)])
def test_pool_programs_hold_words(one_chip, lanes, cols):
    """The device pool's closed set for a geometry (a row of four pages,
    and one narrower than a page), at the shipped 1,024 x 256 KiB pool.
    uint8 on the device costs this compiler 10 s a program and minutes
    for a view of words as bytes (PERF.md, PRs 29-30): the pool, the
    batch it composes and the fused kernel's input are words.  128
    lanes is the tail's geometry on one chip (the lane floor): its
    compose programs take the miss buckets of every staged count the
    device raises to it, 8 and 16 rows among them."""
    codec = _one_chip_codec()
    codec._pool_geom = (1024, 256 << 10)
    keys = codec.pool_program_keys(lanes, cols)
    assert len(keys) == {256: 11, 64: 5, 128: 9}[lanes]
    if lanes == 128:
        assert [key[5] for key in keys[1:-1]] == [0, 8, 16, 32, 64, 96, 128]
    # the one that slices a row out of the batch's parity (ISSUE 33)
    assert keys[-1] == ("parity_row", lanes // 8, 4, cols)
    for key in keys:
        c = TpuCodec.pool_lowered(key, one_chip).compile()
        assert "u8[" not in c.as_text(), key
        # the array, a new one, the batch, a gather's scratch
        assert _device_bytes(c) < 5 * 256 * MIB, key


@pytest.mark.parametrize("lanes,staged", [(256, 256), (128, 128),
                                          (128, 64)])
def test_fused_scrub_takes_words(one_chip, lanes, staged):
    """256 lanes: a pass's main batches; 128: its tail under the lane
    floor, one row of the hash kernel (a tile as tall as the dimension),
    composed by the pool at 128 lanes or, `staged` 64, zero-extended
    from the staged rows inside the program."""
    codec = TpuCodec(CodecParams(rs_data=8, rs_parity=4))
    bytes_in = _scrub_shapes(lanes, MIB, codec, one_chip)
    words_in = (S((staged, MIB // 4), jnp.uint32, sharding=one_chip),
                ) + bytes_in[1:]
    c = codec._scrub_pallas().lower(*words_in, 8).compile()
    assert c.as_text().count("tpu_custom_call") == 2
    # words in, words out: the parity is viewed as bytes on the host
    # (`host_bytes`), and a row of it is sliced out in words
    assert "u8[" not in c.as_text()
    assert c.out_info[3].dtype == jnp.uint32
    assert c.out_info[3].shape == (lanes // 8, 4, MIB // 4)
    assert c.out_info[1].shape == (lanes,)


def test_pool_warm_builds_the_row_program():
    """`pool_warm` compiles the geometry's whole closed set, the row
    program with it (here for the CPU's device, at a small size): a
    pass that names rows builds nothing."""
    codec = TpuCodec(CodecParams(rs_data=8, rs_parity=4))
    codec._pool_geom = (16, 1024)
    codec.pool_warm(16, 4096)
    assert set(codec._pool_execs) == set(codec.pool_program_keys(16, 4096))
    row = codec._pool_execs["parity_row", 2, 4, 4096]
    parity = np.arange(2 * 4 * 1024, dtype=np.uint32).reshape(2, 4, 1024)
    assert np.array_equal(np.asarray(row(parity, np.int32(1))), parity[1])


def test_unrolled_xla_hash_small_lanes(one_chip):
    """The <128-lane road.  Under rehearsal default_backend() is "cpu"
    and would pick the rolled body, so the test steers the unroll."""
    tpu_blake2s.set_unroll_override(True)
    try:
        c = jax.jit(tpu_blake2s.blake2s_batch).lower(
            S((8, MIB), jnp.uint8, sharding=one_chip),
            S((8,), jnp.int32, sharding=one_chip)).compile()
    finally:
        tpu_blake2s.set_unroll_override(None)
    assert "tpu_custom_call" not in c.as_text()
    assert _device_bytes(c) < HBM_BYTES


def test_sharded_scrub_on_four_chips(topo):
    """`[codec] shard_mesh = 4`: the XLA scrub step split over a 2x2
    host — every device holds a quarter of the batch, not all of it."""
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    batch, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    codec = TpuCodec(CodecParams(rs_data=8, rs_parity=4))
    tpu_blake2s.set_unroll_override(True)
    try:
        c = jax.jit(
            scrub_step_kernel, static_argnums=(4,),
            in_shardings=(batch, batch, batch, repl),
            out_shardings=(batch, batch, repl, batch),
        ).lower(*_scrub_shapes(256, MIB, codec, batch, repl), 8).compile()
    finally:
        tpu_blake2s.set_unroll_override(None)
    ma = c.memory_analysis()
    # per device: 64 of the 256 MiB in, 8 codewords x 4 parity MiB out
    assert 64 * MIB <= ma.argument_size_in_bytes < 65 * MIB
    assert 32 * MIB <= ma.output_size_in_bytes < 33 * MIB
    assert _device_bytes(c) < HBM_BYTES
    assert "all-reduce" in c.as_text()      # the corrupt count


def test_twelve_row_batches_are_declined(one_chip):
    """1536 lanes = 12 rows tiles as (6, 128), which the chip's compiler
    refuses: the fused road declines such widths and they run the XLA
    variant; 2048 lanes (16 rows, tiles of 8) compiles."""
    codec = TpuCodec(CodecParams(rs_data=8, rs_parity=4))
    assert not lanes_supported(1536)
    assert not codec._use_pallas_scrub(1536)
    assert codec._use_pallas_scrub(2048) and codec._use_pallas_scrub(256)
    with pytest.raises(AssertionError):
        jax.jit(blake2s_batch_pallas).lower(
            S((1536, 64 << 10), jnp.uint8, sharding=one_chip),
            S((1536,), jnp.int32, sharding=one_chip))
    jax.jit(blake2s_batch_pallas).lower(
        S((2048, 64 << 10), jnp.uint8, sharding=one_chip),
        S((2048,), jnp.int32, sharding=one_chip)).compile()


def test_every_scrub_batch_on_one_chip_takes_the_pallas_road():
    """ISSUE 40: on one chip the device's lane count of every scrub
    batch is one the fused Pallas road takes; on a mesh, on the CPU
    backend and after the latch fell the geometry is staging_geometry's
    and a batch under 128 lanes runs the XLA program."""
    def device_lanes(codec):
        staged = {codec.staging_geometry(n, MIB, "scrub")[0]
                  for n in range(1, 2049)}
        return {n: codec.scrub_device_lanes(n) for n in staged}

    chip = _one_chip_codec()
    lanes = device_lanes(chip)
    assert {n: d for n, d in lanes.items() if d != n} == {
        8: 128, 16: 128, 32: 128, 64: 128}
    assert all(chip._use_pallas_scrub(d) for d in lanes.values())
    # whole codewords where k is no power of two; no row count fits
    # k = 9 under the kernel's tile, and that batch keeps its lanes
    k6 = TpuCodec(CodecParams(rs_data=6, rs_parity=3))
    k6._mosaic_device = lambda: True
    assert k6.scrub_device_lanes(12) == 384
    k9 = TpuCodec(CodecParams(rs_data=9, rs_parity=3))
    k9._mosaic_device = lambda: True
    assert k9.scrub_device_lanes(72) == 72

    demoted = _one_chip_codec()
    demoted._note_fused_failure(NotImplementedError("no mosaic here"))
    on_cpu = TpuCodec(CodecParams(rs_data=8, rs_parity=4))
    mesh = TpuCodec(CodecParams(rs_data=8, rs_parity=4, shard_mesh=4))
    mesh._mosaic_device = lambda: True
    for codec in (demoted, on_cpu, mesh):
        lanes = device_lanes(codec)
        assert all(d == n for n, d in lanes.items())
        assert not codec._use_pallas_scrub(lanes[min(lanes)])
    assert not mesh._use_pallas_scrub(256)
    assert not demoted._use_pallas_scrub(256)
    assert on_cpu._use_pallas_scrub(256)    # the latch starts up


def test_a_permuted_scrub_batch_takes_the_same_programs(tmp_path):
    """ISSUE 44: the parity index's plan of a batch orders the lanes on
    the host (the rows that need their parity in front) and leaves
    their number alone: a pass's batches of 256, 256 and 64 blocks, two
    sidecars lost and a block written since the last pass, go to the
    codec with the lanes the listing gave them and no other (a row whose
    members were verified in two batches is encoded on the host), so
    they are staged at the geometry the batch in listing order takes and
    take the same closed set of programs."""
    import hashlib
    import types

    from garage_tpu.block.parity import ParityStore
    from garage_tpu.db import open_db
    from garage_tpu.ops.cpu_codec import CpuCodec
    from garage_tpu.utils.data import Hash

    params = CodecParams(rs_data=8, rs_parity=4)
    manager = types.SimpleNamespace(
        system=types.SimpleNamespace(metrics=None),
        data_layout=types.SimpleNamespace(data_dirs=[types.SimpleNamespace(
            path=str(tmp_path), read_only=False)]))
    store = ParityStore(manager, open_db("memory"), CpuCodec(params))
    rng = np.random.default_rng(44)
    blocks = sorted(((Hash(hashlib.blake2s(b, digest_size=32).digest()), b)
                     for b in (rng.bytes(64) for _ in range(576))),
                    key=lambda hb: bytes(hb[0]))
    for lo in range(0, 576, 8):
        row = blocks[lo:lo + 8]
        store.put_codeword([h for h, _b in row], [64] * 8,
                           store.codec.rs_encode_blocks(
                               [b for _h, b in row])[0])
    for lo in (16, 296):
        os.remove(store._find_group_path(bytes(store._gid(
            8, 4, [h for h, _b in blocks[lo:lo + 8]]))))
    new = rng.bytes(64)
    listing = sorted(blocks + [(Hash(hashlib.blake2s(
        new, digest_size=32).digest()), new)], key=lambda hb: bytes(hb[0]))
    chip = _one_chip_codec()
    pass_ = store.begin_pass(True)
    rows = 0
    for lo, hi in ((0, 256), (256, 512), (512, 577)):
        batch = listing[lo:hi]
        plan = pass_.plan([h for h, _b in batch], [b for _h, b in batch])
        rows += len(plan.want)
        assert sorted(map(bytes, plan.hashes)) == [
            bytes(h) for h, _b in batch]
        assert len(plan.hashes) == hi - lo      # its own lanes, no other
        staged = chip.staging_geometry(len(plan.hashes), MIB, "scrub")
        assert chip._use_pallas_scrub(chip.scrub_device_lanes(staged[0]))
        pass_.filed(plan, [True] * len(plan.hashes))
    assert rows == 2 and pass_.counts["settled"] == 70
