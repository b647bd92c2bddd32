"""TPU BlockCodec — JAX implementation of the batch block ops.

Design (TPU-first, per SURVEY.md §7):
  - BLAKE2s integrity hashing: vectorized uint32 scan, one lane per block
    (tpu_blake2s.py).  The scrub worker's read→verify step (ref
    block/repair.rs:438-490) becomes read→batch→one device dispatch.
  - Reed-Solomon GF(2^8) encode/reconstruct: the Cauchy generator matrix is
    expanded to a GF(2) bit-matrix W (gf256.bitmatrix_of_gf_matrix), so
    encoding is  parity_bits = (data_bits @ W) & 1  — an int8→int32 matmul
    XLA tiles onto the MXU, batched over every byte position of every shard
    group in the batch.
  - Static shapes: inputs are padded to the configured batch size and block
    size so every scrub/resync step hits the same compiled executable
    (XLA retrace avoidance); pad lanes are masked out of results.
  - Multi-chip: `sharded_fns(mesh)` returns the same ops jitted with batch
    dims sharded over a `jax.sharding.Mesh` — codec batches scale across
    chips with XLA inserting the (trivial, batch-parallel) collectives;
    a psum'd corruption count demonstrates the cross-chip reduction.

Bit-identical to CpuCodec (tests/test_codec_equivalence.py).
"""

from __future__ import annotations

import contextlib
import math
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.data import Hash
from . import compile_listener, gf256
from .codec import BlockCodec, CodecParams, parity_by_row
from .compile_cache import ensure_compile_cache
from .device_pool import miss_buckets
from .tpu_blake2s import blake2s_batch, digests_to_bytes

# --- pure jittable kernels --------------------------------------------------


def unpack_bits(x: jax.Array) -> jax.Array:
    """uint8 (..., n) → int8 (..., n*8) bits LSB-first (matmul operand)."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., None] >> shifts) & jnp.uint8(1)
    return bits.reshape(x.shape[:-1] + (-1,)).astype(jnp.int8)


def pack_bits(b: jax.Array) -> jax.Array:
    """int32/int8 0-1 bits (..., n*8) → uint8 (..., n) LSB-first."""
    g = b.reshape(b.shape[:-1] + (-1, 8)).astype(jnp.uint8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return (g * weights).sum(axis=-1, dtype=jnp.uint32).astype(jnp.uint8)


def gf_bitmatmul(shards: jax.Array, w_bits: jax.Array) -> jax.Array:
    """Apply a GF(2^8) matrix in the bit domain.

    shards (B, k, S) uint8;  w_bits (k*8, r*8) int8 from
    gf256.bitmatrix_of_gf_matrix.  Returns (B, r, S) uint8.
    The contraction runs as int8×int8→int32 on the MXU; parity = count & 1.
    """
    bits = unpack_bits(jnp.swapaxes(shards, -1, -2))     # (B, S, k*8)
    acc = jax.lax.dot_general(
        bits, w_bits,
        dimension_numbers=(((bits.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = pack_bits(acc & 1)                             # (B, S, r)
    return jnp.swapaxes(out, -1, -2)


def gf_mask_consts(mat: np.ndarray) -> np.ndarray:
    """GF(2^8) matrix (r, k) → (r, k, 8) uint32 constants for the mask-XOR
    kernel: entry [p,i,b] = gf_mul(mat[p,i], 1<<b) replicated into all 4
    bytes of a uint32 lane."""
    r, k = mat.shape
    K = np.zeros((r, k, 8), np.uint32)
    for p in range(r):
        for i in range(k):
            for b in range(8):
                K[p, i, b] = gf256.gf_mul(int(mat[p, i]), 1 << b) * 0x01010101
    return K


def gf_apply(shards_u32: jax.Array, K: jax.Array) -> jax.Array:
    """Apply a GF(2^8) matrix via bit-mask XOR accumulation — the fast path.

    shards_u32 (B, k, S4) uint32 (4 data bytes per lane); K (r, k, 8)
    uint32 from gf_mask_consts.  Returns (B, r, S4) uint32.

    gfmul-by-constant is GF(2)-linear in the input bits:
    gfmul(c, x) = XOR_b bit_b(x) · gfmul(c, 2^b).  Each term is computed
    bytewise in uint32 lanes: ((x >> b) & 0x01010101) * 0xFF broadcasts
    bit b of every byte to a full-byte mask (no cross-byte carries), which
    then selects the constant gfmul(c, 2^b).  Pure VPU shift/and/mul/xor —
    no gathers, no MXU, ~700 vector ops total for RS(8,4).  (The earlier
    bit-matmul formulation unpacked to int8 bit-planes and ran a (…,64)×
    (64,32) MXU contraction: 16× data expansion and tiny matmul dims made
    it memory-shuffle-bound.)
    """
    r, k, _ = K.shape
    one = jnp.uint32(0x01010101)
    ff = jnp.uint32(0xFF)
    with jax.named_scope("gf_apply"):
        masks = []
        for i in range(k):
            x = shards_u32[:, i]
            masks.append(
                [(((x >> jnp.uint32(b)) & one) * ff) for b in range(8)])
        outs = []
        for p in range(r):
            acc = jnp.zeros_like(shards_u32[:, 0])
            for i in range(k):
                for b in range(8):
                    acc = acc ^ (masks[i][b] & K[p, i, b])
            outs.append(acc)
        return jnp.stack(outs, axis=1)


def bytes_view_u32(x_u8: jax.Array) -> jax.Array:
    """uint8 (..., 4n) → uint32 (..., n) little-endian (byte j of each lane
    = input byte 4i+j; host_bytes is the way back, on the host).  Bitcast:
    a relayout, not arithmetic — see tpu_blake2s.bytes_to_words."""
    from .tpu_blake2s import bytes_to_words

    return bytes_to_words(x_u8)


def host_words(x_u8: np.ndarray) -> np.ndarray:
    """Host uint8 (..., 4n) as uint32 (..., n) in bytes_view_u32's byte
    order, before it goes to the device: a numpy view.  Outside a jit
    the device-side views are two eager programs a width each, and the
    chip's compiler takes minutes over a view of words as bytes (163 s
    for one heal of a short block inside a scrub pass: PERF.md, PR 29);
    every width a ragged store brings would pay that again."""
    return np.ascontiguousarray(x_u8).view("<u4")


def host_bytes(x_u32) -> np.ndarray:
    """A device uint32 (..., n) array on the host as uint8 (..., 4n),
    little-endian (bytes_view_u32's inverse): the D2H copy and a numpy
    view.  No program views words as bytes on the device any more: the
    fused scrub's parity comes back through here too."""
    return np.asarray(x_u32).astype("<u4", copy=False).view(np.uint8)


def verify_kernel(data_u8: jax.Array, lengths: jax.Array, expected: jax.Array):
    """Batched hash + compare: returns ((B,8) digests, (B,) ok, scalar
    corrupt-count) — the scrub hot op."""
    h = blake2s_batch(data_u8, lengths)
    ok = jnp.all(h == expected, axis=-1)
    return h, ok, jnp.sum(~ok, dtype=jnp.int32)


def scrub_step_kernel(data_u8, lengths, expected, K_enc, k: int):
    """The fused scrub hot op — ONE device dispatch per batch: verify all
    B blocks AND produce RS parity for every group of k blocks (north-star
    batch producer, SURVEY.md §3.4).  data_u8 (B, S) with B % k == 0;
    returns (digests, ok, corrupt_count, parity (B//k, r, S ÷ 4) as
    uint32 words: the host views what it fetches as bytes, host_bytes,
    and a row can be sliced out on the device, parity_row)."""
    h, ok, bad = verify_kernel(data_u8, lengths, expected)
    u32 = bytes_view_u32(data_u8)
    groups = u32.reshape(u32.shape[0] // k, k, u32.shape[-1])
    return h, ok, bad, gf_apply(groups, K_enc)


# The jitted entry points, under the names their programs carry in a
# profiler trace (`jit_<name>` on the device's "XLA Modules" line): a
# reader of the trace finds a kernel by these after any refactor.


def hash_xla(data_u8, lengths):
    return blake2s_batch(data_u8, lengths)


def verify_xla(data_u8, lengths, expected):
    return verify_kernel(data_u8, lengths, expected)


def gf_apply_xla(shards_u32, K):
    return gf_apply(shards_u32, K)


def scrub_fused_xla(data_u8, lengths, expected, K_enc, k: int):
    return scrub_step_kernel(data_u8, lengths, expected, K_enc, k)


def scrub_fused_pallas_step(pg, interpret: bool = False):
    """The fused scrub step with BOTH hot ops as Pallas kernels: the
    VMEM-resident blake2s (pallas_blake2s.py) and, where `pg` is given,
    the GF mask-XOR apply (pallas_gf.py).  `lengths` and `expected`
    have the device batch's lanes (TpuCodec.scrub_device_lanes); a
    staged batch of fewer rows is zero-extended to them here, on the
    device (a batch the pool composed has them already).  `interpret`
    is the tests': the hash kernel in the Pallas interpreter."""
    from .pallas_blake2s import blake2s_batch_pallas

    def scrub_fused_pallas(data_u8, lengths, expected, K_enc, k):
        pad = lengths.shape[0] - data_u8.shape[0]
        if pad:
            data_u8 = jnp.pad(data_u8, ((0, pad), (0, 0)))
        h = blake2s_batch_pallas(data_u8, lengths, interpret=interpret)
        ok = jnp.all(h == expected, axis=-1)
        bad = jnp.sum(~ok, dtype=jnp.int32)
        u32 = bytes_view_u32(data_u8)
        groups = u32.reshape(u32.shape[0] // k, k, u32.shape[-1])
        parity = (pg(groups) if pg is not None
                  else gf_apply(groups, K_enc))
        return h, ok, bad, parity

    return scrub_fused_pallas


# The device pool's programs (ops/device_pool.py).  Everything is
# uint32 words: uint8 on the device costs the chip's compiler 10 s a
# program and minutes for a view (PERF.md, PRs 29 and 30), and both
# kernels hash and encode words.  `pool` is the one array of pages,
# (npages, page words ÷ 128, 128): a page is whole (8, 128) tiles, so a
# gather or a scatter of pages moves contiguous memory (2.9 and 4.1 ms
# for 256 MiB on a v5e against 5.6 and 19.3 with pages as rows of a 2-D
# array; PERF.md, PR 30).  A batch is (lanes, cols ÷ 4) words.  An index
# equal to npages is the sentinel: a gather reads zeros there and a
# scatter drops the page.

POOL_TILE = 128     # words: a page is a whole number of these


def pool_alloc(npages: int, words: int):
    return jnp.zeros((npages, words // POOL_TILE, POOL_TILE),
                     dtype=jnp.uint32)


def _as_pages(rows, per: int, pool):
    """(n, cols) rows as (n · per, …) pages shaped like the pool's,
    each row zero-extended to `per` whole pages."""
    words = pool.shape[1] * pool.shape[2]
    if per * words != rows.shape[1]:
        rows = jnp.pad(rows, ((0, 0), (0, per * words - rows.shape[1])))
    return rows.reshape((rows.shape[0] * per,) + pool.shape[1:])


def pool_compose(pool, row_pages, miss, miss_rows, lanes: int, cols: int):
    """The (lanes, cols) batch: row r is the pages row_pages[r·per :
    (r+1)·per] of the pool, and row miss_rows[i] is miss[i] (staged
    rows; an index of `lanes` pads the bucket and is dropped)."""
    with jax.named_scope("pool_compose"):
        per = row_pages.shape[0] // lanes
        pages = jnp.take(pool, row_pages, axis=0, mode="fill", fill_value=0)
        if miss is not None:
            at = miss_rows[:, None] * per + jnp.arange(per, dtype=jnp.int32)
            pages = pages.at[at.reshape(-1)].set(
                _as_pages(miss, per, pool), mode="drop")
        return pages.reshape(lanes, -1)[:, :cols]


def pool_adopt(pool, batch, dst):
    """The pool with page j of the batch (its rows seen as `per` pages
    each, zero-extended) written to slot dst[j]."""
    with jax.named_scope("pool_adopt"):
        per = dst.shape[0] // batch.shape[0]
        return pool.at[dst].set(_as_pages(batch, per, pool), mode="drop")


def pool_gather(pool, slots):
    return jnp.take(pool, slots, axis=0, mode="fill", fill_value=0)


def parity_row(parity, row):
    """One codeword's parity out of a scrub batch's, on the device:
    (rows, m, words) and a row index → (m, words).  The index is an
    argument, so a geometry has one such program whatever rows a pass
    wants; in words, like everything the pool's programs touch."""
    with jax.named_scope("parity_row"):
        return jax.lax.dynamic_index_in_dim(parity, row, axis=0,
                                            keepdims=False)


# --- codec ------------------------------------------------------------------


# Pallas demotion policy: errors matching these markers mean the backend
# simply cannot run Mosaic kernels — retrying is pointless.  Anything
# else (UNAVAILABLE, DEADLINE_EXCEEDED, connection reset) is
# transient and only demotes after this many CONSECUTIVE failures.
PALLAS_MAX_TRANSIENT_FAILS = 5
_PALLAS_PERMANENT_MARKERS = (
    "mosaic", "not implemented", "unimplemented", "unsupported",
    "no registered", "cannot lower", "interpret mode",
)


def _pallas_error_is_permanent(e: BaseException) -> bool:
    msg = f"{type(e).__name__}: {e}".lower()
    if isinstance(e, NotImplementedError):
        return True
    return any(s in msg for s in _PALLAS_PERMANENT_MARKERS)


# The fewest lanes a scrub batch has on the device where the fused
# Pallas road takes it (TpuCodec.scrub_device_lanes): one 128-lane row
# of the hash kernel.  The kernel's cost goes by tiles of up to 8 rows,
# not by lanes with content, so a 64-lane tail padded to one row costs
# about what a row costs; the XLA scan it ran before took 25-50 x as
# long a lane (PERF.md §6, PR 40: 128 against 256 measured there).
SCRUB_LANE_FLOOR = 128


class TpuCodec(BlockCodec):
    # a real accelerator sits behind a link whose rate is measured, not
    # assumed: HybridCodec's gate probes a device so marked before it
    # routes work there (a scripted fake without the mark, and without a
    # probe_link hook, is taken as healthy)
    metered_link = True

    def __init__(self, params: CodecParams, devices: Optional[list] = None,
                 metrics=None, tracer=None, observer=None):
        super().__init__(params, metrics=metrics, tracer=tracer,
                         observer=observer)
        if params.hash_algo != "blake2s":
            raise ValueError(
                "TpuCodec offloads blake2s only; set codec.hash_algo='blake2s' "
                f"(got {params.hash_algo!r})"
            )
        # the device this codec was built for: every staged buffer and
        # every constant is put THERE explicitly, never on the process
        # default (a numpy buffer adopted without a target stays on the
        # host CPU backend, and jit follows a committed input)
        ensure_compile_cache()
        devs = list(devices or jax.devices())
        self.device = devs[0]
        self.mesh = None
        # where batch-leading arrays and constants go: one device, or —
        # sharded — split over / replicated on the mesh
        self._batch_sh = self._repl_sh = self.device
        if params.shard_mesh > 1:
            if len(devs) < params.shard_mesh:
                raise ValueError(
                    f"codec.shard_mesh={params.shard_mesh} but only "
                    f"{len(devs)} device(s) are present")
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.mesh = jax.sharding.Mesh(
                np.array(devs[: params.shard_mesh]), ("data",))
            self._batch_sh = NamedSharding(self.mesh, P("data"))
            self._repl_sh = NamedSharding(self.mesh, P())
        if params.rs_data > 0:
            pm = gf256.rs_parity_matrix(params.rs_data, params.rs_parity)
            self._enc_mat = pm
            self._K_enc = self._put_const(gf_mask_consts(pm))
        self._decode_w_cache = {}
        # Pallas GF kernels (north star): VMEM-resident mask-XOR apply,
        # one HBM read per input byte.  Built lazily per matrix; the
        # first runtime failure (a backend without Mosaic support)
        # permanently falls back to the XLA kernel.
        self._pallas_cache = {}
        self._pallas_ok = True
        self._pallas_transient_fails = 0
        # Pallas fused scrub (blake2s hash state resident in VMEM across
        # chunks + Pallas GF parity): 117 GiB/s at 1024 lanes on v5e vs
        # the XLA scan's 4.3 in round 5 (jax 0.4.37; not re-measured on
        # the current stack) — the scan was bound by per-chunk state
        # round-trips through HBM.  Separate latch from the GF kernel;
        # same permanent/transient demotion policy.
        self._pallas_fused_ok = True
        self._pallas_fused_fails = 0
        self._scrub_pallas_jit = None
        # which fused-scrub variant produced the LAST submission — the
        # caller snapshots this right after scrub_submit and passes it
        # back into note_sync_{success,failure} so sync-time failures
        # (surfacing only at np.asarray) demote the right latch
        self.last_submit_variant = "xla"
        # LinkProfiler boundary stamps (ops/link_profiler.py): the
        # transport clears these before each submit/collect and reads
        # them after, so adopt (dlpack/device_put) time and the compile
        # vs steady-state dispatch split are attributable without the
        # transport reaching into JAX.  last_submit_compiled says that
        # JAX built a program, or loaded one from its persistent cache,
        # inside this submission's dispatch (ops/compile_listener.py).
        self.last_adopt_ns = 0
        self.last_ready_ns = 0
        self.last_submit_compiled = False
        # the timeline track of this codec's spans (`compose`, `submit
        # <kind>`): the transport names the slot it submits from
        self.span_track = "device"
        # the device pool's closed set of programs, compiled ahead of
        # their first dispatch and called as executables: nothing is
        # traced on the transport thread (`_pool_program`)
        self._pool_execs: dict = {}
        self._pool_geom: Optional[Tuple[int, int]] = None
        self._pool_read_jit = jax.jit(pool_gather)
        compile_listener.attach(self.obs)
        if self.obs.timeline.annotate is None:
            # every timeline span is in the profiler's trace too, as
            # `gt:<name>` (one atomic read while no profile runs)
            self.obs.timeline.annotate = jax.profiler.TraceAnnotation
        if self.mesh is not None:
            batch, repl = self._batch_sh, self._repl_sh
            self._hash_jit = jax.jit(
                hash_xla, in_shardings=(batch, batch), out_shardings=batch
            )
            self._verify_jit = jax.jit(
                verify_xla,
                in_shardings=(batch, batch, batch),
                out_shardings=(batch, batch, repl),
            )
            self._gf_jit = jax.jit(
                gf_apply_xla, in_shardings=(batch, repl), out_shardings=batch
            )
            # static k passed POSITIONALLY: pjit rejects kwargs when
            # in_shardings is given, so static_argnums — not
            # static_argnames — is the only shape that works on both the
            # sharded and single-device builds (caught by the daemon-
            # level sharded scrub test; the kwarg form compiled fine
            # single-device and exploded only on a real mesh)
            self._scrub_jit = jax.jit(
                scrub_fused_xla,
                static_argnums=(4,),
                in_shardings=(batch, batch, batch, repl),
                out_shardings=(batch, batch, repl, batch),
            )
        else:
            self._hash_jit = jax.jit(hash_xla)
            self._verify_jit = jax.jit(verify_xla)
            self._gf_jit = jax.jit(gf_apply_xla)
            self._scrub_jit = jax.jit(scrub_fused_xla, static_argnums=(4,))

    def ragged_side(self) -> str:
        """Feeder attribution: a bare TpuCodec runs every ragged batch
        on the device (routing belongs to HybridCodec)."""
        return "tpu"

    # --- the transport device API (ops/transport.py) ---
    #
    # DeviceTransport stages ragged batches ONCE into reusable host
    # buffers and hands them over through these array-level entry
    # points — no bytes-list repacking, no second pad pass.  The
    # staging buffer is adopted zero-copy via dlpack where host and
    # device share memory (CPU backend, unified hosts); elsewhere
    # device_put is the H2D DMA, which is not a host copy.  The
    # transport's slot discipline guarantees a staging buffer is never
    # rewritten while a dispatch that adopted it is still in flight.

    def staging_geometry(self, nlanes: int, maxlen: int,
                         kind: str) -> Tuple[int, int]:
        """(lanes, row_bytes) the transport must stage for a batch of
        `nlanes` blocks of up to `maxlen` bytes: the HOST's geometry,
        which the staging budget counts (power-of-two bucketing for XLA
        retrace avoidance, lane alignment to whole codewords — and
        codewords-per-device when sharded — for the fused scrub
        kernel's parity output).  A scrub batch's device program may
        have more lanes than that: scrub_device_lanes."""
        cols = self._bucket(max(maxlen, 1))
        if kind in ("scrub", "encode"):
            lanes = self._batch_size(max(nlanes, 1))
            lanes += (-lanes) % self._lane_align()
        else:
            lanes = self._batch_size(max(nlanes, 1))
        return lanes, cols

    def _mosaic_device(self) -> bool:
        """Whether the codec's device is one Mosaic compiles for.  The
        fused latch starts up everywhere and falls on a CPU only when
        the first Pallas attempt fails; what the codec knows of its
        device before any attempt is the platform."""
        return self.device.platform == "tpu"

    def scrub_device_lanes(self, lanes: int) -> int:
        """Lanes of the batch the DEVICE hashes for a scrub batch staged
        at `lanes` (staging_geometry's): where the fused Pallas road
        will take the batch (its latch up, no mesh, a TPU) a count its
        hash kernel does not tile — a pass's tail, a store smaller than
        a batch — is raised to whole rows of SCRUB_LANE_FLOOR lanes
        (and whole codewords).  The pad is built on the device
        (pool_compose reads zeros for a lane without pages, the fused
        program pads a staged batch) with length 0 and the empty
        message's digest, so it verifies clean like the k-alignment
        pad; the host stages, and the budget counts, `lanes` rows.
        Elsewhere (a mesh, the CPU backend, a demoted latch) and for a
        count no such row count fits, the batch keeps `lanes` and runs
        the XLA fused program."""
        from .pallas_blake2s import lanes_supported

        pallas_road = (self._pallas_fused_ok and self.mesh is None
                       and self._mosaic_device())
        if not pallas_road or lanes_supported(lanes):
            return lanes
        step = math.lcm(SCRUB_LANE_FLOOR, self._lane_align())
        raised = -(-lanes // step) * step
        return raised if lanes_supported(raised) else lanes

    def _put(self, arr) -> jax.Array:
        """Batch-leading host array → the codec's device (split over
        the mesh when sharded: jit refuses a committed argument whose
        sharding differs from its in_shardings)."""
        return jax.device_put(arr, self._batch_sh)

    def _put_const(self, arr) -> jax.Array:
        """Constant → the codec's device (replicated when sharded)."""
        return jax.device_put(arr, self._repl_sh)

    def _to_device(self, arr: np.ndarray, shard: bool = False) -> jax.Array:
        """Adopt a staged host buffer ON THE CODEC'S DEVICE: dlpack
        zero-copy only where that device is a CPU device (the buffer
        already lives there), device_put (the H2D DMA) everywhere
        else.  `shard` splits it over the mesh (the inputs of the
        batch-sharded jits); otherwise it goes to the first device."""
        if shard and self.mesh is not None:
            return self._put(arr)
        if self.device.platform == "cpu":
            try:
                return jnp.from_dlpack(arr, device=self.device)
            except Exception:  # noqa: BLE001 — any dlpack refusal → plain put
                pass
        return jax.device_put(arr, self.device)

    def _gf_xla(self, u32, K):
        """The XLA GF apply.  Sharded, its input moves onto the mesh,
        padded to whole codewords per device (a lone decode has one)."""
        if self.mesh is None:
            return self._gf_jit(u32, K)
        n = u32.shape[0]
        pad = (-n) % self.mesh.size
        if pad:
            u32 = jnp.pad(u32, ((0, pad), (0, 0), (0, 0)))
        return self._gf_jit(jax.device_put(u32, self._batch_sh), K)[:n]

    def _mark_adopt(self) -> None:
        """Stamp the adoption boundary of the submission being built
        (LinkProfiler contract); its dispatch has not compiled yet."""
        self.last_adopt_ns = time.monotonic_ns()
        self.last_submit_compiled = False

    @contextlib.contextmanager
    def _dispatching(self, kind: str):
        """The dispatch of one submission, as the span `submit <kind>`:
        in the profiler's trace and on the thread's stack for the
        compile listener, not in the ring, where the transport's event
        of the same name stands.  Leaves `last_submit_compiled` saying
        whether JAX built or loaded a program inside it."""
        n0 = compile_listener.thread_compiles()
        try:
            with self.obs.timeline.span(f"submit {kind}", self.span_track,
                                        record=False):
                yield
        finally:
            if compile_listener.thread_compiles() > n0:
                self.last_submit_compiled = True

    def _mark_ready(self, handle) -> None:
        """Block until the device results exist, then stamp the ready
        boundary — everything after this in a collect is pure D2H
        materialization + reassembly (`collect`), everything before it
        since submit-return is device busy (`compute`)."""
        try:
            jax.block_until_ready(handle)
        except Exception:  # noqa: BLE001 — non-jax handles sync at asarray
            pass
        self.last_ready_ns = time.monotonic_ns()

    def probe_submit(self, arr: np.ndarray):
        """The transport's link probe op: upload a staged buffer and
        return a device scalar that DEPENDS on it (the only sync some
        remote backends honor — see HybridCodec._probe_once).  Compute
        is a trivial reduction, so the measured round-trip is
        transfer-bound like the retired probe, but through the NEW
        staging/adoption path."""
        if not hasattr(self, "_probe_sum_jit"):
            self._probe_sum_jit = jax.jit(
                lambda x: jnp.sum(x, dtype=jnp.uint32))
        da = self._to_device(arr)
        self._mark_adopt()
        with self._dispatching("probe"):
            return self._probe_sum_jit(da)

    def probe_collect(self, handle) -> int:
        self._mark_ready(handle)
        return int(np.asarray(handle))

    def hash_submit(self, arr: np.ndarray, lengths: np.ndarray):
        """Enqueue a staged hash batch WITHOUT synchronizing; returns
        the device digest array handle for hash_collect."""
        with self.obs.stage("h2d_transfer", "tpu"):
            da = self._to_device(arr, shard=True)
            dl = self._put(lengths)
        self._mark_adopt()
        with self._dispatching("hash"), \
                self.obs.stage("kernel_dispatch", "tpu"):
            return self._hash_jit(da, dl)

    def hash_collect(self, handle, n: int) -> List[Hash]:
        self._mark_ready(handle)
        h = np.asarray(handle)[:n]
        return [Hash(d) for d in digests_to_bytes(h)]

    def scrub_collect(self, out, parity_rows):
        """Materialize one scrub_encode_submit result: (ok full-lane
        bool array, parity | None).  `parity_rows` says which rows of
        the batch's parity leave the device: True every row, False or
        empty none, else the rows' indexes.  `parity[r]` is row r's
        (m, cols) bytes for every row asked for: the whole array, or a
        dict of the rows fetched one by one (`_parity_rows`).  Trimming
        to an entry's width is the transport's job (it knows the lane
        spans)."""
        _h, ok, _bad, parity = out
        if parity_rows is True:
            parity_rows = range(parity.shape[0])
        if not parity_rows:
            self._mark_ready(ok)
            return np.asarray(ok), None
        if self.mesh is not None or not parity_by_row(
                len(parity_rows), parity.shape[0]):
            # (a sharded array's rows lie on other chips: the mesh
            # brings the array)
            self._mark_ready((ok, parity))
            return np.asarray(ok), host_bytes(parity)
        picked = self._parity_rows(parity, parity_rows)
        self._mark_ready((ok, *picked))
        return np.asarray(ok), {int(r): host_bytes(p)
                                for r, p in zip(parity_rows, picked)}

    def _parity_rows(self, parity, rows) -> list:
        """The rows, each sliced out on the device by the geometry's
        one parity_row program: all dispatched before any is fetched."""
        rows_n, m, words = (int(d) for d in parity.shape)
        if not all(0 <= r < rows_n for r in rows):
            # on the device an index out of range is clamped, not refused
            raise ValueError(f"parity rows {list(rows)} of {rows_n}")
        key = ("parity_row", rows_n, m, 4 * words)
        return [self._pool_dispatch(key, parity, np.int32(r)) for r in rows]

    def _gf_submit(self, u32, K, mat: np.ndarray):
        """Dispatch one GF apply WITHOUT synchronizing, preferring the
        Pallas kernel with the same demotion policy as _gf_apply_np
        (a backend without Mosaic support must fall back to the XLA
        kernel, not fail the transport's batch).  Pallas failures that
        would only surface at sync time are the transport's
        note_sync_failure path."""
        pg = self._pallas_for(mat)
        if pg is not None:
            try:
                return pg(u32)
            except Exception as e:
                import logging

                if _pallas_error_is_permanent(e):
                    logging.getLogger("garage_tpu.ops").warning(
                        "pallas GF kernel unsupported on this backend "
                        "(permanent); using the XLA kernel", exc_info=True)
                    self._pallas_ok = False
                    self.obs.event("gf_demote", reason="permanent",
                                   error=f"{type(e).__name__}: {e}"[:200])
                else:
                    self._pallas_transient_fails += 1
                    if (self._pallas_transient_fails
                            >= PALLAS_MAX_TRANSIENT_FAILS):
                        self._pallas_ok = False
                        self.obs.event("gf_demote",
                                       reason="transient_limit",
                                       fails=self._pallas_transient_fails)
        return self._gf_xla(u32, K)

    def encode_submit(self, groups: np.ndarray):
        """Enqueue RS parity for staged (B, k, S) codeword groups
        without synchronizing (S must be a multiple of 4 — guaranteed
        by staging_geometry's bucketing).  Returns the parity as the
        device's uint32 words; encode_collect is the sync and views
        them as bytes on the host."""
        assert groups.shape[-1] % 4 == 0, groups.shape
        with self.obs.stage("h2d_transfer", "tpu"):
            u32 = self._to_device(host_words(
                groups.reshape(-1, groups.shape[-2], groups.shape[-1])))
        self._mark_adopt()
        with self._dispatching("encode"), \
                self.obs.stage("kernel_dispatch", "tpu"):
            return self._gf_submit(u32, self._K_enc, self._enc_mat)

    def encode_collect(self, handle) -> np.ndarray:
        self._mark_ready(handle)
        return host_bytes(handle)

    def decode_submit(self, shards: np.ndarray, present: Sequence[int],
                      rows: Optional[Sequence[int]] = None):
        """Enqueue one survivor-pattern decode over staged (B, p, S)
        shards without synchronizing; shares rs_reconstruct's
        mask-constant schedule cache.  → (uint32 words on the device, S)
        for decode_collect."""
        k, m = self.params.rs_data, self.params.rs_parity
        key = (tuple(present[:k]), tuple(rows) if rows is not None else None)
        cached = self._decode_w_cache.get(key)
        if cached is None:
            dec = gf256.rs_decode_matrix(k, m, present)
            if rows is not None:
                dec = np.ascontiguousarray(dec[list(rows)])
            cached = (self._put_const(gf_mask_consts(dec)), dec)
            self._decode_w_cache[key] = cached
        K, dec_mat = cached
        sub = shards[..., :k, :]
        s = sub.shape[-1]
        pad = (-s) % 4
        if pad:
            sub = np.pad(sub, [(0, 0)] * (sub.ndim - 1) + [(0, pad)])
        with self.obs.stage("h2d_transfer", "tpu"):
            u32 = self._to_device(host_words(sub))
        self._mark_adopt()
        with self._dispatching("decode"), \
                self.obs.stage("kernel_dispatch", "tpu"):
            return self._gf_submit(u32, K, dec_mat), s

    def decode_collect(self, handle) -> np.ndarray:
        """Ready-stamped decode materialization (the transport prefers
        this over a bare np.asarray so `compute` vs `collect` split
        holds for decode batches too)."""
        words, s = handle
        self._mark_ready(words)
        return host_bytes(words)[..., :s]

    # --- hashing ---
    @staticmethod
    def _bucket(n: int, quantum: int = 64) -> int:
        """Round up to a power-of-two multiple of `quantum` so variable-size
        batches land in O(log) distinct compiled shapes instead of one per
        length (XLA retrace avoidance)."""
        n = max(n, quantum)
        b = quantum
        while b < n:
            b <<= 1
        return b

    def _lane_align(self) -> int:
        """Lane-count divisor of _pad_group: whole codewords (k), and —
        when sharded — whole codewords per device (k × mesh, so the
        fused kernel's parity output dim B//k divides over the mesh)."""
        k = max(1, self.params.rs_data)
        return k * (self.mesh.size if self.mesh is not None else 1)

    def _batch_size(self, n: int) -> int:
        bsz = self._bucket(n, 8)
        if self.mesh is not None:
            m = self.mesh.size
            bsz += (-bsz) % m  # batch axis must divide over the mesh
        return bsz

    def _pad_batch(self, blocks: Sequence[bytes]) -> Tuple[np.ndarray, np.ndarray]:
        maxlen = max((len(b) for b in blocks), default=0)
        padded = self._bucket(maxlen)
        bsz = self._batch_size(len(blocks))
        arr = np.zeros((bsz, padded), dtype=np.uint8)
        lengths = np.zeros((bsz,), dtype=np.int32)
        for i, b in enumerate(blocks):
            arr[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
            lengths[i] = len(b)
        return arr, lengths

    def batch_hash(self, blocks: Sequence[bytes]) -> List[Hash]:
        if not blocks:
            return []
        arr, lengths = self._pad_batch(blocks)
        h = np.asarray(self._hash_jit(self._put(arr), self._put(lengths)))
        return [Hash(d) for d in digests_to_bytes(h[: len(blocks)])]

    def verify_one(self, block: bytes, hash: Hash) -> bool:
        """Single-block verify stays on the host CPU: one block cannot
        amortize a device dispatch (the accelerator may sit behind a
        high-latency link), and hashlib.blake2s is bit-identical to the
        device kernel (tests/test_codec_equivalence.py).  Batched paths
        (scrub/resync) run on device via batch_verify/scrub_encode."""
        import hashlib

        return hashlib.blake2s(block, digest_size=32).digest() == bytes(hash)

    def batch_verify(self, blocks: Sequence[bytes], hashes: Sequence[Hash]) -> np.ndarray:
        if len(blocks) != len(hashes):
            raise ValueError(f"{len(blocks)} blocks vs {len(hashes)} hashes")
        if not blocks:
            return np.zeros((0,), dtype=bool)
        arr, lengths = self._pad_batch(blocks)
        # Pad lanes (length 0) get the empty-message digest as their
        # expectation so they pass verify and don't inflate the corrupt count.
        import hashlib

        empty = np.frombuffer(
            hashlib.blake2s(b"", digest_size=32).digest(), dtype="<u4"
        )
        expected = np.broadcast_to(empty, (arr.shape[0], 8)).copy()
        expected[: len(blocks)] = np.stack(
            [np.frombuffer(bytes(h), dtype="<u4") for h in hashes]
        )
        _, ok, _ = self._verify_jit(
            self._put(arr), self._put(lengths), self._put(expected)
        )
        return np.asarray(ok)[: len(blocks)]

    # --- Reed-Solomon ---
    def _flat_padded(self, arr: np.ndarray) -> Tuple[np.ndarray, int]:
        """Flatten leading dims to one batch axis padded for the mesh."""
        flat = np.ascontiguousarray(arr, dtype=np.uint8).reshape(
            (-1,) + arr.shape[-2:]
        )
        n = flat.shape[0]
        bsz = self._batch_size(n) if self.mesh is not None else n
        if bsz != n:
            flat = np.concatenate(
                [flat, np.zeros((bsz - n,) + flat.shape[1:], dtype=np.uint8)]
            )
        return flat, n

    def _pallas_for(self, mat: np.ndarray):
        """PallasGf for this matrix, or None (unsupported backend)."""
        if not self._pallas_ok:
            return None
        key = mat.tobytes()
        pg = self._pallas_cache.get(key)
        if pg is None:
            from .pallas_gf import PallasGf

            pg = PallasGf(mat)
            self._pallas_cache[key] = pg
        return pg

    def _gf_apply_np(self, flat: np.ndarray, K,
                     mat: Optional[np.ndarray] = None) -> np.ndarray:
        """(N, k, S) uint8 through the mask-XOR kernel; S padded to ×4 for
        the uint32 view, result truncated back.  Prefers the Pallas
        kernel when the matrix is known and the backend supports Mosaic;
        falls back to the XLA formulation."""
        s = flat.shape[-1]
        pad = (-s) % 4
        if pad:
            flat = np.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
        u32 = jax.device_put(host_words(flat), self.device)
        if mat is not None:
            pg = self._pallas_for(mat)
            if pg is not None:
                try:
                    out = host_bytes(pg(u32))[..., :s]
                    # reset only after the host-side materialization
                    # proved the kernel ran (same rule as the fused
                    # latch)
                    self._pallas_transient_fails = 0
                    return out
                except Exception as e:
                    import logging

                    log = logging.getLogger("garage_tpu.ops")
                    # Latch OFF only for errors that cannot heal: a
                    # backend without Mosaic support will never grow it,
                    # but a flaky device (UNAVAILABLE / DEADLINE / RESET)
                    # recovers — permanently demoting the north-star
                    # kernel on one transient hiccup wasted the rest of
                    # the process lifetime (advisor r3 / VERDICT #8).
                    if _pallas_error_is_permanent(e):
                        log.warning(
                            "pallas GF kernel unsupported on this backend "
                            "(permanent); using the XLA kernel",
                            exc_info=True)
                        self._pallas_ok = False
                        self.obs.event("gf_demote", reason="permanent",
                                       error=f"{type(e).__name__}: {e}"[:200])
                    else:
                        self._pallas_transient_fails += 1
                        if (self._pallas_transient_fails
                                >= PALLAS_MAX_TRANSIENT_FAILS):
                            log.warning(
                                "pallas GF kernel failed %d consecutive "
                                "times; demoting to the XLA kernel",
                                self._pallas_transient_fails, exc_info=True)
                            self._pallas_ok = False
                            self.obs.event(
                                "gf_demote", reason="transient_limit",
                                fails=self._pallas_transient_fails)
                        else:
                            log.warning(
                                "pallas GF kernel transient failure "
                                "(%d/%d); will retry",
                                self._pallas_transient_fails,
                                PALLAS_MAX_TRANSIENT_FAILS, exc_info=True)
        return host_bytes(self._gf_xla(u32, K))[..., :s]

    def rs_encode(self, data: np.ndarray) -> np.ndarray:
        assert data.shape[-2] == self.params.rs_data, data.shape
        lead = data.shape[:-2]
        flat, n = self._flat_padded(data)
        out = self._gf_apply_np(flat, self._K_enc, mat=self._enc_mat)[:n]
        return out.reshape(lead + out.shape[-2:])

    def rs_reconstruct(self, shards: np.ndarray, present: Sequence[int],
                       rows: Optional[Sequence[int]] = None) -> np.ndarray:
        k, m = self.params.rs_data, self.params.rs_parity
        key = (tuple(present[:k]), tuple(rows) if rows is not None else None)
        cached = self._decode_w_cache.get(key)
        if cached is None:
            dec = gf256.rs_decode_matrix(k, m, present)
            if rows is not None:
                dec = np.ascontiguousarray(dec[list(rows)])
            cached = (self._put_const(gf_mask_consts(dec)), dec)
            self._decode_w_cache[key] = cached
        K, dec_mat = cached
        lead = shards.shape[:-2]
        flat, n = self._flat_padded(shards[..., :k, :])
        out = self._gf_apply_np(flat, K, mat=dec_mat)[:n]
        return out.reshape(lead + out.shape[-2:])

    # --- fused pipelined scrub (the north-star hot path) ---

    def _pad_group(self, blocks: Sequence[bytes], hashes: Sequence[Hash]):
        """Pad a block group to the compiled lane/byte shape: (arr, lengths,
        expected) with pad lanes carrying the empty-message digest so they
        verify clean and don't inflate the corruption count."""
        import hashlib as _hl

        arr, lengths = self._pad_batch(blocks)
        k = self.params.rs_data
        # lanes align to k (whole codewords) AND, when sharded, to
        # k × mesh — the fused kernel's PARITY output has leading dim
        # B//k, and its out_sharding over the mesh needs that divisible
        # by the device count (caught by the daemon-level sharded-scrub
        # test: lanes alone being mesh-divisible is not enough)
        align = self._lane_align()
        pad_lanes = (-arr.shape[0]) % align
        if pad_lanes:
            arr = np.pad(arr, [(0, pad_lanes), (0, 0)])
            lengths = np.pad(lengths, (0, pad_lanes))
        # the device's batch may have more lanes than were staged
        lanes = self.scrub_device_lanes(arr.shape[0])
        lengths = np.pad(lengths, (0, lanes - lengths.shape[0]))
        empty = np.frombuffer(
            _hl.blake2s(b"", digest_size=32).digest(), dtype="<u4"
        )
        expected = np.broadcast_to(empty, (lanes, 8)).copy()
        expected[: len(blocks)] = np.stack(
            [np.frombuffer(bytes(h), dtype="<u4") for h in hashes]
        )
        return arr, lengths, expected

    def _scrub_pallas(self):
        """The fused scrub jit (scrub_fused_pallas_step), with the GF
        kernel as Pallas too when that kernel's latch is up."""
        if self._scrub_pallas_jit is None:
            self._scrub_pallas_jit = jax.jit(
                scrub_fused_pallas_step(self._pallas_for(self._enc_mat)),
                static_argnums=(4,))
        return self._scrub_pallas_jit

    def _use_pallas_scrub(self, nlanes: int) -> bool:
        """The Pallas fused scrub wants whole (…,128)-lane tiles in a
        row count its hash kernel can tile.  On one chip every scrub
        batch has such a count: scrub_device_lanes raises a smaller one
        (a pass's tail) to a row, because a row of the kernel costs less
        than the XLA variant's scan does from the second lane up.  The
        XLA variant is the fallback: a mesh, a backend without Mosaic,
        a demoted latch, a count no row count fits."""
        from .pallas_blake2s import lanes_supported

        return (self._pallas_fused_ok and self.mesh is None
                and lanes_supported(nlanes))

    def _note_fused_failure(self, e: BaseException) -> None:
        import logging

        log = logging.getLogger("garage_tpu.ops")
        if _pallas_error_is_permanent(e):
            log.warning(
                "pallas fused scrub unsupported on this backend "
                "(permanent); using the XLA kernels", exc_info=True)
            self._pallas_fused_ok = False
            self.obs.event("fused_demote", reason="permanent",
                           error=f"{type(e).__name__}: {e}"[:200])
        else:
            self._pallas_fused_fails += 1
            if self._pallas_fused_fails >= PALLAS_MAX_TRANSIENT_FAILS:
                log.warning(
                    "pallas fused scrub failed %d consecutive times; "
                    "demoting to the XLA kernels",
                    self._pallas_fused_fails, exc_info=True)
                self._pallas_fused_ok = False
                self.obs.event("fused_demote", reason="transient_limit",
                               fails=self._pallas_fused_fails,
                               error=f"{type(e).__name__}: {e}"[:200])
            else:
                log.warning(
                    "pallas fused scrub transient failure (%d/%d); "
                    "will retry", self._pallas_fused_fails,
                    PALLAS_MAX_TRANSIENT_FAILS, exc_info=True)
                self.obs.event("fused_transient",
                               reason=type(e).__name__,
                               fails=self._pallas_fused_fails)

    def note_sync_failure(self, e: BaseException,
                          variant: Optional[str] = None) -> None:
        """Sync-time kernel failure — surfacing at the caller's
        np.asarray (the transport's collect, scrub_encode_batch), long
        after the submit returned.  Routes the failure into the
        fused-scrub demotion latch when the failing submission came
        from the Pallas variant: a consistently sync-failing kernel must
        demote to the XLA fallback instead of silently losing the device
        side every pass."""
        if (variant or self.last_submit_variant) == "pallas":
            self._note_fused_failure(e)

    def note_sync_success(self, variant: Optional[str] = None) -> None:
        """Successful host-side materialization of a submission — the
        ONLY point the fused-kernel transient-failure counter resets
        (resetting at submit time, before the kernel provably ran,
        defeated the latch)."""
        if (variant or self.last_submit_variant) == "pallas":
            self._pallas_fused_fails = 0

    def scrub_encode_submit(self, arr: np.ndarray, lengths: np.ndarray,
                            expected: np.ndarray):
        """Enqueue ONE device dispatch doing verify + RS(k,m) parity for a
        full batch; returns device arrays WITHOUT synchronizing, so callers
        can pipeline batches and hide the dispatch latency.  `lengths` and
        `expected` may have more lanes than `arr` (scrub_device_lanes):
        the device zero-extends the batch to them.

        Sets `last_submit_variant` ("pallas"|"xla") for the caller to
        thread into note_sync_{success,failure}: kernel failures surface
        only at sync time, and the demotion latch must attribute them to
        the variant that actually produced the arrays.  The transient-
        failure counter is NOT reset here — a submit returning is proof
        of nothing on an async backend; the reset
        happens in note_sync_success."""
        assert arr.shape[0] % self.params.rs_data == 0
        assert arr.shape[1] % 4 == 0
        with self.obs.stage("h2d_transfer", "tpu"):
            da = self._to_device(arr, shard=True)
            dl = self._put(lengths)
            de = self._put(expected)
        return self._scrub_dispatch(da, dl, de)

    def _scrub_dispatch(self, data, dl, de):
        """Dispatch the fused kernel on a batch that is on the device:
        the Pallas variant where its latch and the lane count allow,
        else (or after its failure) the XLA one.  `dl` and `de` have the
        device batch's lanes (scrub_device_lanes), `data` those or the
        rows that were staged."""
        self._mark_adopt()
        with self._dispatching("scrub"):
            if self._use_pallas_scrub(dl.shape[0]):
                try:
                    with self.obs.stage("kernel_dispatch", "tpu"):
                        out = self._scrub_pallas()(
                            data, dl, de, self._K_enc, self.params.rs_data,
                        )
                    self.last_submit_variant = "pallas"
                    return out
                except Exception as e:
                    self._note_fused_failure(e)
            if dl.shape[0] != data.shape[0]:
                # the lane pad was for the road that has just failed
                dl, de = dl[:data.shape[0]], de[:data.shape[0]]
            if self.mesh is not None:
                # a batch composed on the pool's device (the first)
                # moves onto the mesh; one staged there already stays
                data = jax.device_put(data, self._batch_sh)
            with self.obs.stage("kernel_dispatch", "tpu"):
                out = self._scrub_jit(
                    data, dl, de, self._K_enc, self.params.rs_data,
                )
            self.last_submit_variant = "xla"
            return out

    # --- the DevicePool API (ops/device_pool.py) ---
    #
    # Pool-aware scrub: only MISS lanes cross the link (one compact
    # H2D upload, its row count bucketed); resident lanes are gathered
    # from the pool's page array on the device.  One program composes a
    # batch and one adopts its verified misses, each of a shape fixed by
    # the batch's geometry and its miss bucket: a closed set, compiled
    # ahead and dispatched as executables.  The composed batch runs the
    # SAME fused kernel as the plain path, so pool-served lanes are
    # re-verified against their expected digests on every read.

    def pool_alloc(self, npages: int, page_bytes: int):
        """The pool's pages, zeroed, on the codec's (first) device."""
        if page_bytes % (4 * POOL_TILE):
            raise ValueError(f"a pool page is whole tiles of {4 * POOL_TILE} "
                             f"bytes, not {page_bytes}")
        self._pool_geom = (int(npages), int(page_bytes))
        return self._pool_dispatch(("alloc",) + self._pool_geom)

    def pool_program_keys(self, lanes: int, cols: int) -> List[tuple]:
        """The pool programs a (lanes, cols) device batch can dispatch:
        one adopt, one compose for every miss bucket, and where the
        batch is encoded the one that slices a row out of its parity.
        The miss rows are bucketed by the lanes that were STAGED
        (transport._stage_scrub_pooled), so the buckets are those of
        every staged count the device builds at `lanes`: `lanes`
        itself, and under the lane floor the smaller counts
        scrub_device_lanes raises to it."""
        geom = self._pool_geom + (int(lanes), int(cols))
        k, m = self.params.rs_data, self.params.rs_parity
        staged, n = {int(lanes)}, 8
        while n < lanes:
            held = self.staging_geometry(n, 1, "scrub")[0]
            if self.scrub_device_lanes(held) == lanes:
                staged.add(held)
            n <<= 1
        buckets = sorted({mb for held in staged for mb in miss_buckets(held)})
        return [("adopt",) + geom] + [
            ("compose",) + geom + (mb,) for mb in buckets
        ] + ([("parity_row", int(lanes) // k, m, int(cols))] if k else [])

    def pool_warm(self, lanes: int, cols: int) -> None:
        """Compile every pool program of a (lanes, cols) batch."""
        for key in self.pool_program_keys(lanes, cols):
            self._pool_program(key)

    def _pool_program(self, key: tuple):
        """The compiled executable of one member of the closed set."""
        exe = self._pool_execs.get(key)
        if exe is None:
            from jax.sharding import SingleDeviceSharding

            exe = self._pool_execs[key] = self.pool_lowered(
                key, SingleDeviceSharding(self.device)).compile()
        return exe

    @staticmethod
    def pool_lowered(key: tuple, sharding):
        """One pool program, lowered for arrays placed by `sharding`."""
        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        if key[0] == "parity_row":
            rows, m, cols = key[1:]
            return jax.jit(parity_row).lower(
                spec((rows, m, cols // 4), jnp.uint32), spec((), jnp.int32))
        op, npages, page = key[:3]
        pool = spec((npages, page // 4 // POOL_TILE, POOL_TILE), jnp.uint32)
        if op == "alloc":
            return jax.jit(pool_alloc, static_argnums=(0, 1),
                           out_shardings=sharding).lower(npages, page // 4)
        lanes, cols = key[3:5]
        index = spec((lanes * max(1, -(-cols // page)),), jnp.int32)
        if op == "adopt":
            return jax.jit(pool_adopt).lower(
                pool, spec((lanes, cols // 4), jnp.uint32), index)
        mb = key[5]
        miss = (spec((mb, cols // 4), jnp.uint32),
                spec((mb,), jnp.int32)) if mb else (None, None)
        return jax.jit(pool_compose, static_argnums=(4, 5)).lower(
            pool, index, *miss, lanes, cols // 4)

    def _pool_dispatch(self, key: tuple, *args):
        self.obs.note_pool_program(key[0])
        return self._pool_program(key)(*args)

    def scrub_encode_submit_resident(self, miss_arr: np.ndarray,
                                     miss_rows, lengths: np.ndarray,
                                     expected: np.ndarray, pool,
                                     row_pages: np.ndarray):
        """`miss_arr`: the staged miss rows, (miss bucket, cols), of
        which the first len(miss_rows) go to the lanes `miss_rows`;
        `pool`: the page array; `row_pages`: the slot of every page of
        every row (DevicePool.row_index).  Returns (scrub handle,
        composed device input) — the input ref, (lanes, cols ÷ 4)
        words, is what pool_adopt takes verified miss lanes from."""
        lanes = int(lengths.shape[0])
        mb, cols = (int(d) for d in miss_arr.shape)
        assert lanes % self.params.rs_data == 0
        assert cols % 4 == 0
        # a row the pool serves has a slot for its first page
        resident_rows = int(np.count_nonzero(
            row_pages.reshape(lanes, -1)[:, 0] < pool.shape[0]))
        compose = ("compose",) + self._pool_geom + (lanes, cols, mb)
        # `compose`: the part of `adopt` with a stamp of its own; the
        # first batch of a geometry builds that geometry's programs here
        with self.obs.timeline.span(
                "compose", self.span_track, miss_rows=len(miss_rows),
                resident_rows=resident_rows) as sp:
            if compose not in self._pool_execs:
                self.pool_warm(lanes, cols)
            with self.obs.stage("h2d_transfer", "tpu"):
                miss = (None, None)
                if mb:
                    rows = np.full((mb,), lanes, dtype=np.int32)
                    rows[:len(miss_rows)] = miss_rows
                    miss = (self._to_device(host_words(miss_arr)),
                            jax.device_put(rows, self.device))
                index = jax.device_put(row_pages, self.device)
                dl = self._put(lengths)
                de = self._put(expected)
            full = self._pool_dispatch(compose, pool, index, *miss)
        self.obs.note_substage("compose", sp.t1 - sp.t0)
        return self._scrub_dispatch(full, dl, de), full

    def pool_adopt(self, pool, batch, dst: np.ndarray):
        """The pool's array with the pages of the composed `batch`
        written to the slots `dst` names (DevicePool.adopt_lanes) —
        one device program, ZERO link bytes but the index vector."""
        lanes, words = (int(d) for d in batch.shape)
        return self._pool_dispatch(
            ("adopt",) + self._pool_geom + (lanes, 4 * words),
            pool, batch, jax.device_put(dst, self.device))

    def pool_read(self, pool, slots, length: int) -> bytes:
        """D2H readback of a pooled block (tests/debug only): one
        gather of its pages, trimmed to the ragged tail."""
        index = np.full((self._bucket(len(slots), 1),), pool.shape[0],
                        dtype=np.int32)
        index[:len(slots)] = slots
        pages = self._pool_read_jit(pool, jax.device_put(index, self.device))
        return host_bytes(pages).reshape(-1)[:int(length)].tobytes()

    def scrub_encode_batch(self, blocks: Sequence[bytes], hashes: Sequence[Hash],
                           fetch_parity=True):
        """Synchronous fused verify+encode.  Contract shared with
        BlockCodec.scrub_encode_batch: returns (ok (B,), parity) — every
        row's parity as (ceil(B/k), m, maxlen), trimmed of lane/column
        padding (pad rows/columns are zero blocks → zero parity); the
        rows `fetch_parity` names as a dict of (m, maxlen); with none
        asked for it stays on the device and None is returned."""
        with self.obs.stage("host_staging", "tpu"):
            arr, lengths, expected = self._pad_group(blocks, hashes)
        out = self.scrub_encode_submit(arr, lengths, expected)
        n = len(blocks)
        variant = self.last_submit_variant
        try:
            with self.obs.stage("sync_collect", "tpu"):
                ok, parity = self.scrub_collect(out, fetch_parity)
        except Exception as e:
            self.note_sync_failure(e, variant)
            raise
        self.note_sync_success(variant)
        ok = ok[:n]
        if parity is None:
            return ok, None
        maxlen = max(len(b) for b in blocks)
        if isinstance(parity, dict):
            return ok, {r: p[:, :maxlen] for r, p in parity.items()}
        k = self.params.rs_data
        return ok, parity[:(n + k - 1) // k, :, :maxlen]


# --- multi-chip sharded variants (pod-scale batches) ------------------------


def sharded_fns(mesh: "jax.sharding.Mesh", axis: str = "data"):
    """Return {verify, rs_encode} jitted with batch dims sharded over `mesh`.

    The codec batch axis is embarrassingly parallel; sharding it over the
    mesh scales scrub/encode throughput linearly over ICI-connected chips.
    `verify` additionally returns a globally psum-reduced corruption count,
    exercising a real cross-chip collective.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    batch_sharded = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())

    def _verify(data_u8, lengths, expected):
        h, ok, bad = verify_kernel(data_u8, lengths, expected)
        return h, ok, bad

    verify = jax.jit(
        _verify,
        in_shardings=(batch_sharded, batch_sharded, batch_sharded),
        out_shardings=(batch_sharded, batch_sharded, repl),
    )

    def _encode(shards, w_bits):
        return gf_bitmatmul(shards, w_bits)

    rs_encode = jax.jit(
        _encode,
        in_shardings=(batch_sharded, repl),
        out_shardings=batch_sharded,
    )
    return {"verify": verify, "rs_encode": rs_encode}
