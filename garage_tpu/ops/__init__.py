"""ops — the BlockCodec device-op layer (the genuinely new layer vs reference).

The reference's block layer does integrity hashing and (in our north star)
Reed-Solomon erasure coding one block at a time on CPU
(ref src/block/block.rs:66-78 verify, src/block/repair.rs:438-490 scrub).
Here those become *batch* operations behind the `BlockCodec` interface, with:

  - cpu backend (cpu_codec.py): hashlib + numpy GF(2^8) (+ optional C++
    native kernel, native/gf256.cpp) — the correctness baseline;
  - tpu backend (tpu_codec.py): JAX — BLAKE2s as a vectorized uint32 scan
    (tpu_blake2s.py), RS(k,m) encode/decode as GF(2) bit-matrix matmuls on
    the MXU (gf256.py bitmatrix construction), shardable over a device mesh.

Both backends are bit-identical; tests/test_codec_equivalence.py enforces it.
"""

from __future__ import annotations

from .codec import BlockCodec, CodecParams


def tpu_devices() -> list:
    """The devices `backend = "tpu"` computes on.  That backend REQUIRES
    the device: with none present it raises here, at construction,
    instead of computing on whatever jax.devices() returns (the CPU
    floor is `backend = "hybrid"`, which attaches a device when one is
    there)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            'codec.backend = "tpu" but JAX found no TPU (default '
            f'platform {devs[0].platform!r}); use "hybrid" for a CPU '
            "floor that attaches a device when present")
    return devs


def make_codec(backend: str = "cpu", metrics=None, tracer=None,
               **kw) -> BlockCodec:
    """Codec factory — `codec.backend` in config selects this.

    `metrics`/`tracer` plumb the System-owned MetricsRegistry and Tracer
    into the codec (BlockManager passes its own): per-stage histograms,
    bytes-by-side counters, and the gate-decision event ring then show
    up on /metrics and the admin `codec info`/`codec events` commands."""
    if backend == "cpu":
        from .cpu_codec import CpuCodec
        return CpuCodec(CodecParams(**kw), metrics=metrics, tracer=tracer)
    if backend == "tpu":
        from .tpu_codec import TpuCodec
        return TpuCodec(CodecParams(**kw), devices=tpu_devices(),
                        metrics=metrics, tracer=tracer)
    if backend == "hybrid":
        from .hybrid_codec import HybridCodec
        # async: the daemon must come up on the CPU floor even if JAX
        # backend init is slow or finds no device; the device codec
        # attaches in the background when ready
        return HybridCodec(CodecParams(**kw), build_device="async",
                           metrics=metrics, tracer=tracer)
    raise ValueError(f"unknown codec backend {backend!r}")
