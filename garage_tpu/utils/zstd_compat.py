"""zstandard import shim — the real wheel when present, a gated fallback
when not.

Every compression call site imports `zstandard` through this module.
With the wheel installed the name IS the wheel (zero behavior change,
real zstd frames on the wire).  Without it — containers this repo grows
in do not always ship the wheel, and installing one is off-limits — a
minimal API-compatible fallback backed by zlib keeps the block layer
functional: `ZstdCompressor(level, write_checksum, write_content_size)`,
`ZstdDecompressor().decompress/.decompressobj()`, and `ZstdError` on
corrupt frames (zlib's adler32 trailer provides the checksum-verify
property block.rs:66-78 relies on).

Fallback frames carry a private magic and are only readable by the same
fallback — NOT zstd on the wire.  That is acceptable because a cluster
without the wheel is a test/dev cluster; a mixed wheel/fallback cluster
should run with compression_level = None.
"""

from __future__ import annotations

try:
    import zstandard  # noqa: F401  (the real wheel: re-exported as-is)

    HAVE_ZSTD = True
    # which compressor writes this node's `.zst` files (`codec info`)
    COMPRESSOR = f"zstandard {zstandard.__version__}"
except ImportError:
    import types
    import zlib

    HAVE_ZSTD = False
    COMPRESSOR = "zlib-fallback"
    _MAGIC = b"GTZF"

    class ZstdError(Exception):
        pass

    class _Compressor:
        def __init__(self, level: int = 1, write_checksum: bool = True,
                     write_content_size: bool = True, **_kw):
            # zlib levels top out at 9; zstd levels can be higher
            self._level = max(1, min(int(level), 9))

        def compress(self, data: bytes) -> bytes:
            return _MAGIC + zlib.compress(data, self._level)

    class _DecompressObj:
        """Incremental decompressor matching the zstandard
        decompressobj() surface the streaming read path uses."""

        def __init__(self):
            self._hdr = b""
            self._z = zlib.decompressobj()

        def decompress(self, chunk: bytes) -> bytes:
            if len(self._hdr) < len(_MAGIC):
                need = len(_MAGIC) - len(self._hdr)
                self._hdr += bytes(chunk[:need])
                chunk = chunk[need:]
                if len(self._hdr) == len(_MAGIC) and self._hdr != _MAGIC:
                    raise ZstdError("bad fallback frame magic")
                if not chunk:
                    return b""
            try:
                return self._z.decompress(chunk)
            except zlib.error as e:
                raise ZstdError(str(e)) from None

    class _Decompressor:
        def decompress(self, data: bytes) -> bytes:
            if data[: len(_MAGIC)] != _MAGIC:
                raise ZstdError("bad fallback frame magic")
            try:
                return zlib.decompress(data[len(_MAGIC):])
            except zlib.error as e:
                raise ZstdError(str(e)) from None

        def decompressobj(self) -> _DecompressObj:
            return _DecompressObj()

    zstandard = types.SimpleNamespace(
        ZstdError=ZstdError,
        ZstdCompressor=_Compressor,
        ZstdDecompressor=_Decompressor,
        __fallback__=True,
    )
