"""Test config: run JAX on a virtual 8-device CPU mesh.

The tests run on the CPU only; sharding tests use a virtual 8-device CPU
platform per the standard JAX testing pattern.  Tests never touch a chip
(chip_smoke.py does, through the chip tool).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import asyncio  # noqa: E402
import inspect  # noqa: E402

import jax  # noqa: E402  (import after env is set)


def pytest_pyfunc_call(pyfuncitem):
    """Run `async def` tests via asyncio.run (pytest-asyncio is not in the
    image; `pytestmark = pytest.mark.asyncio` markers are inert no-ops)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: async test (run via asyncio.run)")
    config.addinivalue_line(
        "markers", "slow: chaos soaks / long drives, excluded from tier-1")
    config.addinivalue_line(
        "markers",
        "cluster: ≥20-node SimCluster drives (always also marked slow so "
        "tier-1 stays fast; select with -m cluster)")

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# `backend = "tpu"` requires a TPU and raises without one
# (ops.tpu_devices).  The dedicated codec tests run that backend's code
# on the virtual CPU devices, so they are handed those; the raise itself
# is pinned by tests/test_device_placement.py against the original.
import garage_tpu.ops as _gops  # noqa: E402

REAL_TPU_DEVICES = _gops.tpu_devices
_gops.tpu_devices = jax.devices

# The production default codec backend is "hybrid" (async background
# device attach).  In-process test clusters must stay deterministic: the
# attach landing mid-test would switch scrub/verify between backends
# run-to-run (bit-identical results, but timing-sensitive tests would
# exercise different code paths) and pay per-manager jit overhead on the
# 1-core CI host.  Inject backend="cpu" wherever a test config does not
# choose one explicitly; hybrid/tpu behavior is covered by the dedicated
# codec tests that opt in.
import garage_tpu.utils.config as _gconf  # noqa: E402

_orig_config_from_dict = _gconf.config_from_dict


def _cpu_codec_default(d, *a, **kw):
    d = dict(d)
    codec = dict(d.get("codec") or {})
    codec.setdefault("backend", "cpu")
    d["codec"] = codec
    return _orig_config_from_dict(d, *a, **kw)


_gconf.config_from_dict = _cpu_codec_default

# Parity GC grace shields live blocks from in-flight insert-queue refs;
# real clusters wait 5 s, but in-process tests would spend that wall-
# clock on every deletion.  0.3 s still exercises the re-check path.
import garage_tpu.model.parity_repair as _gpr  # noqa: E402

_gpr.PARITY_GC_GRACE_S = 0.3
