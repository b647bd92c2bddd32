"""The yardstick's own tests (`benchmarks/tests/`), collected here so
that tier-1 (`pytest tests/`) guards the readers and the cells: every
test function of every `benchmarks/tests/test_*.py` is taken into this
module under `test_<file>__<name>`.  One file, so one xdist worker
(`--dist loadfile`) runs them, in five to six minutes (94 cases): the
long pole of a tier-1 run on six workers."""

import importlib
import pkgutil

import pytest

import benchmarks.tests as _suite


@pytest.fixture(autouse=True)
def _the_deployments_codec(monkeypatch):
    """`tests/conftest.py` makes `backend = "cpu"` the default of every
    in-process node; the benchmark's cells are the shipped default (the
    hybrid codec, whose device is the CPU backend here)."""
    import garage_tpu.utils.config as gconf
    from conftest import _orig_config_from_dict

    monkeypatch.setattr(gconf, "config_from_dict", _orig_config_from_dict)


for _info in pkgutil.iter_modules(_suite.__path__):
    if not _info.name.startswith("test_"):
        continue
    _mod = importlib.import_module(f"{_suite.__name__}.{_info.name}")
    for _name, _obj in vars(_mod).items():
        if _name.startswith("test_") and callable(_obj):
            globals()[f"test_{_info.name[5:]}__{_name[5:]}"] = _obj
