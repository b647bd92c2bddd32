"""The yardstick's own tests (`benchmarks/tests/`), collected here so
that tier-1 (`pytest tests/`) guards the readers and the cells: every
test function of every `benchmarks/tests/test_*.py` is taken into this
module under `test_<file>__<name>`.  One file, so one xdist worker
(`--dist loadfile`) runs them, in six to seven minutes (95 cases): the
long pole of a tier-1 run on six workers."""

import functools
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


def _fails_as_expected(fn, raises, why, match=None):
    """`fn` as a strict expected failure: it has to raise `raises` (with
    a message that matches `match`), and is then reported as xfail; a
    run that raises anything else, or nothing, fails."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with pytest.raises(raises, match=match):
            fn(*args, **kwargs)
        pytest.xfail(why)
    return run


# Two tests of `benchmarks/tests/` describe the program as an earlier PR
# left it, and no PR but a `benchmark` PR may edit a file there.  Each
# is a strict expected failure on exactly what changed, so the
# `benchmark` PR that repairs it (PERF.md section 7) has to take its
# entry out, and what it guards otherwise is held by a test below.
# (1) `ec84-zst.scrub`'s describes the program as PR 35 left it.  Since PR 36 the
# read-ahead's threads inflate a batch and the segment `decompress` stays
# 0 in a pass, which is what `scrub_decompress_ms_per_gib` was built to
# show; the test asks for it above 0 and above the self time
# (`test_the_stored_cell_traced`).  (2) `ec84-ingest.scrub`'s describes
# PR 43's: "codewords moved: most were written anew", asked for as a
# `parity_fetch_share.scrub` above a static store's.  Since PR 44 a
# codeword keeps its members and the share is under it
# (`test_the_ingest_cell_traced`).
_STALE = {
    ("test_cells_ingest",
     "test_a_traced_run_is_correct_and_reads_what_the_deployment_added"):
        # (a window the gate sent to the CPU side counts no parity row,
        # and the test then fails on the same line by the metric's key)
        dict(raises=(AssertionError, KeyError),
             match=r"^assert \d+\.\d+ > 66\.6|parity_fetch_share\.scrub",
             why="asks for most codewords written anew; since PR 44 a "
                 "block written between two passes moves none"),
    ("test_cells_stored",
     "test_a_traced_run_is_correct_and_reads_what_the_deployment_added"):
        dict(raises=AssertionError,
             match=r"'scrub_decompress_ms_per_gib': 0\.0, ",
             why="asks for a decompress segment above its self time; "
                 "since PR 36 the worker's path holds none"),
}

for _info in pkgutil.iter_modules(_suite.__path__):
    if not _info.name.startswith("test_"):
        continue
    _mod = importlib.import_module(f"{_suite.__name__}.{_info.name}")
    for _name, _obj in vars(_mod).items():
        if _name.startswith("test_") and callable(_obj):
            if (_info.name, _name) in _STALE:
                _obj = _fails_as_expected(_obj, **_STALE[_info.name, _name])
            globals()[f"test_{_info.name[5:]}__{_name[5:]}"] = _obj


def test_the_stored_cell_traced():
    """What the stale test above guards, with PR 36's reading of the
    worker's path: a traced tiny run of `ec84-zst.scrub` is correct and
    reports the deployment's three metrics and the lane's two."""
    from benchmarks import harness
    from benchmarks.tests.tiny import run, tiny

    res = run(tiny(harness.Cell("ec84-zst.scrub")), seed=2**31 + 19,
              trace=True)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["compared"]) == {"blocks_wrong", "form_wrong",
                                    "plants_missed", "parity_wrong",
                                    "gets_wrong"}
    got = {name: m["value"] for name, m in res["metrics"].items()}
    # the inflate is no segment of the worker's; zstd's seconds are
    # still counted, once a block, and the files are what they were
    assert got["scrub_decompress_ms_per_gib"] == 0
    assert got["scrub_decompress_self_ms_per_gib"] > 0
    assert 85.0 < got["scrub_disk_share.scrub"] < 95.0
    assert got["scrub_hop_wait_ms_per_gib"] > 0
    assert 0 <= got["scrub_hint_sent_share.scrub"] <= 100
    if got["tpu_byte_share.scrub"] == 100.0:
        assert {m["name"]
                for m in harness.Cell("ec84-1m.scrub").per_layer()
                if m["source"] != "device_trace"} <= set(got)


def test_the_ingest_cell_traced():
    """What the stale test of `ec84-ingest.scrub` guards, with PR 44's
    reading of a store that took writes: a traced tiny run is correct by
    its seven counts and reports the deployment's metrics; the codewords
    of the last pass are settled, and what is fetched, written anew and
    purged is what the new blocks, the heals and the PUTs' write-time
    codewords account for, not the store."""
    from benchmarks import harness
    from benchmarks.tests.test_cells_ingest import COUNTS, NEW, tiny_ingest
    from benchmarks.tests.tiny import run

    res = run(tiny_ingest(harness.Cell("ec84-ingest.scrub")),
              seed=2**31 + 29, trace=True)
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == COUNTS
    assert res["failed"] == 0 and res["attempted"] > 0
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert set(NEW) | {"scrub_settled_share.scrub"} <= set(got)
    assert got["scrub_settled_share.scrub"] >= 75.0
    # (counted by the transport: absent where the gate held the window)
    assert got.get("parity_fetch_share.scrub", 0.0) <= 25.0
    assert got["scrub_sidecar_rewrite_share.scrub"] <= 12.5
    assert got["scrub_purge_ms_per_gib"] > 0
    assert got["ingest_put_ms.scrub"] > 0
    assert 0 <= got["tpu_byte_share.hash"] <= 100
    assert got["compiles_per_pass.scrub"] == 0
