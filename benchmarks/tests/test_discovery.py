"""A cell, a mix, a configuration, a per-layer metric and its reader
dropped into a copy of `benchmarks/` run without an edit to any file
that was there (entries are added to the copy's BENCHMARK.json)."""

import json
import pathlib
import shutil

from benchmarks import harness
from benchmarks.tests.tiny import run, tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "benchmarks"
    shutil.copytree(ROOT / "benchmarks", root,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())

    conf = json.loads((root / "configs" / "rep3-1m.json").read_text())
    conf["name"] = "rep3-64k"
    (root / "configs" / "rep3-64k.json").write_text(json.dumps(conf))
    shutil.copy(root / "configs" / "rep3-1m.reference.py",
                root / "configs" / "rep3-64k.reference.py")
    mix = json.loads((root / "traffic" / "scrub.json").read_text())
    mix.update(corrupt_per_pass=3, node_under_test=2)
    (root / "traffic" / "scrub3.json").write_text(json.dumps(mix))
    (root / "readers" / "window_seconds.py").write_text(
        "def read(window, scale=1.0):\n"
        "    return {'value': window['window_s'] * scale, 'samples': 1}\n")
    (root / "metrics" / "window_ms.scrub3.json").write_text(json.dumps(
        {"reader": "window_seconds", "params": {"scale": 1000.0}}))

    manifest["configs"].append({
        "name": "rep3-64k", "source": conf["source"], "reduced": ["store"],
        "file": "benchmarks/configs/rep3-64k.json", "why": "a test's"})
    manifest["workloads"].append({
        "name": "rep3-64k.scrub3", "config": "rep3-64k", "traffic": "scrub3",
        "chips": 1, "why": "a test's"})
    for m in manifest["end_to_end"]:
        if "rep3-1m.scrub" in m.get("workloads", []):
            m["workloads"].append("rep3-64k.scrub3")
    manifest["per_layer"].append({
        "name": "window_ms.scrub3", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "a test's", "moves": "scrub_mib_s",
        "workloads": ["rep3-64k.scrub3"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = tiny(harness.Cell("rep3-64k.scrub3", root=root))
    assert cell.mix["corrupt_per_pass"] == 3
    res = run(cell, seed=41)
    assert res["correct"], res["compared"]
    assert {"scrub_mib_s", "setup_s"} == set(res["metrics"])
    layer = cell.read_per_layer({"window_s": 2.0})
    assert layer == {"window_ms.scrub3": {"value": 2000.0, "unit": "ms"}}
    assert all(p.read_bytes() == data for p, data in before.items())
