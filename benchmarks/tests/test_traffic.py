"""The scrub mix's generator of faults: data in, the same faults out,
and nothing listed inside the window."""

import os
import types

from benchmarks import harness

KIND = harness.load_module(harness.HERE / "kinds" / "scrub_passes.py", "k")


def store(tmp_path, blocks=24, k=4):
    """A node's data dir as the generator sees it: block files under
    xx/yy/<64 hex>, and sidecars the scrub would have written."""
    st = types.SimpleNamespace(
        data_dir=str(tmp_path), parity=True, rs_data=k, victims=[],
        sidecar_members={}, sidecars=[],
        mix={"corrupt_per_pass": 2, "sidecars_per_pass": 2})
    names = sorted(f"{i:02x}" * 32 for i in range(blocks))
    for h in names:
        d = tmp_path / h[:2] / h[2:4]
        d.mkdir(parents=True)
        (d / h).write_bytes(bytes(100 + int(h[:2], 16)))
    KIND.survey_blocks(st)
    for g in range(blocks // k):
        path = tmp_path / "parity" / f"{g:02x}" / f"{g:064x}.par"
        path.parent.mkdir(parents=True)
        path.write_bytes(b"x")
        st.sidecar_members[str(path)] = names[g * k:(g + 1) * k]
    KIND.survey_sidecars(st)
    return st


def test_same_seed_same_faults(tmp_path):
    a = KIND.plant(store(tmp_path / "a"), 2**31 + 5, 3, 0.0)
    b = KIND.plant(store(tmp_path / "b"), 2**31 + 5, 3, 0.0)
    other_pass = KIND.plant(store(tmp_path / "c"), 2**31 + 5, 4, 0.0)
    other_seed = KIND.plant(store(tmp_path / "d"), 2**31 + 6, 3, 0.0)

    def names(planted):
        return ([h for h, _p in planted["victims"]],
                [os.path.basename(p) for p in planted["removed"]])

    assert names(a) == names(b)
    assert names(a) != names(other_pass) and names(a) != names(other_seed)


def test_every_pass_plants_the_mix_and_one_byte_a_victim(tmp_path):
    st = store(tmp_path)
    before = {p: open(p, "rb").read() for _h, p in st.files}
    planted = KIND.plant(st, 7, 1, 0.0)
    assert len(planted["victims"]) == 2 and len(planted["removed"]) == 2
    assert planted["blocks"] == 24 and planted["bytes"] == st.store_bytes
    hit = {h for h, _p in planted["victims"]}
    for h, p in st.files:
        now = open(p, "rb").read()
        differ = sum(x != y for x, y in zip(now, before[p]))
        assert differ == (1 if h in hit else 0) and len(now) == len(before[p])
    for path in planted["removed"]:
        assert not os.path.exists(path)
        assert hit.isdisjoint(st.sidecar_members[path])


def test_a_block_awaiting_its_heal_is_not_counted_and_stops_removals(tmp_path):
    st = store(tmp_path)
    first = KIND.plant(st, 7, 1, 0.0)
    h, path = first["victims"][0]
    os.rename(path, path + ".corrupted")        # quarantined, not yet healed
    second = KIND.plant(st, 7, 2, 0.0)
    assert second["blocks"] == 23
    assert second["bytes"] == st.store_bytes - st.size_of[h]
    assert second["removed"] == [] and h not in dict(second["victims"])


def test_only_sidecars_the_last_pass_refreshed_are_removed(tmp_path):
    st = store(tmp_path)
    planted = KIND.plant(st, 7, 1, since=2**40)     # none is that new
    assert planted["removed"] == []


def test_planting_lists_nothing(tmp_path, monkeypatch):
    """Inside the window the generator reads set-up's listings."""
    st = store(tmp_path)

    def refuse(*a, **kw):
        raise AssertionError("a listing inside the window")

    monkeypatch.setattr(KIND.cl, "block_files", refuse)
    monkeypatch.setattr(KIND, "sidecar_files", refuse)
    monkeypatch.setattr(os, "listdir", refuse)
    assert len(KIND.plant(st, 9, 1, 0.0)["victims"]) == 2


def test_a_sidecars_members_come_from_its_head_or_from_all_of_it(tmp_path):
    import msgpack

    hashes = [bytes([i]) * 32 for i in range(8)]
    want = [h.hex() for h in hashes]
    parity = [b"\0" * 65536] * 4
    for name, manifest in [
            ("head.par", {"k": 8, "hashes": hashes, "parity": parity}),
            ("tail.par", {"k": 8, "parity": parity, "hashes": hashes})]:
        path = tmp_path / name
        path.write_bytes(msgpack.packb(manifest, use_bin_type=True))
        assert KIND.sidecar_members(str(path)) == want
