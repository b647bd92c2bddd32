"""The trace reduction on a hand-made trace (ns)."""

import pytest

from benchmarks import trace_reduce as tr

S = 1e9
PLANES = [
    ("/host:CPU", [("python3", [
        ("bench:window", 0.0, 10 * S),
        ("bench:plant", 0.0, 1 * S),
        ("bench:scrub_pass", 1 * S, 9 * S),
        ("other", 2 * S, 1 * S)])]),
    ("/device:TPU:0", [
        ("XLA Modules", [("jit_fused", 2 * S, 4 * S)]),
        ("XLA Ops", [
            ("fused_scrub.1", 2 * S, 1 * S),
            ("fused_scrub.1", 2.5 * S, 1 * S),      # overlaps the first
            ("gf_apply", 5 * S, 1 * S),
            ("copy", 9.5 * S, 2 * S)])]),           # runs past the window
]


def test_busy_is_the_union_inside_the_window():
    red = tr.reduce_trace(PLANES, chips=1)
    # [2, 3.5] + [5, 6] + [9.5, 10]
    assert red["busy_s"] == pytest.approx(3.0)
    assert red["window_s"] == pytest.approx(10.0)
    assert red["device_planes"] == 1


def test_modules_are_not_counted_where_ops_are_given():
    red = tr.reduce_trace(PLANES, chips=1)
    assert "jit_fused" not in red["op_seconds"]


def test_an_ops_time_by_name():
    red = tr.reduce_trace(PLANES, chips=1)
    assert red["op_seconds"]["gf_apply"] == pytest.approx(1.0)
    assert red["device_ops"][0] == ["fused_scrub.1", pytest.approx(2.0)]


def test_idle_gaps_go_to_what_the_host_was_doing():
    red = tr.reduce_trace(PLANES, chips=1)
    gaps = dict(red["idle_gaps"])
    # [0,2]: mid 1.0 is in scrub_pass (latest started of plant/scrub_pass)
    assert gaps["bench:scrub_pass"] == pytest.approx(2 + 1.5 + 3.5)
    idle = 100 * (1 - red["busy_s"] / red["window_s"])
    assert idle == pytest.approx(70.0)


def test_busy_is_the_mean_over_the_chips_used():
    two = PLANES + [("/device:TPU:1", [("XLA Ops", [("x", 0.0, 1 * S)])])]
    assert tr.reduce_trace(two, chips=2)["busy_s"] == pytest.approx(2.0)


def test_no_window_mark_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_trace(PLANES[1:], chips=1)


def test_the_device_readers_return_nothing_without_a_device_plane():
    from benchmarks import harness

    red = tr.reduce_trace(PLANES[:1], chips=1)
    reader = harness.load_module(
        harness.HERE / "readers" / "device_idle_share.py", "r")
    assert reader.read({"trace": red}) is None
    assert reader.read({"trace": tr.reduce_trace(PLANES, 1)})["value"] == \
        pytest.approx(70.0)


def test_a_full_buffer_ends_the_window_at_its_last_event(monkeypatch):
    monkeypatch.setattr(tr, "FULL_BUFFER_EVENTS", 4)
    red = tr.reduce_trace([PLANES[0], ("/device:TPU:0", [("XLA Ops", [
        ("a", 1 * S, 1 * S), ("b", 2 * S, 1 * S), ("c", 3 * S, 1 * S),
        ("d", 4 * S, 1 * S)])])], chips=1)
    assert red["window_s"] == pytest.approx(5.0)
    assert red["busy_s"] == pytest.approx(4.0)
