"""The trace reduction, on a small trace recorded on one TPU v5e
(``python3 -m chipbench.record_fixture``: three calls of a tiny Aligner
batch and a Pallas normalization, each call followed by a 5 ms host
pause) and on hand-made events."""
import pathlib

import pytest

from chipbench import trace as tr

FIXTURE = pathlib.Path(__file__).parent / "data" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def events():
    return tr.load(FIXTURE)


def test_load_keeps_device_ops_and_host_spans(events):
    assert list(events["devices"]) == ["/device:TPU:0"]
    names = [h[0] for h in events["host"]]
    assert names.count("chipbench.window") == 1
    assert names.count("chipbench.call") == 3
    assert names.count("aligner.dispatch") == 3
    assert not any(n.startswith("$") for n in names)


def test_kernels_are_told_apart(events):
    ops = events["devices"]["/device:TPU:0"]
    kinds = {tr.kernel_of(op, k) for op, k, _, _ in ops}
    assert kinds == {None, "wavefront", "normalizer"}
    s = tr.summarize(events, tr.window_of(events, "chipbench.window"))
    wave = sum(d for op, k, _, d in ops
               if tr.kernel_of(op, k) == "wavefront")
    norm = sum(d for op, k, _, d in ops
               if tr.kernel_of(op, k) == "normalizer")
    assert s.kernel_ns == {"wavefront": wave, "normalizer": norm}
    assert wave > 100 * norm > 0


def test_busy_is_the_union_inside_the_window(events):
    w = tr.window_of(events, "chipbench.window")
    s = tr.summarize(events, w)
    assert s.window_ns == w[1] - w[0]
    assert 0 < s.busy_ns < s.window_ns
    assert s.busy_ns >= s.kernel_ns["wavefront"]
    assert 0.8 < s.idle_share < 1.0
    assert s.top_ops[0][0] == "_dispatch"


def test_idle_gaps_go_to_the_host_span_that_covers_them(events):
    s = tr.summarize(events, tr.window_of(events, "chipbench.window"))
    idle = dict(s.idle_by_host)
    # three 5 ms pauses with the device idle
    assert idle["fixture.pause"] > 0.015
    assert idle["fixture.pause"] == s.idle_by_host[0][1]
    total = sum(v for _, v in s.idle_by_host)
    assert total == pytest.approx((s.window_ns - s.busy_ns) / 1e9)


def test_union_gaps_and_attribution_by_hand():
    busy = tr.union([(5, 10), (0, 3), (8, 12), (20, 30)], 1, 25)
    assert busy == [(1, 3), (5, 12), (20, 25)]
    assert tr.gaps(busy, 1, 25) == [(3, 5), (12, 20)]
    host = [["outer.span", 0, 100], ["inner.span", 10, 5]]
    assert tr.host_activity(host, 12) == "inner.span"
    assert tr.host_activity(host, 50) == "outer.span"
    assert tr.host_activity(host, 500) == "no host span"


def test_summary_over_two_devices_averages():
    ev = {"devices": {
        "/device:TPU:0": [["_dispatch.1", True, 0, 50]],
        "/device:TPU:1": [["_dispatch.1", True, 0, 100]]},
        "host": [["chipbench.window", 0, 100]]}
    s = tr.summarize(ev, (0, 100))
    assert s.busy_ns == 75 and s.kernel_ns == {"wavefront": 75}
    assert s.devices == 2


def test_a_trace_without_a_device_is_refused():
    with pytest.raises(ValueError, match="no TPU device"):
        tr.summarize({"devices": {}, "host": []}, (0, 10))

