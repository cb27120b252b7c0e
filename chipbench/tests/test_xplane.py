"""xplane.py on a small trace recorded on a v5e chip (tiny.xplane.pb,
17 KB: three calls of ``jit(lambda x: tanh(x @ x) @ x)`` on 1024 x 1024
bfloat16 inside the harness's spans), against numbers worked out by
hand from its events, and on made-up events."""

import os

import pytest

from chipbench import harness, xplane

TRACE = os.path.join(harness.HERE, "tests", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.load(TRACE)


def test_planes_lines_and_spans(trace):
    assert list(trace["devices"]) == ["/device:TPU:0"]
    ops = trace["devices"]["/device:TPU:0"]
    # each call: copy-start, copy-done, the matmul+tanh fusion, the matmul
    assert len(ops) == 12
    assert [n.split(" ")[0] for n, _, _ in ops[:4]] == [
        "%copy-start", "%copy-done", "%convolution_tanh_fusion", "%fusion"]
    names = [n for n, _, _ in trace["spans"]]
    assert names == ["chipbench.window"] + [
        "chipbench.trainer.step", "chipbench.wait"] * 3
    assert xplane.window_of(trace) == (44605030.0, 44605030.0 + 9879230.0)


def test_busy_share_and_op_times_by_hand(trace):
    # the device's clock runs about a millisecond ahead of the host's in
    # this trace, so the first call's operations (43.59 ms) fall before
    # the window span (44.61 ms) and are clipped away; the second and
    # third calls lie inside: 13 + 2 + 11573 + 12617 and
    # 13 + 3 + 11574 + 12613 nanoseconds
    got = xplane.summary(trace)
    assert got["window_s"] == pytest.approx(9879230e-9)
    assert got["busy_s"] == pytest.approx((24205 + 24203) * 1e-9)
    ops = dict((n.split(" ")[0], s) for n, s in got["device_ops"])
    assert ops["%fusion"] == pytest.approx((12617 + 12613) * 1e-9)
    assert ops["%convolution_tanh_fusion"] == pytest.approx(
        (11573 + 11574) * 1e-9)
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle == pytest.approx((9879230 - 48408) * 1e-9)
    assert all(len(n) <= 120 for n, _ in got["device_ops"])


def test_union_clip_and_labelled_gaps_on_made_up_events():
    events = [("a", 10.0, 10.0), ("b", 15.0, 10.0), ("c", 40.0, 5.0),
              ("d", 90.0, 20.0)]
    assert xplane.union(events) == [[10.0, 25.0], [40.0, 45.0],
                                    [90.0, 110.0]]
    assert xplane.busy_ns(events) == 40.0
    assert xplane.clip(events, 20.0, 100.0) == [
        ("b", 20.0, 5.0), ("c", 40.0, 5.0), ("d", 90.0, 10.0)]
    assert xplane.op_totals(events + [("a", 200.0, 1.0)])["a"] == 11.0
    spans = [("chipbench.window", 0.0, 100.0),
             ("chipbench.engine.step", 20.0, 22.0),
             ("chipbench.gateway.pump", 15.0, 60.0)]
    gaps = xplane.idle_gaps(xplane.clip(events, 0.0, 100.0), spans,
                            0.0, 100.0)
    assert gaps == [("none", 10e-9), ("engine.step", 15e-9),
                    ("gateway.pump", 45e-9)]


def test_a_trace_without_a_window_or_a_device_says_so(trace):
    with pytest.raises(ValueError):
        xplane.window_of({"devices": {}, "spans": []})
    with pytest.raises(ValueError):
        xplane.summary({"devices": {}, "spans": trace["spans"]})
