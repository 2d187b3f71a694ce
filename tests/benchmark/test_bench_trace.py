"""The trace reduction, on a slice recorded on the chip: 0.3 s of
`sensor_hll_100k.replay_pull` (TPU v5 lite, PR 24), device events of
`/device:TPU:0` with the `bench_slice` annotation laid over them."""

import json
import os

import pytest

from benchmarks.harness import rooflines, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_slice_hll_100k.json")


@pytest.fixture(scope="module")
def events():
    with open(FIXTURE) as f:
        return trace.unpack(json.load(f))


def sweep_busy_ns(events) -> int:
    """Busy time by an independent method: a +1/-1 sweep over the
    clipped operation intervals."""
    sl = next(e for e in events if e[2] == trace.SLICE_NAME)
    lo, hi = sl[3], sl[3] + sl[4]
    pts = []
    for e in events:
        if e[1] != trace.OPS_LINE:
            continue
        a, b = max(e[3], lo), min(e[3] + e[4], hi)
        if b > a:
            pts += [(a, 1), (b, -1)]
    depth = busy = 0
    last = None
    for t, d in sorted(pts):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_idle_share_of_the_recorded_slice(events):
    red = trace.reduce(events)
    assert red["planes"] == 1 and red["clipped_to_slice"]
    assert red["window_s"] == pytest.approx(0.3)
    # hand-computed with the sweep above when the slice was cut
    assert red["busy_s"] == pytest.approx(0.076342446, abs=1e-9)
    assert red["busy_s"] * 1e9 == pytest.approx(sweep_busy_ns(events))
    idle = 100.0 * (1 - red["busy_s"] / red["window_s"])
    assert idle == pytest.approx(74.552518, abs=1e-5)
    assert 0 < red["busy_s"] <= red["window_s"]


def test_kernel_time_by_program_name(events):
    red = trace.reduce(events)
    assert set(red["programs"]) == {"jit_step", "jit_extract"}
    assert red["programs"]["jit_step"] == pytest.approx(0.075973331,
                                                        abs=1e-9)
    assert red["programs"]["jit_extract"] == pytest.approx(0.000381461,
                                                           abs=1e-9)
    # seven whole steps and the share of one the slice's edge cut
    assert red["program_runs"]["jit_step"] == pytest.approx(7.69238434,
                                                            abs=1e-6)
    assert trace.matching_seconds(red["programs"], ["jit_step"]) \
        == red["programs"]["jit_step"]
    assert trace.matching_seconds(red["programs"], ["jit_nothing"]) == 0


def test_roofline_arithmetic_against_a_hand_computed_value(events):
    red = trace.reduce(events)
    config = {"aggregates": ["COUNT", "SUM", "APPROX_COUNT_DISTINCT"]}
    assert rooflines.step_bytes_per_event(config) == 18
    hop = {"aggregates": ["AVG", "MIN", "MAX"]}
    assert rooflines.step_bytes_per_event(hop) == 32
    peak = rooflines.peaks("TPU v5 lite")
    events_stepped = red["program_runs"]["jit_step"] * 65536
    least = rooflines.least_step_seconds(config, events_stepped, peak)
    # 7.69238434 runs x 65 536 rows x 18 B / 819e9 B/s = 11.0797 us
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(1.10797e-5, rel=1e-5)
    share = 100.0 * least["seconds"] / red["programs"]["jit_step"]
    assert share == pytest.approx(0.0145837, rel=1e-4)
    assert 0 < share <= 100


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        rooflines.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        rooflines.peaks("source")


def test_a_slice_without_device_operations_raises(events):
    sl = [e for e in events if e[2] == trace.SLICE_NAME]
    far = [[e[0], e[1], e[2], e[3] + 10**12, e[4]] for e in sl]
    ops = [e for e in events if e[2] != trace.SLICE_NAME]
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce(ops + far)
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        trace.reduce(sl)


def test_breakdown_is_short_and_named(events):
    br = trace.breakdown(trace.reduce(events))
    assert 1 <= len(br["device_ops"]) <= 10
    assert len(br["idle_gaps"]) <= 10
    assert br["device_ops"][0][0] == "fusion.2 s8[402653184] fusion"
    assert all(len(name) <= 80 for name, _s in br["device_ops"])
    secs = [s for _n, s in br["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_pack_round_trips(events):
    assert trace.unpack(trace.pack(events)) == events
