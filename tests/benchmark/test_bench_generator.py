"""The seeded generator, the plain references and the control."""

import numpy as np
import pytest

from benchmarks import control
from benchmarks.generators import sensor as gen
from benchmarks.harness import manifest
from benchmarks.readers import _stages

MAN = manifest.manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
BIG_SEED = 2**31 + 11  # more than 32 signed bits hold


def dry_size(cell: str) -> tuple[dict, dict]:
    config = manifest.cell(cell)["config"]
    return config, manifest.size_of(config, True)


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_frames_other_seed_other_frames(cell):
    _config, size = dry_size(cell)
    n_warm = gen.warm_frames(size)
    for i in (0, n_warm - 1, n_warm, n_warm + 17):
        a = gen.draw(size, BIG_SEED, i)
        b = gen.draw(size, BIG_SEED, i)
        c = gen.draw(size, BIG_SEED + 1, i)
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
        assert not all(np.array_equal(x, y) for x, y in zip(a, c))
        # every seed: the same sizes, in the same pane
        assert [len(x) for x in a] == [len(x) for x in c]
        pane = gen.pane_of(size, i)
        lo = gen.BASE + pane * size["advance_ms"]
        assert ((a[3] >= lo) & (a[3] < lo + size["advance_ms"])).all()
        assert ((c[3] >= lo) & (c[3] < lo + size["advance_ms"])).all()


@pytest.mark.parametrize("cell", CELLS)
def test_warm_frames_name_every_key_and_the_window_starts_on_a_pane(cell):
    _config, size = dry_size(cell)
    n_warm = gen.warm_frames(size)
    assert n_warm % size["frames_per_call"] == 0
    seen = set()
    for i in range(n_warm):
        kids, tenths, temps, _ts = gen.draw(size, 5, i)
        if gen.pane_of(size, i) == 0:
            seen |= set(kids.tolist())
        assert temps.dtype == np.float32 and len(kids) == size["frame_rows"]
        # one-decimal values: the codec-canonical form
        assert np.array_equal(temps, tenths.astype(np.float32)
                              * np.float32(0.1))
    assert seen == set(range(size["keys"]))
    per_pane = gen.frames_per_pane(size)
    assert gen.pane_of(size, n_warm) == gen.WARM_PANES
    assert gen.pane_of(size, n_warm + per_pane - 1) == gen.WARM_PANES
    assert gen.pane_of(size, n_warm + per_pane) == gen.WARM_PANES + 1


@pytest.mark.parametrize("cell", CELLS)
def test_full_sizes_fill_whole_panes(cell):
    config = manifest.cell(cell)["config"]
    size = manifest.size_of(config, False)
    assert gen.frames_per_pane(size) * size["frame_rows"] \
        == size["events_per_advance"]
    names = gen.key_names(size)
    assert len(names) == size["keys"] and names[0] == "dev000000"
    n_frames = gen.warm_frames(size) + 5 * gen.frames_per_pane(size) + 1
    assert gen.pane_of(size, n_frames - 1) == 7
    [(stream, ts, cols, events)] = gen.closers(size, n_frames)
    assert stream == size["stream"] and events == 1 == len(ts)
    assert ts[0] == gen.BASE + 8 * size["advance_ms"] + size["size_ms"]
    assert list(cols) == size["columns"]
    assert gen.pulls(size, n_frames)["horizon"] == ts[0]


@pytest.mark.parametrize("cell", CELLS)
def test_the_reference_agrees_with_itself_and_the_control_fails(cell):
    config, size = dry_size(cell)
    n_frames = gen.warm_frames(size) + 3 * gen.frames_per_pane(size) + 2
    same = control.control_numbers(config, size, 9, n_frames, "f32")
    assert all(v <= size["limits"][k] for k, v in same.items()), same
    low = control.control_numbers(config, size, 9, n_frames, "bf16")
    over = [k for k, v in low.items() if v > size["limits"][k]]
    assert over, low


def test_hll_reference_is_close_to_the_exact_distinct_count():
    from benchmarks.references import tumbling_hll as ref

    rng = np.random.default_rng(3)
    vals = (rng.integers(0, 5000, 20000).astype(np.float32)
            * np.float32(0.1))
    est = ref.hll_estimate(ref.hll_registers(
        np.zeros(len(vals), np.int64), vals, 1), 1)[0]
    exact = len(np.unique(vals))
    assert abs(est - exact) / exact < 4 * 0.0325


def test_hop_reference_against_a_loop():
    from benchmarks.references import hop_minmax as ref

    _config, size = dry_size("sensor_hop_1k.replay")
    n_frames = gen.warm_frames(size) + 2 * gen.frames_per_pane(size)
    got = ref.answers(size, 4, n_frames)
    width = size["size_ms"] // size["advance_ms"]
    by_pane: dict[int, list] = {}
    for i in range(n_frames):
        kids, _tenths, temps, _ts = gen.draw(size, 4, i)
        by_pane.setdefault(gen.pane_of(size, i), []).append((kids, temps))
    m = max(by_pane) - 1  # a window over several panes
    vals = [[] for _ in range(size["keys"])]
    for p in range(m, m + width):
        for kids, temps in by_pane.get(p, []):
            for k, v in zip(kids.tolist(), temps.tolist()):
                vals[k].append(v)
    for k in (0, 7, size["keys"] - 1):
        v = np.array(vals[k], np.float32)
        assert got[m]["cnt"][k] == len(v)
        assert got[m]["lo"][k] == v.min() and got[m]["hi"][k] == v.max()
        assert got[m]["avg"][k] == pytest.approx(
            v.astype(np.float64).mean(), rel=1e-12)


def test_bucket_percentile_of_a_window_delta():
    bounds = [1.0, 5.0, 10.0]
    run = {"start": {"histograms": {"stage_latency_ms": {"x": {
               "bounds": bounds, "cum": [1, 1, 1, 1], "sum_ms": 0.5,
               "count": 1}}}},
           "end": {"histograms": {"stage_latency_ms": {"x": {
               "bounds": bounds, "cum": [1, 5, 9, 11], "sum_ms": 80.5,
               "count": 11}}}}}
    counts, got_bounds, total, n = _stages.delta(run, "stage_latency_ms",
                                                 "x")
    assert counts == [0, 4, 4, 2] and n == 10 and total == 80.0
    assert got_bounds == bounds
    # rank 5 of 10: one quarter into the (5, 10] bucket
    assert _stages.percentile(counts, bounds, 50) == pytest.approx(6.25)
    assert _stages.percentile(counts, bounds, 99) == 10.0  # +Inf bucket
    assert _stages.percentile([0, 0, 0, 0], bounds, 50) is None
    assert _stages.delta(run, "stage_latency_ms", "absent") is None


def test_encoders_make_the_client_librarys_bytes():
    from benchmarks.harness import producer
    from hstream_tpu.client.producer import encode_batch

    _config, size = dry_size(CELLS[0])
    _stream, ts, cols, _events = gen.frame(size, 3, 5)
    assert producer.encode_frame(ts, cols) == encode_batch(ts, cols)
