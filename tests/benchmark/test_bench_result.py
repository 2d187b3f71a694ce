"""The last line is held to the driver's contract before it is printed:
for every cell of the manifest, in both `--trace` modes."""

import copy
import io
import json

import pytest

from benchmarks.harness import manifest, result

MAN = manifest.manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
MODES = [(c, t) for c in CELLS for t in (0, 1)]


def good_line(cell: str, traced: int) -> tuple[dict, list]:
    due = manifest.metrics_of(cell, MAN,
                              "per_layer" if traced else "end_to_end")
    metrics = {m["name"]: {"value": 1.5, "unit": m["unit"]} for m in due}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1 << 30}
    breakdown = None
    if traced:
        device.update(busy_s=0.9, window_s=4.0)
        breakdown = {"device_ops": [["fusion.2", 0.5]], "idle_gaps": []}
    line = result.build(correct=True, attempted=10, failed=0,
                        metrics=metrics, device=device,
                        compared={"rows_missing": {"value": 0, "limit": 0}},
                        breakdown=breakdown)
    return line, due


@pytest.mark.parametrize("cell,traced", MODES)
def test_a_good_line_passes_and_is_printed_last(cell, traced):
    line, due = good_line(cell, traced)
    assert result.validate(line, due, traced=bool(traced), chips=1) == []
    assert list(line)[-1] == "compared"
    out, err = io.StringIO(), io.StringIO()
    assert result.emit(line, due, traced=bool(traced), chips=1,
                       out=out, err=err) == 0
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == line
    assert "compared rows_missing: 0 (limit 0)" in err.getvalue()
    assert err.getvalue().strip().endswith("correct: true")


def broken(line: dict, how: str) -> dict:
    line = copy.deepcopy(line)
    first = next(iter(line["metrics"]))
    if how == "metric_missing":
        del line["metrics"][first]
    elif how == "metric_extra":
        line["metrics"]["not_in_manifest"] = {"value": 1, "unit": "ms"}
    elif how == "unit_too_long":
        line["metrics"][first]["unit"] = "tokens per second"
    elif how == "value_nan":
        line["metrics"][first]["value"] = float("nan")
    elif how == "value_string":
        line["metrics"][first]["value"] = "1.5"
    elif how == "no_device_kind":
        del line["device"]["kind"]
    elif how == "wrong_platform":
        line["device"]["platform"] = "cpu"
    elif how == "wrong_count":
        line["device"]["count"] = 4
    elif how == "no_peak":
        line["device"]["memory_peak_bytes"] = 0
    elif how == "no_correct":
        del line["correct"]
    elif how == "failed_over_attempted":
        line["failed"] = line["attempted"] + 1
    return line


@pytest.mark.parametrize("how", [
    "metric_missing", "metric_extra", "unit_too_long", "value_nan",
    "value_string", "no_device_kind", "wrong_platform", "wrong_count",
    "no_peak", "no_correct", "failed_over_attempted"])
@pytest.mark.parametrize("cell,traced", MODES)
def test_a_broken_line_is_refused_and_not_printed(cell, traced, how):
    line, due = good_line(cell, traced)
    bad = broken(line, how)
    assert result.validate(bad, due, traced=bool(traced), chips=1)
    out, err = io.StringIO(), io.StringIO()
    assert result.emit(bad, due, traced=bool(traced), chips=1,
                       out=out, err=err) != 0
    assert out.getvalue() == ""
    assert "refused" in err.getvalue()


@pytest.mark.parametrize("busy,window", [(0.0, 4.0), (4.1, 4.0),
                                         (None, 4.0), (1.0, None),
                                         (-1.0, 4.0)])
@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_line_needs_busy_above_zero_within_the_window(
        cell, busy, window):
    line, due = good_line(cell, 1)
    for key, v in (("busy_s", busy), ("window_s", window)):
        if v is None:
            del line["device"][key]
        else:
            line["device"][key] = v
    assert result.validate(line, due, traced=True, chips=1)


@pytest.mark.parametrize("cell", CELLS)
def test_a_roofline_share_over_100_is_refused(cell):
    line, due = good_line(cell, 1)
    for share, ok in ((0.0146, True), (100.0, True), (101.0, False),
                      (0.0, False)):
        line["metrics"]["step_roofline"]["value"] = share
        assert (result.validate(line, due, traced=True, chips=1) == []) is ok


@pytest.mark.parametrize("cell", CELLS)
def test_an_end_to_end_metric_is_never_zero(cell):
    line, due = good_line(cell, 0)
    line["metrics"]["events_per_s"]["value"] = 0
    assert result.validate(line, due, traced=False, chips=1)


def test_a_dry_line_says_cpu():
    line, due = good_line(CELLS[0], 0)
    line["device"].update(platform="cpu", kind="cpu", count=8,
                          memory_peak_bytes=0)
    assert result.validate(line, due, traced=False, chips=8,
                           platform="cpu") == []
    assert result.validate(line, due, traced=False, chips=1)
