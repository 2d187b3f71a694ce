"""A window join program's share of its roofline in the traced slice:
the least time the chip could take for the bytes of the program's runs
there (`harness/window_join_rooflines.py`, the function the metric file
names under `least`: a frame's rows a run of the fused step, the rows
that survive a run of the eviction), over the device time of the
programs the metric file names. None where no such program ran in the
slice, or the configuration is not a window join's: a program without
the pinned name reports nothing, never 0."""

from benchmarks.harness import rooflines, trace, window_join_rooflines


def read(run: dict, spec: dict):
    red = run["trace"]
    if red is None:
        return None
    seconds = trace.matching_seconds(red["programs"], spec["programs"])
    runs = trace.matching_seconds(red["program_runs"], spec["programs"])
    if seconds <= 0 or runs <= 0 or "span_epochs" not in run["config"]:
        return None
    least = getattr(window_join_rooflines, spec["least"])(
        run["config"], runs, rooflines.peaks(run["device"]["kind"]))
    return 100.0 * least / seconds
