"""The session step's share of its roofline in the traced slice: the
least time the chip could take for the bytes of the events stepped
there (one frame a run of the step program), over the device time of
the step programs. None where no such program ran in the slice: a
program without the pinned name reports nothing, never 0."""

from benchmarks.harness import rooflines, session_rooflines, trace


def read(run: dict, spec: dict):
    red = run["trace"]
    if red is None:
        return None
    seconds = trace.matching_seconds(red["programs"], spec["programs"])
    runs = trace.matching_seconds(red["program_runs"], spec["programs"])
    if seconds <= 0 or runs <= 0:
        return None
    least = session_rooflines.least_step_seconds(
        run["config"], runs * run["size"]["frame_rows"],
        rooflines.peaks(run["device"]["kind"]))
    return 100.0 * least / seconds
