"""The lattice step's share of its roofline in the traced slice: the
least time the chip could take for the state bytes of the events
stepped there, over the device time of the step programs."""

from benchmarks.harness import rooflines, trace


def read(run: dict, spec: dict):
    red = run["trace"]
    if red is None:
        return None
    seconds = trace.matching_seconds(red["programs"], spec["programs"])
    runs = trace.matching_seconds(red["program_runs"], spec["programs"])
    if seconds <= 0 or runs <= 0:
        return None
    events = runs * run["size"]["frame_rows"]
    least = rooflines.least_step_seconds(
        run["config"], events, rooflines.peaks(run["device"]["kind"]))
    return 100.0 * least["seconds"] / seconds
