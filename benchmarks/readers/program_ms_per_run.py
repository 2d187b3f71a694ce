"""Device milliseconds one run of a program takes, in the traced slice:
the device seconds of the programs named in the metric file over their
runs there (a run cut by the slice's edge counts for its share of
both). None where no such program ran in the slice."""

from benchmarks.harness import trace


def read(run: dict, spec: dict):
    red = run["trace"]
    if red is None:
        return None
    seconds = trace.matching_seconds(red["programs"], spec["programs"])
    runs = trace.matching_seconds(red["program_runs"], spec["programs"])
    if seconds <= 0 or runs <= 0:
        return None
    return 1e3 * seconds / runs
