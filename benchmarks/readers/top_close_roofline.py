"""The top close's share of its roofline in the traced slice: the least
time the chip could take for the bytes of the windows closed there,
over the device time of the top close programs. A run of the program
closes one cycle's windows; how many groups a cycle's windows held is
the mean over the run's window, from the device's own reduce
(`close_stats`: `close_groups` over `close_cycles`). None where the
program did not run in the slice, or the program states no such count
(one without the pinned name or the counter reports nothing, never 0)."""

from benchmarks.harness import rooflines, top_close_rooflines, trace


def read(run: dict, spec: dict):
    red = run["trace"]
    if red is None:
        return None
    seconds = trace.matching_seconds(red["programs"], spec["programs"])
    runs = trace.matching_seconds(red["program_runs"], spec["programs"])
    end, start = run["end"]["close_stats"], run["start"]["close_stats"]
    if seconds <= 0 or runs <= 0 or "close_groups" not in end:
        return None
    cycles = end["close_cycles"] - start.get("close_cycles", 0)
    groups = end["close_groups"] - start.get("close_groups", 0)
    if cycles <= 0 or groups <= 0:
        return None
    least = top_close_rooflines.least_close_seconds(
        run["config"], runs * groups / cycles,
        rooflines.peaks(run["device"]["kind"]))
    return 100.0 * least / seconds
