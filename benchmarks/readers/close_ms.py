"""Host time the close kernels' dispatches took per close cycle of the
window (`kernel_dispatch_ms{close}`; the fetch and decode that follow
lie inside the task's step span and are not split out by the program)."""

from benchmarks.readers import _stages


def read(run: dict, spec: dict):
    d = _stages.delta(run, spec["histogram"], spec["label"])
    cycles = (run["end"]["close_stats"].get("close_cycles", 0)
              - run["start"]["close_stats"].get("close_cycles", 0))
    if d is None or cycles <= 0:
        return None
    return d[2] / cycles
