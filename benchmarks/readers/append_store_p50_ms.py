"""Median of the append door's store span over the window."""

from benchmarks.readers import _stages


def read(run: dict, spec: dict):
    d = _stages.delta(run, spec["histogram"], spec["label"])
    if d is None:
        return None
    return _stages.percentile(d[0], d[1], 50)
