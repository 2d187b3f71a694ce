"""Median of send -> ack over every call sent in the window: the body
of the distribution whose tail `append_ack_p95_ms` reads."""

import statistics


def read(run: dict, spec: dict):
    ms = [(c[3] - c[2]) * 1e3 for c in run["calls"]]
    return float(statistics.median(ms)) if ms else None
