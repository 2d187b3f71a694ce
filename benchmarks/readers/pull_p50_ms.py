"""Median client-side time of every pull started in the window."""

import statistics


def read(run: dict, spec: dict):
    ms = [(p["t1"] - p["t0"]) * 1e3 for p in run["pulls"]
          if p["rows"] is not None]
    return float(statistics.median(ms)) if ms else None
