"""95th percentile of send -> ack over every call sent in the window."""

import numpy as np


def read(run: dict, spec: dict):
    ms = [(c[3] - c[2]) * 1e3 for c in run["calls"]]
    return float(np.percentile(ms, 95)) if ms else None
