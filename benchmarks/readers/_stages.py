"""Window deltas of the program's latency histograms
(`stage_latency_ms{stage}`, `kernel_dispatch_ms{family}`)."""


def delta(run: dict, histogram: str, label: str):
    """(per-bucket counts, bounds, sum ms, count) of one labelled series
    over the window, or None where it never observed anything."""
    end = run["end"]["histograms"][histogram].get(label)
    if end is None:
        return None
    start = run["start"]["histograms"][histogram].get(label) or {
        "cum": [0] * len(end["cum"]), "sum_ms": 0.0, "count": 0}
    cum = [e - s for e, s in zip(end["cum"], start["cum"])]
    counts = [c - (cum[i - 1] if i else 0) for i, c in enumerate(cum)]
    return (counts, end["bounds"], end["sum_ms"] - start["sum_ms"],
            end["count"] - start["count"])


def percentile(counts: list, bounds: list, q: float):
    """Bucket-interpolated percentile (the arithmetic of the program's
    `Histogram.percentile`, on a delta); the +Inf bucket reads its lower
    edge."""
    total = sum(counts)
    if total == 0:
        return None
    rank = q / 100.0 * total
    seen = 0
    for i, c in enumerate(counts):
        if seen + c >= rank and c:
            if i >= len(bounds):
                return float(bounds[-1])
            lo = bounds[i - 1] if i else 0.0
            return lo + (bounds[i] - lo) * (rank - seen) / c
        seen += c
    return float(bounds[-1])
