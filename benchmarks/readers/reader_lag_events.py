"""Backlog growth over the window: events acked but not consumed at its
end, less the same at its start (the window starts drained)."""


def read(run: dict, spec: dict):
    hi = run["end"]["t"]
    acked = sum(c[1] for c in run["calls"] if c[6] and c[3] <= hi)
    consumed = (run["end"]["consumed_frames"]
                - run["start"]["consumed_frames"])
    return float((acked - consumed) * run["size"]["frame_rows"])
