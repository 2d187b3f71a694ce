"""Events consumed by the query over the window, per second of it."""


def read(run: dict, spec: dict):
    frames = (run["end"]["consumed_frames"]
              - run["start"]["consumed_frames"])
    return frames * run["size"]["frame_rows"] / run["window_s"]
