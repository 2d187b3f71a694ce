"""Events consumed by the query over the window, per second of it: the
difference of the program's public `consumed_events` count."""


def read(run: dict, spec: dict):
    events = (run["end"]["consumed_events"]
              - run["start"]["consumed_events"])
    return events / run["window_s"]
