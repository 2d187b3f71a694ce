"""Share of the traced slice in which no operation ran on the device."""


def read(run: dict, spec: dict):
    red = run["trace"]
    if red is None:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
