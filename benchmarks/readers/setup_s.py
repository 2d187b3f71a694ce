"""Process start to the window's start."""


def read(run: dict, spec: dict):
    return run["setup_s"]
