"""Executor peeks the read plane ran per pull of the window."""


def read(run: dict, spec: dict):
    pulls = len(run["pulls"])
    if not pulls:
        return None
    return (run["end"]["read_extracts"]
            - run["start"]["read_extracts"]) / pulls
