"""Share of the window the ingest pipeline's caller spent in the step
(dispatch, window bookkeeping, inline drains)."""


def read(run: dict, spec: dict):
    end, start = run["end"]["pipe"], run["start"]["pipe"]
    if "step_s" not in end:
        return None
    return 100.0 * (end["step_s"] - start.get("step_s", 0.0)) \
        / run["window_s"]
