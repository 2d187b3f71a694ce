"""Share of the window one thread spent inside one stage: the sum of
`stage_latency_ms{<label>}` over the window, over the window's length.
A stage that several threads run at once (`encode`) can pass 100; the
task thread's and a pull's stages cannot. None where the label never
observed anything (a renamed stage must not read 0); 0.0 where it
observed waits of no length."""

from benchmarks.readers import _stages


def read(run: dict, spec: dict):
    d = _stages.delta(run, spec["histogram"], spec["label"])
    if d is None:
        return None
    return 100.0 * (d[2] / 1e3) / run["window_s"]
