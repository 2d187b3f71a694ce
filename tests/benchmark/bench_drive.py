"""Helpers for the tests that drive `benchmarks/run.py` end to end at
toy size on the CPU, each run a process of its own."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# runs `benchmarks.run.main` with the timed path broken underneath: the
# fault is planted in the program (or the reference), never in run.py
DRIVER = """
import sys
sys.path.insert(0, {root!r})
{patch}
import benchmarks.run as run
sys.exit(run.main({argv!r}))
"""

FAULTS = {
    # an answer altered where it is produced: the view's closed rows
    "answer_altered": """
from hstream_tpu.server import views
_add = views.Materialization.add_closed
def add_closed(self, rows):
    rows = [dict(r) for r in rows]
    for r in rows[:1]:
        for k, v in r.items():
            if isinstance(v, (int, float)) and k not in ("winStart", "winEnd"):
                r[k] = v + 1
                break
    return _add(self, rows)
views.Materialization.add_closed = add_closed
""",
    # half of each batch left out before the step
    "half_batch": """
from hstream_tpu.engine import pipeline
_submit = pipeline.IngestPipeline.submit
def submit(self, key_ids, ts_ms, cols, nulls=None):
    n = max(len(key_ids) // 2, 1)
    return _submit(self, key_ids[:n], ts_ms[:n],
                   {k: v[:n] for k, v in cols.items()},
                   None if nulls is None else
                   {k: v[:n] for k, v in nulls.items()})
pipeline.IngestPipeline.submit = submit
""",
    # the reference corrupted instead: the comparison must notice too
    "reference_corrupted": """
import benchmarks.references.{reference} as ref
_answers = ref.answers
def answers(*a, **kw):
    out = _answers(*a, **kw)
    for acc in out.values():
        acc["cnt"] = acc["cnt"] + 1
        for k in ("total", "avg"):
            if k in acc:
                acc[k] = acc[k] * 1.01
    return out
ref.answers = answers
""",
}


def drive(workload: str, seed: int, *, fault: str | None = None,
          reference: str = "", seconds: float = 1.5, trace: int = 0,
          manifest: str | None = None, devices: int = 1):
    """(exit code, the last stdout line parsed or None, stderr).
    `manifest`: a fixture manifest in BENCHMARK.json's place; `devices`:
    virtual CPU devices for the run."""
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--dry", "1"]
    if manifest:
        argv += ["--manifest", manifest]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    if devices > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    if fault is None:
        cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
               *argv]
    else:
        patch = FAULTS[fault].replace("{reference}", reference)
        code = (DRIVER.replace("{root!r}", repr(ROOT))
                .replace("{patch}", patch).replace("{argv!r}", repr(argv)))
        cmd = [sys.executable, "-c", code]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=240)
    lines = p.stdout.strip().splitlines()
    line = None
    if lines:
        try:
            line = json.loads(lines[-1])
        except ValueError:
            line = None
    return p.returncode, line, p.stderr
