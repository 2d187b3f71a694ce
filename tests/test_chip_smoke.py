"""chip_smoke.py and the compile-cache helper, as far as a CPU can show
(ISSUE 21): the smoke refuses to run off a TPU, its --dry-run drives all
three phases end to end, the cache directory is where it is documented
to be, and RetraceGuard still counts a program served from that cache.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def _run(args, env, timeout=300):
    return subprocess.run([sys.executable, *args], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


def test_smoke_refuses_a_cpu_and_names_it(tmp_path):
    proc = _run([SMOKE], _env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    # no result line: nothing on stdout parses as the smoke's JSON
    assert not any(line.startswith("{") for line in
                   proc.stdout.splitlines())


def test_dry_run_passes_three_phases(tmp_path):
    cache = tmp_path / "cache"
    # JAX keeps only programs that took 1 s to build: on an idle host no
    # toy program does, so the threshold is 0 here and the cache
    # assertion below holds on any host
    proc = _run([SMOKE, "--dry-run"],
                _env(JAX_COMPILATION_CACHE_DIR=str(cache),
                     JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    # the last line is the verdict the chip check reads: these two keys
    # and the device's three, nothing else; the report is the line before
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    out = json.loads(lines[-2])
    assert out["ok"] is True and out["dry_run"] is True
    assert out["platform"] == "cpu" == verdict["device"]["platform"]
    assert verdict["device"]["count"] == out["n_devices"] >= 1
    # the variable was set: the program used it and set no other
    assert out["cache_dir"] == str(cache) and os.listdir(cache)
    phases = out["phases"]
    assert {"served_tumbling", "session_device", "join_device",
            "shutdown"} <= set(phases)
    for name in ("served_tumbling", "session_device", "join_device"):
        assert phases[name]["device_fallbacks"] == 0, name
        # a toy run on a CPU shared with the other test workers may
        # read DEGRADED (overload); the run on a chip holds OK
        assert phases[name]["health"] in ("OK", "DEGRADED"), name
    st = phases["served_tumbling"]
    assert st["last_window_compiles"] == 0
    assert st["frames"] == st["windows"][-1]["window"] + 2  # + closer
    assert [w["window"] for w in st["windows"]] == [0, 1, 2, 3]
    assert phases["shutdown"]["paired_lsn"] == st["store_batches"]


_PLACE = """
import json, sys
import jax
from hstream_tpu.common.jaxenv import place_compile_cache
before = jax.config.jax_compilation_cache_dir
got = place_compile_cache()
print(json.dumps({"got": got, "before": before,
                  "after": jax.config.jax_compilation_cache_dir}))
"""


def test_cache_dir_env_wins_and_default_is_in_the_checkout(tmp_path):
    # set: the helper changes nothing (JAX read the variable itself)
    proc = _run(["-c", _PLACE],
                _env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["got"] == out["before"] == out["after"] == str(tmp_path)
    # unset: <checkout>/.jax_cache, the same in two different processes
    # started from two different directories
    want = os.path.join(ROOT, ".jax_cache")
    for cwd in (ROOT, str(tmp_path)):
        proc = subprocess.run([sys.executable, "-c", _PLACE], env=_env(),
                              cwd=cwd, capture_output=True, text=True,
                              timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["got"] == out["after"] == want
        assert out["before"] is None


_CACHED_JIT = """
import json, sys
import jax, jax.numpy as jnp
import jax.monitoring
from hstream_tpu.common.jaxenv import place_compile_cache
from hstream_tpu.common.tracing import RetraceGuard

place_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
hits = []
jax.monitoring.register_event_listener(
    lambda event, **kw: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
x = jnp.arange(17, dtype=jnp.float32)
jax.block_until_ready(x)
hits.clear()  # the arange above is a (cached) program of its own
with RetraceGuard() as g:
    jax.jit(lambda v: v * 3.0 + 7.0)(x).block_until_ready()
print(json.dumps({"count": g.count, "hits": len(hits)}))
"""


def test_retrace_guard_counts_a_program_served_from_disk(tmp_path):
    """RetraceGuard's event wraps compile_or_get_cached, so a build
    the persistent cache serves still counts: turning the cache on
    cannot hide a steady-state recompile."""
    env = _env(JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    first = _run(["-c", _CACHED_JIT], env)
    assert first.returncode == 0, first.stderr[-2000:]
    cold = json.loads(first.stdout.strip().splitlines()[-1])
    assert cold == {"count": 1, "hits": 0}
    assert os.listdir(tmp_path), "nothing was written to the cache"
    second = _run(["-c", _CACHED_JIT], env)
    assert second.returncode == 0, second.stderr[-2000:]
    warm = json.loads(second.stdout.strip().splitlines()[-1])
    assert warm == {"count": 1, "hits": 1}
