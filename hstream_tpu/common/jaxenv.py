"""Where this process runs JAX: the device it got, and where compiled
programs are kept between runs.

Called from process entry points only (`server.main.main`,
`benchmarks/run.py`, `chip_smoke.py`, `__graft_entry__.py`) — never from
`serve()` or any import — so in-process test servers write nothing to
disk (`tests/test_chip_smoke.py` holds where the cache lands).
"""

from __future__ import annotations

import os

# the checkout root: <root>/hstream_tpu/common/jaxenv.py
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def place_compile_cache() -> str:
    """Give JAX's persistent compilation cache a directory that does
    not move between runs (one derived from a temp dir, a pid or the
    clock never hits). `JAX_COMPILATION_CACHE_DIR` wins — JAX reads it
    itself and nothing here sets another; otherwise the cache lives at
    `<checkout>/.jax_cache`. Returns the directory in force."""
    import jax

    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_summary() -> dict:
    """The device as JAX reports it: platform, kind and count."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> dict:
    """`device_summary()` on a TPU; SystemExit anywhere else. The
    measurement entry points call this first: if libtpu fails to start,
    JAX warns and continues on the CPU, and a number taken there must
    never be written under a device metric's name."""
    dev = device_summary()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"no TPU: jax.devices()[0].platform is {dev['platform']!r} "
            f"({dev['kind']} x{dev['count']}); refusing to run on it")
    return dev
