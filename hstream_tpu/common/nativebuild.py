"""Shared build-on-demand for native shared libraries.

One canonical g++ invocation for every cbits-style source in the tree
(store/cpp/nstore.cpp, engine/cpp/encode.cpp) — the dev-friendly
analogue of the reference's cabal cxx-sources builds."""

from __future__ import annotations

import os
import subprocess
import threading

_lock = threading.Lock()


def build_so(src: str, so: str, *, libs: tuple[str, ...] = (),
             opt: str = "-O2", force: bool = False) -> str:
    """Compile `src` -> `so` if stale; returns the .so path."""
    with _lock:
        if (not force and os.path.exists(so)
                and os.path.getmtime(so) >= os.path.getmtime(src)):
            return so
        # a name of this process's own: test workers that find the
        # library stale build it side by side, and each puts a whole
        # file in place
        tmp = f"{so}.{os.getpid()}.tmp"
        cmd = ["g++", "-std=c++17", opt, "-fPIC", "-shared", "-pthread",
               src, "-o", tmp] + [f"-l{lib}" for lib in libs]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"native build of {os.path.basename(src)} failed:\n"
                f"{proc.stderr[-4000:]}")
        os.replace(tmp, so)
        return so
