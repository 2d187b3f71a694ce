"""BENCHMARK.json and the files it names. A cell `<config>.<traffic>`
resolves to the configuration's `file` and
`benchmarks/traffic/<traffic>.json`; a per-layer metric to
`benchmarks/metrics/<name>.json`, whose `reader` names a module under
`benchmarks/readers/`; a configuration's `generator` names a module
under `benchmarks/generators/` and its `reference` one under
`benchmarks/references/`. Nothing here knows a cell by name.

`use(path)` puts another manifest in BENCHMARK.json's place (the tests'
fixture manifest): files and modules are then looked for beside that
manifest first (`<its directory>/<kind>/<name>`) and under
`benchmarks/` after."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
_home: dict = {}


def use(path: str | None) -> None:
    """Read `path` (relative to the checkout's root) as the manifest;
    None: BENCHMARK.json itself."""
    if not path:
        _home.update(manifest=os.path.join(ROOT, "BENCHMARK.json"),
                     dirs=[BENCH_DIR])
        return
    path = os.path.join(ROOT, path)
    _home.update(manifest=path, dirs=[os.path.dirname(path), BENCH_DIR])


use(None)


def _find(kind: str, name: str) -> tuple[str, str]:
    for d in _home["dirs"]:
        path = os.path.join(d, kind, name)
        if os.path.exists(path):
            return d, path
    raise FileNotFoundError(
        f"no {kind}/{name} under {' or '.join(_home['dirs'])}")


def load_json(kind: str, name: str) -> dict:
    with open(_find(kind, name)[1]) as f:
        return json.load(f)


def module_of(kind: str, name: str):
    """`<kind>/<name>.py`: of `benchmarks/` by import, of a fixture
    manifest's directory by its path."""
    d, path = _find(kind, name + ".py")
    if d == BENCH_DIR:
        return importlib.import_module(f"benchmarks.{kind}.{name}")
    key = f"bench_fixture_{kind}_{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def manifest() -> dict:
    with open(_home["manifest"]) as f:
        return json.load(f)


def cell(name: str) -> dict:
    """The workload entry `name`, with its configuration and traffic
    files loaded beside it."""
    man = manifest()
    for w in man["workloads"]:
        if w["name"] == name:
            break
    else:
        raise SystemExit(
            f"unknown workload {name!r}; BENCHMARK.json lists "
            f"{[w['name'] for w in man['workloads']]}")
    entry = next(c for c in man["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", w["traffic"] + ".json")
    return {"name": name, "chips": w["chips"], "config": config,
            "traffic": traffic, "manifest": man}


def size_of(config: dict, dry: bool) -> dict:
    """The sizes a run uses: the configuration's own, or with its `dry`
    block laid over them (tests only, never a result)."""
    size = {k: v for k, v in config.items() if k != "dry"}
    if dry:
        size.update(config["dry"])
    return size


def reports(metric: dict, cell_name: str, man: dict) -> bool:
    """Whether `metric` is due in `cell_name`: its own `workloads` list,
    or every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    moved = metric.get("moves")
    if moved is None:
        return True
    target = next(m for m in man["end_to_end"] if m["name"] == moved)
    return reports(target, cell_name, man)


def metrics_of(cell_name: str, man: dict, kind: str) -> list[dict]:
    return [m for m in man[kind] if reports(m, cell_name, man)]


def reader_of(metric_name: str):
    """A metric's file and its reader, `read(run, spec) -> number or
    None` in `benchmarks/readers/<reader>.py`."""
    spec = load_json("metrics", metric_name + ".json")
    mod = module_of("readers", spec["reader"])
    return spec, getattr(mod, spec.get("function", "read"))


def reference_of(config: dict):
    return module_of("references", config["reference"])


def generator_of(config: dict):
    """The module that makes the configuration's streams, frames and
    pulls. A configuration that names none is an error: there is no
    default generator."""
    if not config.get("generator"):
        raise SystemExit(f"configuration {config.get('name')!r} names no "
                         "generator")
    return module_of("generators", config["generator"])


def server_options(config: dict, serve) -> dict:
    """The configuration's `server` block: keyword arguments of
    `serve()` that the deployment itself states. A key `serve()` does
    not take as a keyword-only option is refused."""
    import inspect

    block = dict(config.get("server") or {})
    takes = {n for n, p in inspect.signature(serve).parameters.items()
             if p.kind is p.KEYWORD_ONLY}
    unknown = sorted(set(block) - takes)
    if unknown:
        raise SystemExit(f"configuration {config.get('name')!r}: server "
                         f"block has {unknown}, which serve() does not "
                         "take")
    return block


def mesh_devices(config: dict) -> int | None:
    """Devices the configuration's `mesh_shape` asks for ("DxK"), or
    None where it states none."""
    shape = (config.get("server") or {}).get("mesh_shape")
    if not shape:
        return None
    n_data, _, n_key = str(shape).lower().partition("x")
    return int(n_data) * int(n_key or 1)
