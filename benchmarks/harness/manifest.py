"""BENCHMARK.json and the files it names. A cell `<config>.<traffic>`
resolves to `benchmarks/configs/<config>.json` and
`benchmarks/traffic/<traffic>.json`; a per-layer metric to
`benchmarks/metrics/<name>.json`, whose `reader` names a module under
`benchmarks/readers/`; a configuration's `reference` names a module
under `benchmarks/references/`. Nothing here knows a cell by name."""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
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
    mod = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    return spec, getattr(mod, spec.get("function", "read"))


def reference_of(config: dict):
    return importlib.import_module(
        f"benchmarks.references.{config['reference']}")
