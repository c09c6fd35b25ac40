"""Finds everything of a benchmark by name, from the root of a checkout.

``BENCHMARK.json`` names the cells, configurations and metrics; each has
files of its own, found here by name alone, so a later change adds a
configuration, a traffic mix, a cell or a metric by adding files and
entries, and edits nothing:

* ``<config file>``: the configuration's entry names it (``bench/configs/``);
* ``bench/traffic/<mix>.json``: a traffic mix, read by ``bench.traffic``;
* ``bench/cells/<workload>.json``: a cell's batch, cache length and limits;
* ``bench/metrics/<metric>.py``: the metric's reader, ``read(run)``;
* ``bench/programs/<architecture>.py`` and ``bench/references/
  <architecture>.py``: the program adapter and the plain reference of the
  architecture a configuration file names (``"architecture"``), so a new
  architecture joins by adding these two files.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

#: root of the checkout this file lies in
ROOT = Path(__file__).resolve().parent.parent


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _entry(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: {[e['name'] for e in entries]}")


def workload(name: str, root: Path = ROOT) -> dict:
    return _entry(benchmark(root)["workloads"], name, "workload")


def config(name: str, root: Path = ROOT) -> dict:
    entry = _entry(benchmark(root)["configs"], name, "config")
    return json.loads((Path(root) / entry["file"]).read_text())


def traffic(name: str, root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "bench" / "traffic" / f"{name}.json").read_text())


def cell(name: str, root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "bench" / "cells" / f"{name}.json").read_text())


def metrics(workload_name: str, kind: str, root: Path = ROOT) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a workload reports."""
    return [m for m in benchmark(root)[kind]
            if "workloads" not in m or workload_name in m["workloads"]]


def _load(path: Path, name: str, missing: str):
    """The module at ``path``, loaded under ``name``; raises ``missing``
    when there is no such file."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(missing)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _architecture(cfg: dict, kind: str, root: Path):
    arch = cfg.get("architecture")
    if arch is None:
        raise KeyError(f"configuration {cfg.get('name')!r} names no architecture")
    path = Path(root) / "bench" / kind / f"{arch}.py"
    return _load(path, f"bench_{kind}_{arch.replace('.', '_').replace('-', '_')}",
                 f"configuration {cfg.get('name')!r} names architecture {arch!r}, "
                 f"which has no {kind[:-1]}: looked for {path}")


def program(cfg: dict, root: Path = ROOT):
    """The program adapter ``bench/programs/<architecture>.py`` of a configuration."""
    return _architecture(cfg, "programs", root)


def reference(cfg: dict, root: Path = ROOT):
    """The plain reference ``bench/references/<architecture>.py`` of a configuration."""
    return _architecture(cfg, "references", root)


def reader(metric: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    return _load(path, f"bench_metric_{metric.replace('.', '_')}",
                 f"no reader for metric {metric!r} at {path}").read
