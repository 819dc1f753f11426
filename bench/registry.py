"""Finds what ``BENCHMARK.json`` names, by name, in files of their own.

  configuration   bench/configs/<config>.json
  traffic mix     bench/traffic/<traffic>.json, whose "generator" names
                  bench/generators/<generator>.py
  per-layer metric bench/metrics/<metric>.py, a reader with ``read(ctx)``

A later change adds a configuration, a mix, a generator or a metric by
adding such a file and an entry in ``BENCHMARK.json``; nothing here
changes.  ``root`` is the directory that holds ``BENCHMARK.json`` and
``bench/`` (tests point it at a scratch copy).
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


class NotFound(LookupError):
    pass


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise NotFound(f"no file {path}")
    return json.loads(path.read_text())


def benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def workload(name: str, root: Path = ROOT) -> dict:
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise NotFound(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, root: Path = ROOT) -> dict:
    for c in benchmark(root)["configs"]:
        if c["name"] == name:
            cfg = _load_json(root / c["file"])
            if cfg.get("name") != name:
                raise NotFound(f"{c['file']} holds {cfg.get('name')!r}, "
                               f"not {name!r}")
            return cfg
    raise NotFound(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    return _load_json(root / "bench" / "traffic" / f"{name}.json")


def _module(path: Path, what: str):
    if not path.is_file():
        raise NotFound(f"no {what} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{what}_{path.stem.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def generator(kind: str, root: Path = ROOT):
    """The module whose ``make(mix, seed, n_slots)`` returns the mix's
    request source (``next_due``, ``pop_due``, ``on_finish``)."""
    return _module(root / "bench" / "generators" / f"{kind}.py", "generator")


def metric_reader(name: str, root: Path = ROOT):
    """The module whose ``read(ctx)`` returns the metric or None."""
    return _module(root / "bench" / "metrics" / f"{name}.py", "metric")


def metrics_for(workload_name: str, root: Path = ROOT) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries this cell reports."""
    bm = benchmark(root)

    def mine(ms):
        return [m for m in ms
                if workload_name in m.get("workloads", [workload_name])]
    return mine(bm["end_to_end"]), mine(bm["per_layer"])


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    table = _load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise NotFound(f"device {device_kind!r} is not in bench/peaks.json")
    return table["devices"][device_kind]
