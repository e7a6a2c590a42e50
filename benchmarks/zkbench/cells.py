"""Resolve a cell of ``BENCHMARK.json`` to the data files that define it.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: every name comes from ``BENCHMARK.json`` and is turned into a path
under ``benchmarks/`` by one rule each (``benchmarks/README.md``).
"""

import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class CellError(Exception):
    """The cell cannot be resolved: a name without its file."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def merged(base: Dict, override: Optional[Dict]) -> Dict:
    """``base`` with ``override`` laid over it, group by group (the
    ``rehearsal`` groups of the data files)."""
    out = dict(base)
    for key, value in (override or {}).items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merged(out[key], value)
        else:
            out[key] = value
    return out


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise CellError(f"no BENCHMARK.json at {path}")
    return load_json(path)


_LOADED: Dict[str, Any] = {}


def load_module(path: str, name: str):
    """Import one file by path, once per process (metric readers and
    entries carry dots in their names, so they are not importable as
    modules by name; the program's example tasks register themselves by
    name and must not be executed twice)."""
    path = os.path.abspath(path)
    if path in _LOADED:
        return _LOADED[path]
    if not os.path.isfile(path):
        raise CellError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        "zkbench_file_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    _LOADED[path] = module
    return module


def _one(entries: List[dict], name: str, what: str) -> dict:
    found = [e for e in entries if e.get("name") == name]
    if len(found) != 1:
        raise CellError(
            f"{what} {name!r}: {len(found)} entries in BENCHMARK.json "
            f"(known: {sorted(e.get('name') for e in entries)})"
        )
    return found[0]


def metric_applies(metric: dict, cell: str, cell_end_to_end: List[str]) -> bool:
    """The contract's rule: a metric with ``workloads`` is reported in
    those cells; without it, an end-to-end metric is reported everywhere
    and a per-layer metric wherever the metric it ``moves`` is."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in cell_end_to_end
    return True


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmarks")
        self.benchmark = load_benchmark(root)
        self.entry = _one(self.benchmark["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        config_entry = _one(
            self.benchmark["configs"], self.entry["config"], "config"
        )
        self.config_path = os.path.join(root, config_entry["file"])
        if not os.path.isfile(self.config_path):
            raise CellError(f"config file missing: {self.config_path}")
        self.config = load_json(self.config_path)
        self.traffic_path = os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"
        )
        if not os.path.isfile(self.traffic_path):
            raise CellError(f"traffic file missing: {self.traffic_path}")
        self.traffic = load_json(self.traffic_path)
        self.run_seconds = int(self.benchmark["run_seconds"])
        self.end_to_end = [
            m for m in self.benchmark["end_to_end"]
            if metric_applies(m, name, [])
        ]
        names = [m["name"] for m in self.end_to_end]
        self.per_layer = [
            m for m in self.benchmark["per_layer"]
            if metric_applies(m, name, names)
        ]

    def entry_module(self):
        kind = self.config["entry"]
        return load_module(
            os.path.join(self.bench_dir, "entries", kind + ".py"), kind
        )

    def reference_module(self):
        ref = self.config["reference"]
        return load_module(
            os.path.join(self.bench_dir, "reference", ref + ".py"),
            "reference_" + ref,
        )

    def shapes_module(self, name: str):
        return load_module(
            os.path.join(self.bench_dir, "shapes", name + ".py"),
            "shapes_" + name,
        )

    def layer_metric(self, name: str):
        """``(spec, reader module)`` of one per-layer metric: the spec is
        ``layer_metrics/<name>.json``; its ``reader`` key names the
        ``.py`` beside it (default: the metric's own name)."""
        base = os.path.join(self.bench_dir, "layer_metrics")
        spec_path = os.path.join(base, name + ".json")
        if not os.path.isfile(spec_path):
            raise CellError(f"per-layer metric file missing: {spec_path}")
        spec = load_json(spec_path)
        reader = spec.get("reader", name)
        return spec, load_module(
            os.path.join(base, reader + ".py"), "reader_" + reader
        )

    def peaks(self, device_kind: str) -> Dict[str, float]:
        table = load_json(os.path.join(self.bench_dir, "peaks.json"))
        row: Optional[dict] = table["devices"].get(device_kind)
        if row is None:
            raise CellError(
                f"device kind {device_kind!r} is not in benchmarks/"
                f"peaks.json (known: {sorted(table['devices'])}); a device "
                "that is not in the table is an error, not a default"
            )
        return row
