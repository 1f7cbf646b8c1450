"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A workload names a configuration and a traffic mix.  The configuration
entry names its file; the mix is ``bench/traffic/<traffic>.json``; each
metric is read by ``bench/metrics/<name>.py``; a configuration's plain
reference is ``bench/reference/<reference>.py``.  Adding a cell, a mix
or a metric is adding files and entries: nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List

# <root>/bench/harness/load.py -> <root>
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]         # the configuration's file
    traffic: Dict[str, Any]        # the traffic mix's file
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: str


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _by_name(entries, name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{sorted(e['name'] for e in entries)}")


def load_cell(name: str, root: str = ROOT, bench=None) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "configuration")
    config = _read_json(os.path.join(root, c["file"]))
    traffic = _read_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without a list is reported wherever the
    # end-to-end metric it moves is
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"]
                                  in e2e_names else [])]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                root=root)


def _load_module(path: str, modname: str):
    if not os.path.exists(path):
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    mod = _load_module(os.path.join(root, "bench", "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))
    return mod.read


def reference_module(name: str, root: str = ROOT):
    return _load_module(os.path.join(root, "bench", "reference",
                                     name + ".py"),
                        "bench_reference_" + name.replace("-", "_"))


def peaks(device_kind: str, root: str = ROOT) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = _read_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (known: "
                       f"{sorted(table['devices'])})")
    return table["devices"][device_kind]
