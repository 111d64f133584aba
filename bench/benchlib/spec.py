"""Find a cell's pieces by name.

``BENCHMARK.json`` lists the cells as configuration x traffic mix x chips.
Every other piece is a file of its own under ``bench/``, found by the name
the cell gives it, so a later change adds a cell, a configuration, a mix or
a metric by adding files and entries, never by editing one:

* configuration ``<c>``: the file ``BENCHMARK.json`` names for it
  (``bench/configs/<c>.json``), with its plain reference beside it
  (``bench/configs/<c>.py``);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* the limits of the comparison that decides ``correct`` for cell ``<w>``:
  ``bench/limits/<w>.json``;
* per-layer metric ``<m>``: a reader ``bench/metrics/<m>.py`` with
  ``read(ctx) -> float | None``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class SpecError(Exception):
    """A cell, file or entry the benchmark needs is missing or malformed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    run_seconds: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    reference: ModuleType
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e


def load_module(path: str, name: str) -> ModuleType:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: Dict[str, Any], workload: str) -> bool:
    """A metric without a ``workloads`` key applies to every cell."""
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names config "
                        f"{w['config']!r}, which BENCHMARK.json lacks")
    cfg_file = os.path.join(root, configs[w["config"]]["file"])
    bench_dir = os.path.join(root, "bench")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        run_seconds=int(bench["run_seconds"]),
        config_name=w["config"],
        config=_read_json(cfg_file),
        traffic_name=w["traffic"],
        traffic=_read_json(os.path.join(bench_dir, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(bench_dir, "limits",
                                       workload + ".json")),
        reference=load_module(os.path.splitext(cfg_file)[0] + ".py",
                              f"bench_reference_{w['config']}"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if applies(m, workload)],
    )


def load_reader(metric: str, root: str = ROOT) -> ModuleType:
    return load_module(os.path.join(root, "bench", "metrics", metric + ".py"),
                       f"bench_metric_{metric}")


def read_metrics(per_layer: List[Dict[str, Any]], ctx: Dict[str, Any],
                 root: str = ROOT) -> Dict[str, Dict[str, Any]]:
    """Run each metric's reader; a reader that finds nothing returns None
    and the metric is left out of the result."""
    out = {}
    for m in per_layer:
        value: Optional[float] = load_reader(m["name"], root).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
