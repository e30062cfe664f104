"""Where a cell's pieces live, found by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds files and entries and edits none of these.

    BENCHMARK.json            workloads[] -> {name, config, traffic, chips}
    configs/<config>.json     published sizes, ``source``, ``assumed``, ``reduced``,
                              ``reference`` (a module under reference/)
    traffic/<traffic>.json    ``driver`` (a module under drivers/) + its parameters
    limits/<workload>.json    the limit of every number ``correct`` compares
                              (``rehearse``: the limits at the rehearsal sizes)
    metrics/<metric>.json|py  one per-layer metric: an expression over the
                              run's table, or a reader ``read(table) -> value|None``
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List, NamedTuple, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class Cell(NamedTuple):
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]     # the metrics this cell reports
    per_layer: List[Dict[str, Any]]


def _load(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: Dict[str, Any], workload: str, e2e_names=None) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    # a per-layer metric without the key is read wherever the end-to-end
    # metric it moves is reported
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(workload: str, rehearse: bool = False) -> Cell:
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = _load("traffic", entry["traffic"] + ".json")
    limits = _load("limits", workload + ".json")
    if rehearse:
        preset = _load("rehearse.json")
        config = {**config, **preset["config"]}
        traffic = {**traffic, **traffic.get("rehearse", {})}
        limits = {**limits, **limits.get("rehearse", {})}
    limits = {k: float(v) for k, v in limits.items() if k != "rehearse"}
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return Cell(workload, int(entry["chips"]), config, traffic, limits,
                e2e, per_layer)


def driver(name: str):
    return importlib.import_module(f"benchmark.drivers.{name}")


def reference(name: str):
    return importlib.import_module(f"benchmark.reference.{name}")


def metric_reader(name: str):
    """The reader of one per-layer metric: ``read(table) -> value | None``.
    ``metrics/<name>.py`` is a reader of its own; ``metrics/<name>.json``
    holds ``{"value": <expression>, "needs": [keys]}`` over the table."""
    py = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if os.path.exists(py):
        return importlib.import_module(f"benchmark.metrics.{name}").read
    spec = _load("metrics", name + ".json")

    def read(table: Dict[str, Any]) -> Optional[float]:
        return evaluate(spec, table)

    return read


_SAFE = {"max": max, "min": min, "abs": abs, "len": len, "sum": sum}


def evaluate(spec: Dict[str, Any], table: Dict[str, Any]
             ) -> Optional[float]:
    """An expression-type metric.  Nothing to read — a needed key is
    absent or None, or the expression divides by a zero — is ``None``,
    never 0."""
    flat = flatten(table)
    for key in spec.get("needs", []):
        if flat.get(key) is None:
            return None
    try:
        value = eval(spec["value"], {"__builtins__": {}},  # noqa: S307 — the benchmark's own data files
                     {**_SAFE, **{k.replace(".", "_"): v
                                  for k, v in flat.items()}})
    except (ZeroDivisionError, NameError, TypeError):
        return None
    return None if value is None else float(value)


def flatten(table: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in table.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = v
    return out
