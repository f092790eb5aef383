"""Find the benchmark's entries by name: cells, configurations, traffic
mixes and per-layer metric readers are files, never names in code."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def benchmark(root: str) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(root: str, name: str) -> dict:
    """Everything one cell runs with: its ``BENCHMARK.json`` entry, its
    file under ``bench/workloads``, the configuration and the mix."""
    bm = benchmark(root)
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bm['workloads']]}")
    conf_entry = next(c for c in bm["configs"] if c["name"] == entry["config"])
    data = os.path.join(root, "bench")
    return {
        "entry": entry,
        "bench": bm,
        "cell": load_json(os.path.join(data, "workloads", name + ".json")),
        "conf": load_json(os.path.join(root, conf_entry["file"])),
        "mix": load_json(os.path.join(data, "traffic",
                                      entry["traffic"] + ".json")),
    }


def metric_readers(root: str) -> Dict[str, Callable]:
    """``{metric name: read(ctx)}`` for every ``bench/metrics/<name>.py``."""
    d = os.path.join(root, "bench", "metrics")
    out = {}
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".py") or fn.startswith("_"):
            continue
        name = fn[:-3]
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace(".", "_").replace("-", "_"),
            os.path.join(d, fn))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod.read
    return out


def cell_metrics(bm: dict, trace: bool, workload: str = None) -> list:
    """The metric entries of ``BENCHMARK.json`` a run reports: the
    per-layer group with ``--trace 1``, else the end-to-end group; a metric
    with a ``workloads`` list only in the cells it names."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group
            if workload is None or workload in m.get("workloads", [workload])]
