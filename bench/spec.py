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


def load_module(path: str, prefix: str):
    """The Python file at ``path``, loaded by file under a name of its own
    (files are never imported as top-level names)."""
    stem = os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(
        prefix + stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_readers(root: str) -> Dict[str, Callable]:
    """``{metric name: read(ctx)}`` for every ``bench/metrics/<name>.py``."""
    d = os.path.join(root, "bench", "metrics")
    return {fn[:-3]: load_module(os.path.join(d, fn), "bench_metric_").read
            for fn in sorted(os.listdir(d))
            if fn.endswith(".py") and not fn.startswith("_")}


def reference(root: str, conf: dict):
    """The configuration's plain reference: the module its
    ``reference_file`` names (a path from the checkout's root), else
    ``bench/reference.py``.  It provides ``seq_len`` and ``served_gaps``,
    and may provide ``layout(conf)`` and ``draw_leaf`` (see
    ``weights.build``)."""
    path = conf.get("reference_file")
    if path is None:
        from bench import reference as default
        return default
    if os.path.isabs(path) or ".." in path.split("/"):
        raise ValueError(f"reference_file {path!r} must be a path inside "
                         f"the checkout, from its root")
    return load_module(os.path.join(root, path), "bench_reference_")


def cell_metrics(bm: dict, trace: bool, workload: str = None) -> list:
    """The metric entries of ``BENCHMARK.json`` a run reports: the
    per-layer group with ``--trace 1``, else the end-to-end group; a metric
    with a ``workloads`` list only in the cells it names."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group
            if workload is None or workload in m.get("workloads", [workload])]
