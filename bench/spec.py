"""Find a cell's pieces by name: ``BENCHMARK.json`` at the repository
root, and the configuration, traffic, peak and limit files under
``bench/``."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def config(name: str) -> Dict[str, Any]:
    return _load(os.path.join(BENCH_DIR, "configs", f"{name}.json"))


def traffic(name: str) -> Dict[str, Any]:
    return _load(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def limits(workload: str) -> Dict[str, Any]:
    return _load(os.path.join(BENCH_DIR, "limits", f"{workload}.json"))


def peaks(device_kind: str) -> Dict[str, Any]:
    """The chip's published peaks; a device not in the table is an
    error, never a default."""
    table = _load(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: Dict[str, Any], name: str, section: str
               ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: all of
    them, less those whose ``workloads`` list leaves the cell out."""
    return [m for m in bench[section]
            if "workloads" not in m or name in m["workloads"]]


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_metric(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """The reader of a per-layer metric: ``read(ctx) -> value or None``."""
    path = os.path.join(BENCH_DIR, "layer_metrics", f"{name}.py")
    return _module(path, f"layer_metric:{name}").read


def reference(name: str):
    """A configuration's plain reference module, named in its file."""
    path = os.path.join(BENCH_DIR, "references", f"{name}.py")
    return _module(path, f"reference:{name}")


def kernels(backend: str) -> Dict[str, Any]:
    """Trace names of a backend's fused step program and attention
    kernel (``bench/kernels.json``)."""
    table = _load(os.path.join(BENCH_DIR, "kernels.json"))
    if backend not in table:
        raise KeyError(f"no trace names for backend {backend!r} in "
                       f"bench/kernels.json")
    return table[backend]
