"""Per-layer metrics: one small reader per metric, found by its name.

``bench/metrics/<name>.py`` defines ``read(ctx) -> float | None``. A
reader that finds nothing to read returns ``None`` and the metric is left
out of the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
from typing import Any, Dict, Optional

METRICS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "metrics")


@dataclasses.dataclass
class Context:
    reduced: Optional[dict]     # trace.reduce(...) of the traced steps
    steps: int                  # steps under the profiler
    mix: dict                   # the cell's traffic mix
    config: Any                 # the TreeConfig of one tree (one shard)
    spans: Dict[str, float]     # harness span seconds over those steps
    device_kind: str


def reader(name: str, metrics_dir: str = METRICS_DIR):
    path = os.path.join(metrics_dir, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(entries, ctx: Context, metrics_dir: str = METRICS_DIR) -> dict:
    out = {}
    for m in entries:
        v = reader(m["name"], metrics_dir)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
