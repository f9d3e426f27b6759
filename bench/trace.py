"""From a profiler trace to the numbers the per-layer metrics read.

:func:`load_xspace_dir` turns the ``.xplane.pb`` that ``jax.profiler``
writes into a compact record: per device plane, its XLA op events and its
XLA module (program) events; on the host, the annotations of the harness
(``client.*``) and of the index (``op.*``, ``shard.*``). Each event is
``[name, start_ns, duration_ns]`` on the profiler's one clock.

:func:`reduce` works on that record alone, so a recorded one
(``bench/tests/fixtures``) tests it:

* the window is the host span ``client.window``;
* a device is busy where any of its op events (synchronous or async)
  runs: the union of their intervals, clipped to the window; ``busy_s`` is the mean over the cell's
  devices and the idle share is ``1 - busy_s / window_s``;
* a program's device time is the sum of its module events, clipped to the
  window and summed over devices, keyed by the program's jitted function
  name (``jit__lookup_batch_jit(7)`` is ``_lookup_batch_jit``); a program
  not in the trace is absent, never 0;
* each idle gap is put down to the innermost host span open at its
  middle, ``client.window`` alone meaning the harness's loop itself.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional

import numpy as np

HOST_PREFIXES = ("client.", "op.", "shard.")
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINES = ("XLA Ops", "Async XLA Ops")
MODULES_LINE = "XLA Modules"
LAYOUT = re.compile(r"\{[^{}]*\}")
WINDOW = "client.window"
TOP = 10


def load_xspace_dir(path: str) -> dict:
    from jax.profiler import ProfileData
    out = {"devices": {}, "host": []}
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    for f in files:
        pd = ProfileData.from_file(f)
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                dev = out["devices"].setdefault(plane.name,
                                                {"ops": [], "modules": []})
                for line in plane.lines:
                    key = "ops" if line.name in OPS_LINES else (
                        "modules" if line.name == MODULES_LINE else None)
                    if key:
                        dev[key].extend([e.name, e.start_ns, e.duration_ns]
                                        for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    out["host"].extend(
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events
                        if e.name.startswith(HOST_PREFIXES))
    return out


def program_name(module: str) -> str:
    """``jit__lookup_batch_jit(12)`` -> ``_lookup_batch_jit``."""
    base = module.split("(")[0]
    return base[len("jit_"):] if base.startswith("jit_") else base


def op_name(hlo: str) -> str:
    """A device op's event name is its HLO text; keep the instruction and
    its shapes without layouts, at most 160 characters."""
    return LAYOUT.sub("", hlo).replace("%", "")[:160]


def _clip(events, t0: float, t1: float) -> np.ndarray:
    """``[k, 2]`` start/end of every event, clipped to ``[t0, t1]``
    (empty where an event lies outside)."""
    if not events:
        return np.zeros((0, 2))
    a = np.array([[e[1], e[1] + e[2]] for e in events], dtype=np.float64)
    return np.clip(a, t0, t1)


def _sum_by_name(events, iv: np.ndarray, key, into: Dict[str, float]):
    for e, (s, t) in zip(events, iv):
        if t > s:
            k = key(e[0])
            into[k] = into.get(k, 0.0) + (t - s) * 1e-9


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted intervals."""
    if iv.shape[0] == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out)


def _labels(host, mids: np.ndarray) -> List[str]:
    """Innermost host span (latest start) open at each time in ``mids``;
    ``client.window`` where only the window is."""
    spans = [e for e in host if e[0] != WINDOW]
    if not spans or mids.size == 0:
        return [WINDOW] * mids.size
    names = [e[0] for e in spans]
    s = np.array([e[1] for e in spans], np.float64)
    e = s + np.array([e[2] for e in spans], np.float64)
    out = []
    for lo in range(0, mids.size, 256):
        m = mids[lo:lo + 256, None]
        open_ = (s[None, :] <= m) & (m < e[None, :])
        start = np.where(open_, s[None, :], -np.inf)
        pick = np.argmax(start, axis=1)
        out.extend(names[i] if open_[r, i] else WINDOW
                   for r, i in enumerate(pick))
    return out


def reduce(ev: dict, n_devices: int) -> Optional[dict]:
    """The traced window's device busy time, program times, top ops and
    labelled idle gaps; ``None`` where the trace has no window."""
    wins = [e for e in ev["host"] if e[0] == WINDOW]
    if not wins:
        return None
    t0 = float(wins[0][1])
    t1 = t0 + float(wins[0][2])
    window_s = (t1 - t0) * 1e-9
    devs = sorted(ev["devices"], key=lambda d: int(d.rsplit(":", 1)[1]))
    devs = devs[:n_devices]
    host = [e for e in ev["host"] if e[1] < t1 and e[1] + e[2] > t0]
    busy: List[float] = []
    program_s: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    for d in devs:
        rec = ev["devices"][d]
        ops = _clip(rec["ops"], t0, t1)
        merged = _union(ops[ops[:, 1] > ops[:, 0]])
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9
                    if merged.size else 0.0)
        _sum_by_name(rec["modules"], _clip(rec["modules"], t0, t1),
                     program_name, program_s)
        _sum_by_name(rec["ops"], ops, op_name, op_s)
        edges = np.concatenate([[t0], merged.ravel(), [t1]]).reshape(-1, 2) \
            if merged.size else np.array([[t0, t1]])
        edges = edges[edges[:, 1] > edges[:, 0]]
        for lab, g in zip(_labels(host, edges.mean(axis=1)),
                          edges[:, 1] - edges[:, 0]):
            gap_s[lab] = gap_s.get(lab, 0.0) + g * 1e-9 / len(devs)
    if not devs:
        return None
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(gap_s.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": float(np.mean(busy)),
        "program_s": program_s,
        "top_ops": [[k, float(v)] for k, v in top],
        "idle_gaps": [[k, float(v)] for k, v in gaps],
    }
