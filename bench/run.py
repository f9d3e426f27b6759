#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run.py --workload ycsb-c --seed 7 --seconds 10 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/<traffic>.json``).
Set-up phases print as JSON lines first; the last line of standard output
is the result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window's first steps), ``device``,
``breakdown`` (traced runs) and, last, ``checks``: every number compared
with the reference beside its limit, also printed as the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.

``--fault`` runs the control or a planted fault (``bench/faults.py``)
in place of the timed path; the benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_cell(name: str, root: str = ROOT):
    """``(workload, configuration, mix, end_to_end, per_layer)`` of the
    cell ``name``, each metric list cut to the metrics that apply."""
    from bench import traffic
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    wl = cells[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    with open(os.path.join(root, conf_entry["file"])) as f:
        conf = json.load(f)
    mix = traffic.load_mix(os.path.join(root, "bench", "traffic",
                                        wl["traffic"] + ".json"))

    def applies(m):
        return name in m.get("workloads", [name])
    return (wl, conf, mix, [m for m in spec["end_to_end"] if applies(m)],
            [m for m in spec["per_layer"] if applies(m)])


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else a fixed ``.jax_cache/`` in the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None,
                    help="control or planted fault (bench/faults.py)")
    args = ap.parse_args(argv)
    # run as a script, Python puts bench/ first on the path, where its
    # modules would shadow others of the same name (``trace``)
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    wl, conf, mix, e2e, per_layer = load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found {devices[0].platform!r} devices, not a TPU; "
              f"no result", file=sys.stderr)
        return 2
    if len(devices) < int(wl["chips"]):
        print(f"bench: the cell needs {wl['chips']} chips, JAX found "
              f"{len(devices)}; no result", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    print(json.dumps({"phase": "start", "compile_cache": cache,
                      "kind": devices[0].device_kind,
                      "count": len(devices)}), flush=True)

    from bench import harness
    out = harness.run_cell(conf, mix, args.seed, args.seconds,
                           trace=bool(args.trace), fault=args.fault,
                           t_start=T_START, per_layer=per_layer)
    if not args.trace:
        keep = {m["name"] for m in e2e}
        out["metrics"] = {k: v for k, v in out["metrics"].items()
                          if k in keep}
    out.pop("setup_phases", None)
    checks = out.pop("checks")
    out["checks"] = checks
    harness.print_checks(checks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
