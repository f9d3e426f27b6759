"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only ycsb,...]
      [--backend {jnp,pallas,...}] [--layout {tuple,stacked}] [--smoke]

``--suite`` is an alias for ``--only``. ``--backend``/``--layout`` apply
to the engine-selecting suites (ycsb, factor); the traverse suite always
A/Bs every backend×layout×stats combination and the scan suite A/Bs both
scan backends (jnp reference vs the fused scan kernel) on ordered and
dirtied leaves. ``--smoke`` is the CI guard: tiny trees, one timing pass,
all traversal backends (incl. the fused descent and fused scan kernels in
interpret mode) parity-checked — and ``BENCH_traverse.json`` is left
untouched so CI runs never overwrite the perf trajectory anchor.

The ``traverse`` suite writes ``BENCH_traverse.json`` at the repo root;
the ``build`` suite (host vs device ``bulk_build`` + ``rebuild``) and the
``scan`` suite (``scan_rows``) merge their rows into the same file.
Writes CSVs under out/bench/ and prints each table.
"""
from __future__ import annotations

import argparse
import csv
import json
import os
import sys
import time

from repro.jax_cache import use_compile_cache

from . import (contention, factor_analysis, feature_size,
               hardware_counters, memory, roofline_table, scan, shard,
               traverse_bench, ycsb)
from .common import fmt_table

SUITES = {
    "ycsb": ("Fig.11/17 — YCSB core workloads",
             lambda fast, **eng: ycsb.run(n_keys=8_000 if fast else 20_000,
                                          n_ops=8_192 if fast else 40_960,
                                          **eng),
             ycsb.COLUMNS),
    "factor": ("Fig.12a — structural factor analysis",
               lambda fast, **eng: factor_analysis.run(
                   n_keys=8_000 if fast else 20_000,
                   n_ops=8_192 if fast else 16_384, **eng),
               factor_analysis.COLUMNS),
    "traverse": ("Engine A/B — traversal backends × layouts × stats",
                 lambda fast, **kw: traverse_bench.run(
                     n_keys=8_000 if fast else 20_000,
                     n_ops=8_192 if fast else 16_384, **kw),
                 traverse_bench.COLUMNS),
    "build": ("DESIGN.md §5 — host vs device bulk build + rebuild",
              lambda fast: traverse_bench.run_build(
                  sizes=(2_000, 8_000) if fast else (5_000, 20_000)),
              traverse_bench.BUILD_COLUMNS),
    "memory": ("Fig.12b — index memory consumption",
               lambda fast: memory.run(n_keys=8_000 if fast else 20_000),
               memory.COLUMNS),
    "feature_size": ("Fig.13 — feature-size sweep",
                     lambda fast: feature_size.run(
                         n_keys=8_000 if fast else 20_000,
                         n_ops=4_096 if fast else 16_384,
                         fss=(1, 2, 4) if fast else (1, 2, 4, 8, 12)),
                     feature_size.COLUMNS),
    "contention": ("Fig.14/15 — update scalability under contention",
                   lambda fast: contention.run_batched(
                       n_keys=8_000 if fast else 20_000,
                       n_ops=8_192 if fast else 32_768),
                   contention.COLUMNS_BATCHED),
    "contention_protocol": ("Fig.14 (protocol view) — retries vs threads",
                            lambda fast: contention.run_protocol(),
                            contention.COLUMNS_PROTOCOL),
    "hardware": ("Fig.1/16 — hardware-event analogue counters",
                 lambda fast: hardware_counters.run(
                     n_keys=10_000 if fast else 50_000),
                 hardware_counters.COLUMNS),
    "scan": ("Fig.17(E) — range scan engine A/B (jnp vs fused × "
             "ordered/dirty)",
             lambda fast, **kw: scan.run(n_keys=8_000 if fast else 20_000,
                                         **kw),
             scan.COLUMNS),
    "shard": ("DESIGN.md §7 — sharded tree: 1 vs 2 vs 4 shards, "
              "parity-gated",
              lambda fast, **kw: shard.run(n_keys=8_000 if fast else 20_000,
                                           n_ops=4_096 if fast else 8_192,
                                           **kw),
              shard.COLUMNS),
    "roofline": ("§Roofline — dry-run derived table",
                 lambda fast: roofline_table.run(),
                 roofline_table.COLUMNS),
}


# suites that accept traversal-engine selection
_ENGINE_SUITES = ("ycsb", "factor")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--suite", default=None, help="alias for --only")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny n, parity asserts across all "
                         "backends; skips the BENCH_traverse.json write")
    ap.add_argument("--out", default="out/bench")
    ap.add_argument("--backend", default="jnp",
                    help="traversal branch backend (jnp, pallas, ...)")
    ap.add_argument("--layout", default=None, choices=(None, "tuple",
                                                       "stacked"),
                    help="descent layout (default: tree config)")
    args = ap.parse_args(argv)
    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    only = args.suite or args.only
    names = only.split(",") if only else list(SUITES)
    os.makedirs(args.out, exist_ok=True)
    failed = []
    report = []     # per-suite timing/status -> out/bench_report.json
    for name in names:
        title, fn, cols = SUITES[name]
        eng = (dict(backend=args.backend, layout=args.layout)
               if name in _ENGINE_SUITES else {})
        if args.smoke and name in ("traverse", "scan", "shard"):
            eng["smoke"] = True
        t0 = time.time()
        try:
            rows = fn(args.fast, **eng)
        except Exception as e:  # keep the suite running
            print(f"\n== {name}: FAILED — {type(e).__name__}: {e}",
                  flush=True)
            import traceback
            traceback.print_exc()
            failed.append(name)
            report.append({"suite": name, "case": title,
                           "wall_s": round(time.time() - t0, 3),
                           "status": "failed", "rows": 0})
            continue
        dt = time.time() - t0
        # the parity-gated suites assert inside fn(), so reaching here
        # means their backend A/B checks passed
        report.append({"suite": name, "case": title,
                       "wall_s": round(dt, 3), "status": "ok",
                       "rows": len(rows)})
        print(f"\n== {title}  [{name}, {dt:.1f}s]")
        print(fmt_table(rows, cols))
        with open(os.path.join(args.out, f"{name}.csv"), "w",
                  newline="") as f:
            w = csv.DictWriter(f, fieldnames=cols, extrasaction="ignore")
            w.writeheader()
            w.writerows(rows)
        if args.smoke:
            continue  # never clobber the perf trajectory anchor from CI
        if name == "traverse":
            print("engine A/B written to", traverse_bench.write_json(rows))
        elif name == "build":
            print("build rows written to",
                  traverse_bench.write_json(build_rows=rows))
        elif name == "scan":
            print("scan rows written to",
                  traverse_bench.write_json(scan_rows=rows))
        elif name == "shard":
            print("shard rows written to",
                  traverse_bench.write_json(shard_rows=rows))
    rpt_path = os.path.join(args.out, "..", "bench_report.json")
    rpt_path = os.path.normpath(rpt_path)
    with open(rpt_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print("\nCSV written to", args.out)
    print("suite report written to", rpt_path)
    if failed:
        raise SystemExit(f"suites failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
