#!/usr/bin/env python3
"""Record a cell's first window steps under the profiler, as a fixture for
the trace reduction's tests.

    python3 bench/record_trace.py --workload ycsb-c --seed 7 --steps 2 \\
        --out bench/tests/fixtures/v5e_ycsb_c_2steps_spans.json

Set-up is the cell's own (``bench/harness.py``): data and index from the
seed, the warm-up steps, then ``--steps`` steps of the closed loop inside
the harness's ``client.window`` annotation. The record is what
``bench.trace.load_xspace_dir`` keeps, with one field more on each device
op event: the op's ``op_name`` metadata, the path of named scopes it was
traced under (``jit(_lookup_batch_jit)/descent/level_3/...``; "" where
unknown). The trace names each op event's instruction but carries no
``op_name``, so it is looked up in the compiled text of the lookup
program (a one-chip cell with reads). A fixture is recorded on a TPU at
the configuration's own size: any other backend is refused.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# `%name = ... metadata={op_name="jit(f)/scope/..." ...}` in compiled HLO
HLO_OP_NAME = re.compile(r'^\s*(?:ROOT )?%?([^\s=]+) = .*?op_name="([^"]*)"',
                         re.M)


def compiled_scopes(index, subs) -> dict:
    """``{program: {instruction: op_name}}`` for the lookup program of a
    one-chip index, compiled for the first read sub-batch of ``subs``. A
    v5e trace gives each op event its instruction (the event's name) but
    no ``op_name``."""
    from repro.core import batch_ops as B
    sb = next(sb for sb in subs if sb.op == "read")
    kb, kl, _ = index.put(sb.kb, sb.kl, None)
    low = B._lookup_batch_jit.lower(index.tree, kb, kl, sibling_check=True,
                                    engine=index.engine)
    return {"_lookup_batch_jit":
            dict(HLO_OP_NAME.findall(low.compile().as_text()))}


def load_scoped(path: str, scopes: dict) -> dict:
    """``load_xspace_dir``'s record, each device op event with its
    ``op_name`` as a fourth field ("" where unknown: looked up in
    ``scopes`` under the program whose module event holds the op)."""
    from bench import trace
    out = trace.load_xspace_dir(path)
    for dev in out["devices"].values():
        mods = [(s, s + d, scopes.get(trace.program_name(m), {}))
                for m, s, d in dev["modules"]]
        for e in dev["ops"]:
            inst = e[0].split(" = ")[0].lstrip("%")
            held = [sc for s, t, sc in mods if s <= e[1] <= t]
            e.append(held[0].get(inst, "") if held else "")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    from bench.run import load_cell, use_compile_cache
    _, conf, mix, _, _ = load_cell(args.workload)

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"record_trace: JAX found {platform!r} devices, not a TPU; "
              f"no fixture", file=sys.stderr)
        return 2
    use_compile_cache()

    @contextlib.contextmanager
    def phase(name):
        yield

    run = harness.Run(conf, mix, args.seed, phase)
    spans = harness.Spans()
    client = harness.Client(run.index, run.traffic, spans)
    scopes = compiled_scopes(run.index, client.next)
    for _ in range(harness.WARMUP_STEPS):
        client.step()
    tracer = harness._Tracer()
    try:
        tracer.start()
        for _ in range(args.steps):
            client.step()
        tracer.stop()
        rec = load_scoped(tracer.dir, scopes)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, separators=(",", ":"))
    ops = [e for d in rec["devices"].values() for e in d["ops"]]
    print(json.dumps({"out": args.out, "devices": len(rec["devices"]),
                      "ops": len(ops), "scoped": sum(bool(e[3]) for e in ops),
                      "host": len(rec["host"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
