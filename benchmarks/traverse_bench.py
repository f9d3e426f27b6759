"""Traversal engine A/B: backends (jnp vs pallas-interpret) × stats (on vs
the stats-free hot path) on identical trees and query streams — plus the build
benchmark (:func:`run_build`): host-numpy vs device-jnp ``bulk_build``
across datasets and tree sizes, with a bit-exact parity cross-check
(DESIGN.md §5).

Cross-checks that every stats-on combination returns identical leaf ids and
machine-independent counters (``key_compares``, ``suffix_bs``,
``feat_rounds``) and that every stats-off combination returns identical
``found`` — the engine contract (the check runs the FULL lookup pipeline,
descent + hashtag probe) — then reports relative throughput. Since PR 3
the ``Mops`` column times the *engine descent* (``batch_ops.traverse_path``,
the code the backends actually differ on) rather than the whole lookup, so
``Mops`` is not comparable to pre-PR3 rows; the counter columns are
unchanged and stay comparable. Results land in ``BENCH_traverse.json`` at
the repo root (``rows`` = traversal A/B, ``build_rows`` = host-vs-device
build) so the perf trajectory of future kernel PRs starts here.

``smoke=True`` is the CI mode (`benchmarks/run.py --suite traverse
--smoke`): tiny trees, one timing iteration, every backend, ``pallas`` in
interpret mode — the parity asserts are the point; a kernel-path
regression fails CI instead of rotting until the next bench run.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batch_ops as B
from repro.core import keys as K
from repro.core.fbtree import TreeConfig, bulk_build
from repro.core.traverse import TraversalEngine

from .common import build_tree, make_dataset, timed, zipf_indices

BACKENDS = ("jnp", "pallas")


def run(datasets=("ycsb", "url"), n_keys=20_000, n_ops=16_384,
        seed=23, smoke: bool = False) -> List[Dict]:
    if smoke:
        datasets = ("ycsb",)
        n_keys, n_ops = 600, 512
    rows = []
    rng = np.random.default_rng(seed)
    chunk = min(4096, n_ops)
    for ds in datasets:
        keys, width = make_dataset(ds, n_keys)
        tree, ks = build_tree(keys, width)
        idx = zipf_indices(rng, len(keys), n_ops, 0.99)
        qb, ql = jnp.asarray(ks.bytes[idx]), jnp.asarray(ks.lens[idx])
        ref = None
        ref_found = None
        for backend in BACKENDS:
            for stats_on in (True, False):
                eng = TraversalEngine(backend=backend, collect_stats=stats_on)
                def fn():
                    outs = []
                    for off in range(0, n_ops, chunk):
                        leaf, _, _ = B.traverse_path(tree, qb[off:off + chunk],
                                                     ql[off:off + chunk],
                                                     engine=eng)
                        outs.append(leaf)
                    return outs
                t = timed(fn, warmup=1 if smoke else 2,
                          iters=1 if smoke else 7)
                _, rep = B.lookup_batch(tree, qb[:chunk], ql[:chunk],
                                        engine=eng)
                if stats_on:
                    sig = (np.asarray(rep.found),
                           np.asarray(rep.key_compares),
                           np.asarray(rep.suffix_bs),
                           np.asarray(rep.feat_rounds))
                    if ref is None:
                        ref, ref_found = sig, sig[0]
                    else:
                        for a, b, nm in zip(ref, sig,
                                            ("found", "key_compares",
                                             "suffix_bs", "feat_rounds")):
                            assert (a == b).all(), \
                                f"{ds}: {backend} diverges on {nm}"
                else:
                    # stats-free contract: counters are zero by design,
                    # found-ness must still match the stats-on reference
                    assert (np.asarray(rep.found) == ref_found).all(), \
                        f"{ds}: {backend} stats-off diverges"
                row = {
                    "dataset": ds, "n_keys": len(keys), "n_ops": n_ops,
                    "backend": backend,
                    "stats": "on" if stats_on else "off",
                    "Mops": round(n_ops / t / 1e6, 3),
                    "parity": "ok",
                }
                if stats_on:
                    row.update({
                        "key_cmp/op": round(float(rep.key_compares.mean()), 2),
                        "suffix_bs/op": round(float(rep.suffix_bs.mean()), 3),
                        "feat_rounds/op": round(float(rep.feat_rounds.mean()), 2),
                    })
                rows.append(row)
    return rows


# n_keys/n_ops ride along so the trajectory anchor stays comparable across
# PRs — counters like key_cmp/op shift with tree size, not just with code
COLUMNS = ["dataset", "n_keys", "n_ops", "backend", "stats",
           "Mops", "key_cmp/op", "suffix_bs/op", "feat_rounds/op", "parity"]


def run_build(datasets=("ycsb", "url"), sizes=(5_000, 20_000),
              rebuild_frac=0.3, seed=23) -> List[Dict]:
    """Host vs device ``bulk_build`` (+ ``rebuild``) across datasets/sizes.

    For each (dataset, n_keys): time the numpy host build, the jit device
    build, and a device ``rebuild`` after tombstoning ``rebuild_frac`` of the
    keys; verify host and device builds are bit-identical (the DESIGN.md §5
    parity contract) before reporting. On the CPU backend the device rows are
    relative anchors only (XLA-CPU gathers lose to numpy at these sizes); the
    win the rows track is device residency — no host round-trip, and
    ``rebuild`` composing under jit with the serving loop.
    """
    rows = []
    for ds in datasets:
        for n_keys in sizes:
            keys, width = make_dataset(ds, n_keys, seed=seed)
            ks = K.make_keyset(keys, width)
            cfg = TreeConfig.plan(max_keys=int(len(keys) * 2.5),
                                  key_width=width)
            vals = np.arange(len(keys), dtype=np.int32)
            def _equal(ta, tb):
                return all(
                    (np.asarray(x) == np.asarray(y)).all()
                    for x, y in zip(jax.tree_util.tree_leaves(ta.arrays),
                                    jax.tree_util.tree_leaves(tb.arrays)))

            th = bulk_build(cfg, ks, vals)
            td = bulk_build(cfg, ks, vals, device=True)
            parity = _equal(th, td)
            t_host = timed(lambda: bulk_build(cfg, ks, vals))
            t_dev = timed(lambda: bulk_build(cfg, ks, vals, device=True))
            n_rm = int(len(keys) * rebuild_frac)
            rm = K.make_keyset(keys[:n_rm], width)
            tfrag, _ = B.remove_batch(td, jnp.asarray(rm.bytes),
                                      jnp.asarray(rm.lens))
            t_reb = timed(lambda: B.rebuild(tfrag))
            # rebuild's own §5 contract: equals a fresh build of the live set
            trebuilt, _ = B.rebuild(tfrag)
            tref = bulk_build(cfg, K.make_keyset(keys[n_rm:], width),
                              vals[n_rm:], device=True)
            reb_parity = _equal(trebuilt, tref)
            for mode, t, ok in (("host", t_host, parity),
                                ("device", t_dev, parity),
                                ("rebuild", t_reb, reb_parity)):
                rows.append({
                    "dataset": ds, "n_keys": len(keys), "mode": mode,
                    "build_ms": round(t * 1e3, 2),
                    "Mkeys/s": round(len(keys) / t / 1e6, 3),
                    "parity": "ok" if ok else "MISMATCH",
                })
    return rows


BUILD_COLUMNS = ["dataset", "n_keys", "mode", "build_ms", "Mkeys/s",
                 "parity"]


def write_json(rows: List[Dict] = None, build_rows: List[Dict] = None,
               scan_rows: List[Dict] = None, shard_rows: List[Dict] = None,
               path: str = None) -> str:
    """Merge the given section(s) into ``BENCH_traverse.json`` — the perf
    trajectory anchor accumulates (``rows`` = traversal A/B, ``build_rows``
    = host-vs-device build, ``scan_rows`` = scan-engine A/B, ``shard_rows``
    = sharded-tree 1/2/4-shard A/B); suites never clobber each other."""
    if path is None:
        path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                            "BENCH_traverse.json")
    data = {"suite": "traverse"}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    if rows is not None:
        data["rows"] = rows
    if build_rows is not None:
        data["build_rows"] = build_rows
    if scan_rows is not None:
        data["scan_rows"] = scan_rows
    if shard_rows is not None:
        data["shard_rows"] = shard_rows
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")
    return path
