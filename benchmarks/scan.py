"""Range-scan benchmark (YCSB-E side of paper Fig. 17).

Per dataset, times the jnp chain walk (DESIGN.md §6: engine descent +
early-exit ``while_loop`` + lazy-rearrangement cond) on the same trees and
query streams.

It is measured on an all-ordered tree (``scan_Mitems`` — the
lazy-rearrangement fast path, no per-hop sorting) and on a tree whose
leaves were dirtied by in-place inserts (``dirty_Mitems`` — the sort cond
fires). ``alwayssort_Mitems`` is the pre-scan-engine baseline (the old
``range_scan`` sorted every visited leaf on every hop; ``force_sort=True``
reproduces it bit-identically), so ``speedup_vs_alwayssort`` is the win the
ordered fast path carries into the anchor. The trie-pointer-chasing model
(each successor found by a fresh root descent) stays for paper context.

Every row cross-checks the stats-on and stats-free scans and the
always-sort baseline for bit-identical emissions before timing — a scan
regression fails the suite (and CI, via ``--smoke``) rather than reporting
wrong throughput.
Rows land in ``BENCH_traverse.json`` under ``scan_rows``.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batch_ops as B
from repro.core import keys as K
from repro.core.traverse import TraversalEngine

from .common import build_tree, make_dataset, timed

def _dirty_tree(tree, ks, rng, n_extra):
    """In-place-insert siblings of existing keys so leaves ACROSS the
    scanned range drop ``leaf_ordered`` (the §4.5 lazy-rearrangement
    scenario). Perturbing the last byte of a sampled key keeps the new key
    inside the same (populated) leaf — inserting unrelated random keys
    would funnel into the range's edge leaves and split into *ordered*
    chunks, leaving the scan path clean. Returns the dirtied tree."""
    seen = {bytes(ks.bytes[i][:ks.lens[i]].tobytes()) for i in range(ks.n)}
    extra = []
    for i in rng.permutation(ks.n):
        if len(extra) >= n_extra:
            break
        b, ln = ks.bytes[i].copy(), int(ks.lens[i])
        b[ln - 1] ^= 0xA5
        cand = bytes(b[:ln].tobytes())
        if cand not in seen:
            seen.add(cand)
            extra.append(cand)
    eks = K.make_keyset(extra, ks.bytes.shape[1])
    tree, _, _ = B.insert_batch(tree, eks.bytes, eks.lens,
                                np.arange(len(extra), dtype=np.int32)
                                + (1 << 20))
    n_dirty = int((~np.asarray(tree.arrays.leaf_ordered)
                   [:int(tree.arrays.leaf_count)]).sum())
    assert n_dirty > 0, "dirtying produced no unordered leaves"
    return tree


def run(datasets=("rand-int", "ycsb", "url"), n_keys=20_000, n_scans=512,
        scan_len=100, seed=31, smoke: bool = False) -> List[Dict]:
    if smoke:
        datasets = ("ycsb",)
        n_keys, n_scans, scan_len = 600, 128, 24
    rows = []
    rng = np.random.default_rng(seed)
    for ds in datasets:
        keys, width = make_dataset(ds, n_keys)
        tree, ks = build_tree(keys, width)
        t_dirty = _dirty_tree(tree, ks, rng, max(16, n_keys // 16))
        idx = rng.integers(0, n_keys, size=n_scans)
        qb, ql = jnp.asarray(ks.bytes[idx]), jnp.asarray(ks.lens[idx])

        # ---- parity gate: the scan and the always-sort baseline emit
        # bit-identical pairs on the ordered AND the dirtied tree
        ref = {}
        # ONE compiled always-sort baseline serves both the parity gate and
        # the timing below (stats-off: kid/val/emitted are bit-identical
        # either way, and timing runs the serving configuration)
        slow_ref = jax.jit(lambda t: B._range_scan_jnp(
            t, qb, ql, scan_len, TraversalEngine("jnp", collect_stats=False),
            force_sort=True))
        for label, t in (("ordered", tree), ("dirty", t_dirty)):
            ref[label] = [np.asarray(x) for x in B.range_scan(
                t, qb, ql, max_items=scan_len, engine=TraversalEngine("jnp"))]
            slow = slow_ref(t)
            for a, b in zip(ref[label][:3], slow[:3]):
                assert (a == np.asarray(b)).all(), \
                    f"{ds}/{label}: always-sort baseline diverges"

        # pointer-chasing model: each successor found by a fresh root
        # descent (what a trie iterator without leaf links pays)
        def chase_fn():
            out = []
            for _ in range(4):      # sample: 4 hops via full descents
                v, _ = B.lookup_batch(tree, qb, ql)
                out.append(v)
            return out
        t_chase = timed(chase_fn, warmup=1, iters=1 if smoke else 3) \
            * (scan_len / 4)

        # throughput runs stats-free (the serving configuration); parity
        # was pinned above with stats on
        eng = TraversalEngine("jnp", collect_stats=False)
        for label, t in (("ordered", tree), ("dirty", t_dirty)):
            got = B.range_scan(t, qb, ql, max_items=scan_len, engine=eng)
            for a, b, nm in zip(ref[label][:3], got[:3],
                                ("kid", "val", "emitted")):
                assert (a == np.asarray(b)).all(), \
                    f"{ds}/{label}: stats-free scan diverges on {nm}"

        def scan_fn(t):
            return B.range_scan(t, qb, ql, max_items=scan_len,
                                engine=eng)[1]
        t_ord = timed(lambda: scan_fn(tree), warmup=1,
                      iters=1 if smoke else 5)
        t_dirt = timed(lambda: scan_fn(t_dirty), warmup=1,
                       iters=1 if smoke else 5)
        # the pre-engine baseline: every visited leaf re-sorted on every
        # hop (bit-identical outputs, checked above; reuses the parity
        # gate's compiled slow_ref)
        t_slow = timed(lambda: slow_ref(tree)[1], warmup=1,
                       iters=1 if smoke else 5)
        rows.append({
            "dataset": ds, "n_keys": n_keys, "n_scans": n_scans,
            "scan_len": scan_len, "backend": "jnp",
            "scan_Mitems": round(n_scans * scan_len / t_ord / 1e6, 3),
            "dirty_Mitems": round(n_scans * scan_len / t_dirt / 1e6, 3),
            "alwayssort_Mitems": round(
                n_scans * scan_len / t_slow / 1e6, 3),
            "speedup_vs_alwayssort": round(t_slow / t_ord, 2),
            "chase_model_Mitems": round(
                n_scans * scan_len / t_chase / 1e6, 3),
            "parity": "ok",
        })
    return rows


COLUMNS = ["dataset", "n_keys", "n_scans", "scan_len", "backend",
           "scan_Mitems", "dirty_Mitems", "alwayssort_Mitems",
           "speedup_vs_alwayssort", "chase_model_Mitems", "parity"]
