"""YCSB core workloads (paper Fig. 11 / Fig. 17 analogue).

LOAD (100% insert), A (50% read / 50% update), C (100% read),
E (95% scan / 5% insert) over the five datasets, for the FB+-tree and the
binary-search B+-tree baseline (same arrays — the paper's STX/B+-treeOLC
stand-in). Zipfian requests, skew 0.99 (YCSB default).
"""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from repro.core import batch_ops as B
from repro.core import keys as K
from repro.core.baseline import lookup_variant

from .common import (DATASETS, build_tree, make_dataset, make_engine, timed,
                     zipf_indices)

N_KEYS = 20_000
N_OPS = 40_960
BATCH = 4096
SKEW = 0.99


def run(datasets=DATASETS, n_keys=N_KEYS, n_ops=N_OPS, seed=11,
        backend="jnp") -> List[Dict]:
    engine = make_engine(backend)
    rows = []
    rng = np.random.default_rng(seed)
    for ds in datasets:
        keys, width = make_dataset(ds, n_keys)
        tree, ks = build_tree(keys, width)
        idx = zipf_indices(rng, len(keys), n_ops, SKEW)
        qb = jnp.asarray(ks.bytes[idx])
        ql = jnp.asarray(ks.lens[idx])
        row = {"dataset": ds}

        # ---- LOAD: bulk insert fresh keys batch-by-batch
        fresh, _ = make_dataset(ds, n_keys // 2, seed + 1)
        fresh = [k for k in fresh if k not in set(keys)][:BATCH * 2]
        fks = K.make_keyset(fresh, width)
        def load_fn(t=tree):
            out = t
            for off in range(0, len(fresh), BATCH):
                nb = jnp.asarray(fks.bytes[off:off + BATCH])
                nl = jnp.asarray(fks.lens[off:off + BATCH])
                out, _, _ = B.insert_batch(out, nb, nl,
                                           jnp.arange(nb.shape[0]),
                                           engine=engine)
            return out.arrays.leaf_occ
        t_load = timed(load_fn, warmup=1, iters=2)
        row["LOAD_Mops"] = round(len(fresh) / t_load / 1e6, 3)

        # ---- C: 100% read, fb vs binary baseline
        for var, label in (("feature+hash", "fb"), ("base", "btree")):
            def read_fn(v=var):
                outs = []
                for off in range(0, n_ops, BATCH):
                    f, val, st, ls = lookup_variant(
                        tree, qb[off:off + BATCH], ql[off:off + BATCH],
                        variant=v, engine=engine)
                    outs.append(val)
                return outs
            t = timed(read_fn)
            row[f"C_{label}_Mops"] = round(n_ops / t / 1e6, 3)

        # ---- A: 50/50 read/update
        upd_vals = jnp.arange(BATCH, dtype=jnp.int32)
        def a_fn():
            t2 = tree
            outs = []
            for off in range(0, n_ops, BATCH * 2):
                f, val, _, _ = lookup_variant(
                    tree, qb[off:off + BATCH], ql[off:off + BATCH],
                    variant="feature+hash", engine=engine)
                t2, _ = B.update_batch(t2, qb[off + BATCH:off + 2 * BATCH],
                                       ql[off + BATCH:off + 2 * BATCH],
                                       upd_vals, engine=engine)
                outs.append(val)
            return t2.arrays.leaf_val
        t_a = timed(a_fn)
        row["A_Mops"] = round(n_ops / t_a / 1e6, 3)

        # ---- E: 95% short scan (50 items) / 5% insert
        n_scan = 1024
        sb, sl = qb[:n_scan], ql[:n_scan]
        def e_fn():
            kid, val, em, _ = B.range_scan(tree, sb, sl, max_items=50,
                                           engine=engine)
            return val
        t_e = timed(e_fn)
        row["E_Mops"] = round(n_scan * 50 / t_e / 1e6, 3)  # items/s
        rows.append(row)
    return rows


COLUMNS = ["dataset", "LOAD_Mops", "A_Mops", "C_fb_Mops", "C_btree_Mops",
           "E_Mops"]
