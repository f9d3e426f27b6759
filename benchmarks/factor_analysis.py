"""Structural-optimization factor analysis (paper Fig. 12a).

Enable optimizations one at a time on the SAME key sets:
  base -> +prefix -> +feature2 -> +feature4 -> +hashtag
reporting throughput and machine-independent counters (full-key compares
and modeled 64B cache lines per lookup).
"""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from repro.core import keys as K
from repro.core.baseline import lookup_variant
from repro.core.fbtree import TreeConfig, bulk_build

from .common import build_tree, make_dataset, make_engine, timed, zipf_indices

STEPS = ("base", "+prefix", "+feature2", "+feature4", "+hashtag")


def run(datasets=("3-gram", "ycsb", "twitter", "url"), n_keys=20_000,
        n_ops=16_384, seed=13, backend="jnp") -> List[Dict]:
    engine = make_engine(backend)
    rows = []
    rng = np.random.default_rng(seed)
    for ds in datasets:
        keys, width = make_dataset(ds, n_keys)
        ks = K.make_keyset(keys, width)
        idx = zipf_indices(rng, len(keys), n_ops, 0.99)
        qb, ql = jnp.asarray(ks.bytes[idx]), jnp.asarray(ks.lens[idx])
        trees = {}
        for fs in (2, 4):
            cfg = TreeConfig.plan(max_keys=2 * n_keys, key_width=width, fs=fs)
            trees[fs] = bulk_build(cfg, ks, np.arange(n_keys, dtype=np.int32))
        plan = [("base", trees[4], "base"),
                ("+prefix", trees[4], "prefix"),
                ("+feature2", trees[2], "feature"),
                ("+feature4", trees[4], "feature"),
                ("+hashtag", trees[4], "feature+hash")]
        for label, tree, variant in plan:
            def fn():
                outs = []
                for off in range(0, n_ops, 4096):
                    f, v, st, ls = lookup_variant(tree, qb[off:off + 4096],
                                                  ql[off:off + 4096],
                                                  variant=variant,
                                                  engine=engine)
                    outs.append(v)
                return outs
            t = timed(fn)
            _, _, st, ls = lookup_variant(tree, qb[:4096], ql[:4096],
                                          variant=variant, engine=engine)
            rows.append({
                "dataset": ds, "step": label, "backend": backend,
                "Mops": round(n_ops / t / 1e6, 3),
                "key_cmp/op": round(float(st.key_compares.mean()), 2),
                "lines/op": round(float(st.lines_touched.mean()), 1),
                "suffix_bs/op": round(float(st.suffix_bs.mean()), 3),
            })
    return rows


COLUMNS = ["dataset", "step", "backend", "Mops", "key_cmp/op", "lines/op",
           "suffix_bs/op"]
