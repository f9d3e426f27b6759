"""Baseline B+-tree branch/probe variants for the paper's factor analysis.

Fig. 12(a) enables optimizations one by one starting from a typical B+-tree:

  base       binary search over anchors in inner nodes + binary search in
             sorted leaves (STX-B+-tree / B+-treeOLC behaviour)
  +prefix    compare the common prefix once, then binary search on suffixes
  +feature2  feature comparison with fs=2 (build the tree with fs=2)
  +feature4  feature comparison with fs=4 (the default engine)
  +hashtag   hashtag probe in leaves instead of leaf binary search

All variants run over the same FBTree arrays so throughput and the modeled
hardware counters (key compares, 64B lines touched) are directly comparable.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .branch import BranchStats
from .fbtree import FBTree, Level
from .keys import compare_padded
from .leaf import LeafStats, probe
from .traverse import TraversalEngine, resolve_engine

__all__ = ["branch_level_binary", "probe_leaf_binary", "lookup_variant",
           "VARIANTS"]

VARIANTS = ("base", "prefix", "feature", "feature+hash")


def _full_cmp(key_bytes, key_lens, aid, qb, ql, skip: jnp.ndarray = None):
    aid_safe = jnp.maximum(aid, 0)
    akb = key_bytes[aid_safe]
    akl = key_lens[aid_safe]
    return compare_padded(akb, akl, qb, ql)  # anchor vs query


def branch_level_binary(level: Level, key_bytes, key_lens, node_ids, qb, ql,
                        use_prefix: bool, collect_stats: bool = True,
                        ) -> Tuple[jnp.ndarray, Optional[BranchStats]]:
    """Classic binary-search branch (optionally with +prefix suffix skip)."""
    B = node_ids.shape[0]
    ns = level.features.shape[-1]
    knum = level.knum[node_ids]
    plen = level.plen[node_ids]
    anchors = level.anchors[node_ids]

    if use_prefix:
        # one prefix compare, counted as touching the prefix line(s)
        prefix = level.prefix[node_ids]
        L = qb.shape[-1]
        pos = jnp.arange(L, dtype=jnp.int32)
        m = pos[None, :] < plen[:, None]
        diff = (qb.astype(jnp.int32) - prefix.astype(jnp.int32)) * m
        nz = diff != 0
        anynz = nz.any(-1)
        fi = jnp.argmax(nz, axis=-1)
        first = jnp.take_along_axis(diff, fi[:, None], axis=-1)[:, 0]
        pcmp = jnp.where(anynz, jnp.sign(first), 0).astype(jnp.int32)
    else:
        pcmp = jnp.zeros((B,), jnp.int32)

    lo = jnp.zeros((B,), jnp.int32)
    hi = knum
    key_cmp = jnp.zeros((B,), jnp.int32)
    n_steps = max(1, ns.bit_length())
    for _ in range(n_steps):
        active = lo < hi
        mid = jnp.clip((lo + hi) // 2, 0, ns - 1)
        aid = jnp.take_along_axis(anchors, mid[:, None], axis=-1)[:, 0]
        c = _full_cmp(key_bytes, key_lens, aid, qb, ql)
        go_right = c <= 0
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        if collect_stats:
            key_cmp = key_cmp + active.astype(jnp.int32)
    idx = jnp.clip(lo - 1, 0, jnp.maximum(knum - 1, 0))
    idx = jnp.where(pcmp < 0, 0, idx)
    idx = jnp.where(pcmp > 0, jnp.maximum(knum - 1, 0), idx)
    trivial = knum <= 1
    idx = jnp.where(trivial, 0, idx)
    child = jnp.take_along_axis(level.children[node_ids], idx[:, None], axis=-1)[:, 0]

    if not collect_stats:
        return child, None
    # modeled lines: control line + per compare (anchor-pointer line + key
    # line(s)); +prefix adds the prefix line but shortens the compared bytes.
    nzs = lambda x: jnp.where(trivial, 0, x).astype(jnp.int32)
    cmp_bytes = jnp.maximum(ql - (plen if use_prefix else 0), 1)
    kw_lines = (cmp_bytes + 63) // 64
    lines = 1 + key_cmp * (1 + kw_lines) + (1 if use_prefix else 0) + 1
    stats = BranchStats(
        feat_rounds=jnp.zeros((B,), jnp.int32),
        suffix_bs=nzs(jnp.ones((B,), jnp.int32)),
        key_compares=nzs(key_cmp),
        lines_touched=nzs(lines),
        sibling_hops=jnp.zeros((B,), jnp.int32),
    )
    return child, stats


def probe_leaf_binary(tree: FBTree, leaf_ids, qb, ql):
    """Sorted-leaf binary search (models STX; requires bulk-built leaves)."""
    a = tree.arrays
    ns = a.leaf_tags.shape[-1]
    B = leaf_ids.shape[0]
    occ = a.leaf_occ[leaf_ids]
    kid = a.leaf_keyid[leaf_ids]
    nocc = occ.sum(-1).astype(jnp.int32)
    lo = jnp.zeros((B,), jnp.int32)
    hi = nocc
    key_cmp = jnp.zeros((B,), jnp.int32)
    for _ in range(max(1, ns.bit_length())):
        active = lo < hi
        mid = jnp.clip((lo + hi) // 2, 0, ns - 1)
        aid = jnp.take_along_axis(kid, mid[:, None], axis=-1)[:, 0]
        c = _full_cmp(a.key_bytes, a.key_lens, aid, qb, ql)
        go_right = c < 0
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        key_cmp = key_cmp + active.astype(jnp.int32)
    slot = jnp.clip(lo, 0, ns - 1)
    aid = jnp.take_along_axis(kid, slot[:, None], axis=-1)[:, 0]
    c = _full_cmp(a.key_bytes, a.key_lens, aid, qb, ql)
    in_range = lo < nocc
    found = in_range & (c == 0)
    val = jnp.take_along_axis(a.leaf_val[leaf_ids], slot[:, None], axis=-1)[:, 0]
    val = jnp.where(found, val, 0)
    kw_lines = (ql + 63) // 64
    stats = LeafStats(
        tag_candidates=jnp.zeros((B,), jnp.int32),
        lines_touched=(1 + (key_cmp + 1) * (1 + kw_lines)).astype(jnp.int32),
    )
    return found, slot, val, stats


@functools.partial(jax.jit, static_argnames=("variant", "engine"))
def lookup_variant(tree: FBTree, qb, ql, variant: str = "feature+hash",
                   engine: Optional[TraversalEngine] = None):
    """Point lookup under a factor-analysis variant. Returns (found, val, stats).

    All variants descend through the traversal engine: the binary-search
    baselines are the registered ``binary`` / ``binary+prefix`` backends,
    and the feature variants use ``engine``'s backend (``jnp`` or
    ``pallas``).
    """
    assert variant in VARIANTS, variant
    eng = resolve_engine(engine)
    if variant in ("base", "prefix"):
        eng = TraversalEngine(
            backend="binary" if variant == "base" else "binary+prefix",
            collect_stats=eng.collect_stats)
    node_ids, _, stats = eng.traverse(tree, qb, ql, sibling_check=True)
    if variant == "feature+hash":
        found, slot, val, ls = probe(tree, node_ids, qb, ql,
                                     collect_stats=eng.collect_stats)
    else:
        found, slot, val, ls = probe_leaf_binary(tree, node_ids, qb, ql)
    if ls is None:
        ls = LeafStats.zeros(node_ids.shape[0])
    return found, val, stats._replace(
        lines_touched=stats.lines_touched + ls.lines_touched), ls
