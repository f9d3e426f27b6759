"""Engine-facing wrappers for the fused whole-descent kernel.

Registered as the ``"fused"`` descent backend in ``core.traverse``
(DESIGN.md §3): :func:`fused_traverse` matches the descent-backend
signature, :func:`fused_traverse_probe` is the fused traverse+probe entry
``core.batch_ops._traverse_probe`` collapses to — one kernel launch for
descent + sibling hop + hashtag leaf probe, with BranchStats/LeafStats
accounting bit-identical to the ``jnp`` oracle when ``collect_stats`` is on
and compiled out entirely when off.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax.numpy as jnp

from repro.core.branch import BranchStats
from repro.core.fbtree import FBTree
from repro.core.keys import fnv1a_tags
from repro.core.leaf import LeafStats

from .. import interpret_mode
from .kernel import descent_tile, fused_descent_kernel


def _run(tree: FBTree, qb, ql, sibling_check: bool, with_probe: bool,
         collect_stats: bool):
    a = tree.arrays
    s = a.stacked
    n_levels = len(a.levels)
    fs = s.features.shape[-2]
    ns = s.features.shape[-1]
    B = qb.shape[0]

    tile_b = descent_tile(B, ns)
    Bp = -(-B // tile_b) * tile_b
    qb_p, ql_p = qb, ql
    qtag_p = None
    if with_probe:
        qtag_p = fnv1a_tags(qb, ql)[:, None]
    if Bp != B:
        qb_p = jnp.pad(qb, [(0, Bp - B), (0, 0)])
        ql_p = jnp.pad(ql, [(0, Bp - B)])
        if with_probe:
            qtag_p = jnp.pad(qtag_p, [(0, Bp - B), (0, 0)])

    stacked_arrays = (s.knum, s.plen, s.prefix, s.features, s.children,
                      s.anchors)
    leaf_arrays = ()
    if sibling_check:
        leaf_arrays += (a.leaf_high[:, None], a.leaf_next[:, None])
    if with_probe:
        leaf_arrays += (a.leaf_tags, a.leaf_occ.astype(jnp.uint8),
                        a.leaf_keyid, a.leaf_val)

    outs = fused_descent_kernel(
        qb_p, ql_p[:, None], qtag_p, stacked_arrays, a.key_bytes,
        a.key_lens[:, None], leaf_arrays, tile_b=tile_b, n_levels=n_levels,
        fs=fs, ns=ns, sibling_check=sibling_check, with_probe=with_probe,
        collect_stats=collect_stats, interpret=interpret_mode())
    outs = [o[:B] for o in outs]

    it = iter(outs)
    leaf_ids = next(it)[:, 0]
    path_arr = next(it)
    path = [path_arr[:, l] for l in range(n_levels)]
    found = slot = val = None
    if with_probe:
        found = next(it)[:, 0].astype(bool)
        slot = next(it)[:, 0]
        val = next(it)[:, 0]
    bstats = lstats = None
    if collect_stats:
        fr, sb, kc, li, sh = (next(it)[:, 0] for _ in range(5))
        bstats = BranchStats(feat_rounds=fr, suffix_bs=sb, key_compares=kc,
                             lines_touched=li, sibling_hops=sh)
        if with_probe:
            tc = next(it)[:, 0]
            kw_lines = (ql + 63) // 64
            lstats = LeafStats(
                tag_candidates=tc,
                lines_touched=(max(1, ns // 64) + 1 + tc * (1 + kw_lines)
                               ).astype(jnp.int32))
    return leaf_ids, path, found, slot, val, bstats, lstats


def fused_traverse(tree: FBTree, qb, ql, sibling_check: bool = True,
                   collect_stats: bool = True,
                   ) -> Tuple[jnp.ndarray, List[jnp.ndarray],
                              Optional[BranchStats]]:
    """Descent-backend entry: whole root→leaf descent in one kernel launch.

    Returns ``(leaf_ids, path, stats | None)`` — the
    ``TraversalEngine.traverse`` contract.
    """
    leaf_ids, path, _, _, _, bstats, _ = _run(
        tree, qb, ql, sibling_check, with_probe=False,
        collect_stats=collect_stats)
    return leaf_ids, path, bstats


def fused_traverse_probe(tree: FBTree, qb, ql, sibling_check: bool = True,
                         collect_stats: bool = True):
    """Fused traverse+probe: descent, sibling hop, and the hashtag leaf
    probe (full-key verify included) in ONE launch. Returns
    ``(leaf_ids, path, found, slot, val, bstats | None, lstats | None)`` —
    the ``core.batch_ops._traverse_probe`` contract.
    """
    return _run(tree, qb, ql, sibling_check, with_probe=True,
                collect_stats=collect_stats)
