"""Jitted wrapper: full leaf probe (tag filter kernel + exact verify in XLA)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.keys import fnv1a_tags
from repro.core.leaf import LeafStats, leaf_rows, verify_candidates

from .. import interpret_mode
from .kernel import DEFAULT_TILE_B, leaf_probe_kernel
from ..feature_branch.kernel import auto_tile


def leaf_probe(tags, occ, qtag, use_pallas: bool = True, tile_b: int = None):
    """``tile_b=None`` picks the largest power-of-two tile ≤ B (floor 8,
    cap ``DEFAULT_TILE_B``) so serving-sized batches stay pad-free."""
    B = tags.shape[0]
    if not use_pallas:
        from .ref import leaf_probe_ref
        return leaf_probe_ref(tags, occ, qtag)
    if tile_b is None:
        tile_b = auto_tile(B, DEFAULT_TILE_B)
    Bp = -(-B // tile_b) * tile_b
    if Bp != B:
        tags = jnp.pad(tags, [(0, Bp - B), (0, 0)])
        occ = jnp.pad(occ, [(0, Bp - B), (0, 0)])
        qtag = jnp.pad(qtag, [(0, Bp - B), (0, 0)], constant_values=1)
    outs = leaf_probe_kernel(tags, occ.astype(jnp.uint8), qtag,
                             tile_b=tile_b, interpret=interpret_mode())
    return tuple(o[:B] for o in outs)


def probe_pallas(tree, leaf_ids, qb, ql, use_pallas: bool = True,
                 collect_stats: bool = True):
    """Drop-in replacement for core.leaf.probe using the kernel for the
    hashtag filter; exact verification gathers only candidate slots."""
    a = tree.arrays
    ns = tree.config.ns
    qtag = fnv1a_tags(qb, ql)
    tags = leaf_rows(a.leaf_tags_f, leaf_ids, ns)
    occ = leaf_rows(a.leaf_occ_f, leaf_ids, ns)
    cand_u8, first, count = leaf_probe(tags, occ, qtag[:, None],
                                       use_pallas=use_pallas)
    cand = cand_u8 != 0
    kid = leaf_rows(a.leaf_keyid_f, leaf_ids, ns)
    found, slot = verify_candidates(a, cand, kid, qb, ql)
    val = jnp.take_along_axis(leaf_rows(a.leaf_val_f, leaf_ids, ns),
                              slot[:, None], axis=-1)[:, 0]
    val = jnp.where(found, val, 0)
    if not collect_stats:
        return found, slot, val, None
    n_cand = count[:, 0]
    kw_lines = (ql + 63) // 64
    stats = LeafStats(
        tag_candidates=n_cand,
        lines_touched=(max(1, ns // 64) + 1 + n_cand * (1 + kw_lines)
                       ).astype(jnp.int32),
    )
    return found, slot, val, stats
