"""Engine-facing wrapper for the fused range-scan kernel.

Registered as the ``"fused"`` scan backend in ``core.traverse``
(DESIGN.md §6): :func:`fused_range_scan` matches the ScanBackend signature,
so ``core.batch_ops.range_scan`` collapses the whole scan — descent, sibling
hop, and the leaf-chain walk with lazy-rearrangement sorting — into one
kernel launch whenever the engine's backend is ``"fused"``. Emitted
``(key_id, value)`` pairs are bit-identical to the jnp chain-walk reference
(the scan parity suite pins this); the ``rearranged`` counter is compiled
out entirely when ``collect_stats`` is off.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.core.fbtree import FBTree

from .. import interpret_mode
from .kernel import descent_tile, fused_scan_kernel


def fused_range_scan(tree: FBTree, qb, ql, max_items: int = 64,
                     collect_stats: bool = True,
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                jnp.ndarray]:
    """Scan-backend entry: whole range scan in one kernel launch.

    Returns ``(out_kid [B, max_items], out_val [B, max_items], emitted [B],
    rearranged [B])`` — the ``core.batch_ops.range_scan`` contract.
    ``rearranged`` is all-zero (and never traced in-kernel) when
    ``collect_stats`` is off.
    """
    a = tree.arrays
    s = a.stacked
    n_levels = len(a.levels)
    fs = s.features.shape[-2]
    ns = s.features.shape[-1]
    B, L = qb.shape

    tile_b = descent_tile(B, ns)
    Bp = -(-B // tile_b) * tile_b
    qb_p, ql_p = qb, ql
    if Bp != B:
        # pad with +inf-like queries (0xff.., full length): padded lanes
        # land on the last leaf, emit nothing, and retire on hop 0
        qb_p = jnp.concatenate(
            [qb, jnp.full((Bp - B, L), 0xFF, jnp.uint8)], axis=0)
        ql_p = jnp.concatenate(
            [ql, jnp.full((Bp - B,), L, ql.dtype)], axis=0)

    stacked_arrays = (s.knum, s.plen, s.prefix, s.features, s.children,
                      s.anchors)
    leaf_arrays = (a.leaf_high[:, None], a.leaf_next[:, None], a.leaf_keyid,
                   a.leaf_val, a.leaf_occ.astype(jnp.uint8),
                   a.leaf_ordered.astype(jnp.uint8)[:, None])

    outs = fused_scan_kernel(
        qb_p, ql_p[:, None], stacked_arrays, a.key_bytes,
        a.key_lens[:, None], leaf_arrays, tile_b=tile_b, n_levels=n_levels,
        fs=fs, ns=ns, max_items=max_items, collect_stats=collect_stats,
        interpret=interpret_mode())
    outs = [o[:B] for o in outs]
    out_kid, out_val = outs[0], outs[1]
    emitted = outs[2][:, 0]
    rearranged = (outs[3][:, 0] if collect_stats
                  else jnp.zeros((B,), jnp.int32))
    return out_kid, out_val, emitted, rearranged
