"""Jitted wrapper: full branch_level built on the feature_branch kernel.

Registered as the ``"pallas"`` backend in the traversal-engine registry
(``core.traverse``) — drop-in for core.branch.branch_level with identical
BranchStats accounting (and the same static ``collect_stats`` switch). The
gather / prefix-compare stages run in XLA, the feature-comparison hot loop
in Pallas (compiled by Mosaic on a TPU, interpreted on the CPU test
backend: ``kernels.interpret_mode``), and the suffix fallback shares
``core.branch.suffix_binary_search``: a while-loop bounded by the widest
surviving equal run, so levels where no lane needs the fallback (e.g.
single-child chain levels, knum <= 1 everywhere) skip the anchor-gather
compare rounds entirely instead of burning ``ns.bit_length()`` dead rounds.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.branch import (BranchStats, _first_diff_cmp,
                               suffix_binary_search)

from .. import interpret_mode
from .kernel import DEFAULT_TILE_B, auto_tile, feature_branch_kernel
from .ref import feature_branch_ref


def feature_branch(feats, qfeat, knum, pcmp, use_pallas: bool = True,
                   tile_b: int = None, collect_stats: bool = True):
    """Pad-to-tile wrapper around the kernel (or the jnp oracle).

    ``tile_b=None`` picks the largest power-of-two tile ≤ B (floor 8, cap
    ``DEFAULT_TILE_B``): a serving-sized batch is not padded to the
    throughput tile.
    """
    B = feats.shape[0]
    if not use_pallas:
        return feature_branch_ref(feats, qfeat, knum, pcmp)
    if tile_b is None:
        tile_b = auto_tile(B, DEFAULT_TILE_B)
    Bp = -(-B // tile_b) * tile_b
    if Bp != B:
        padw = [(0, Bp - B)] + [(0, 0)] * (feats.ndim - 1)
        feats = jnp.pad(feats, padw)
        qfeat = jnp.pad(qfeat, [(0, Bp - B), (0, 0)])
        knum = jnp.pad(knum, [(0, Bp - B), (0, 0)])
        pcmp = jnp.pad(pcmp, [(0, Bp - B), (0, 0)])
    outs = feature_branch_kernel(feats, qfeat, knum, pcmp, tile_b=tile_b,
                                 interpret=interpret_mode(),
                                 collect_stats=collect_stats)
    return tuple(o[:B] for o in outs)


def branch_level_pallas(level, key_bytes, key_lens, node_ids, qb, ql,
                        use_pallas: bool = True, collect_stats: bool = True):
    """Drop-in replacement for core.branch.branch_level using the kernel."""
    B = node_ids.shape[0]
    ns = level.features.shape[-1]
    fs = level.features.shape[-2]
    L = qb.shape[-1]
    lines_per_row = max(1, ns // 64)

    knum = level.knum[node_ids]
    plen = level.plen[node_ids]
    prefix = level.prefix[node_ids]
    feats = level.features[node_ids]

    pcmp = _first_diff_cmp(qb, prefix, plen)
    # query feature bytes following the per-node prefix
    qpos = plen[:, None] + jnp.arange(fs, dtype=jnp.int32)[None, :]
    qfeat = jnp.take_along_axis(qb, jnp.clip(qpos, 0, L - 1), axis=-1)
    qfeat = jnp.where(qpos < L, qfeat, 0).astype(jnp.uint8)

    outs = feature_branch(feats, qfeat, knum[:, None], pcmp[:, None],
                          use_pallas=use_pallas, collect_stats=collect_stats)
    idx1, resolved, run_lo, run_hi = outs[:4]
    idx = idx1[:, 0]
    resolved = resolved[:, 0].astype(bool)
    lo, hi = run_lo[:, 0], run_hi[:, 0]
    feat_rounds = outs[4][:, 0] if len(outs) > 4 else None

    # suffix binary search fallback (XLA: data-dependent gathers). The
    # kernel's `resolved` already folds in the prefix/trivial overrides, so
    # ~resolved is exactly the billed-fallback lane set of the jnp oracle.
    need_bs = ~resolved
    lo_b, key_cmp = suffix_binary_search(
        level.anchors, node_ids, key_bytes, key_lens, qb, ql, lo, hi,
        need_bs, ns, count_compares=collect_stats)
    bs_idx = jnp.clip(lo_b - 1, 0, jnp.maximum(knum - 1, 0))
    idx = jnp.where(need_bs, bs_idx, idx)

    child = level.children[node_ids, idx]
    if not collect_stats:
        return child, None
    trivial = knum <= 1
    nz = lambda x: jnp.where(trivial, 0, x).astype(jnp.int32)
    kw_lines = (ql + 63) // 64
    stats = BranchStats(
        feat_rounds=nz(feat_rounds),
        suffix_bs=nz(need_bs.astype(jnp.int32)),
        key_compares=nz(key_cmp),
        lines_touched=nz(1 + feat_rounds * lines_per_row
                         + key_cmp * (1 + kw_lines) + 1),
        sibling_hops=jnp.zeros((B,), jnp.int32),
    )
    return child, stats
