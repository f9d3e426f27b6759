"""Leaf-node operations: hashtag probe (paper Fig. 6 lines 30-42) + slot ops."""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .fbtree import FBTree, leaf_fold
from .keys import fnv1a_tags

__all__ = ["LeafStats", "probe", "verify_candidates", "find_free_slots",
           "leaf_rows", "leaf_at", "leaf_set", "leaf_counts"]


# --------------------------------------------------------------------------
# folded leaf slot arrays (DESIGN.md §1): leaf i is row i // f, lanes
# (i % f) * ns .. + ns of a [LC // f, f * ns] array, f = leaf_fold(ns)
# --------------------------------------------------------------------------

def leaf_rows(arr, leaf_ids, ns: int):
    """Slot rows ``[..., ns]`` of leaves ``leaf_ids`` from a folded array:
    gather the stored row, keep the leaf's lane group."""
    f = leaf_fold(ns)
    if f == 1:
        return arr[leaf_ids]
    rows = arr[leaf_ids // f]                     # [..., f * ns]
    group = (leaf_ids % f)[..., None]
    out = rows[..., :ns]
    for g in range(1, f):
        out = jnp.where(group == g, rows[..., g * ns:(g + 1) * ns], out)
    return out


def leaf_at(arr, leaf_ids, slot, ns: int):
    """Slot ``slot`` of leaves ``leaf_ids`` from a folded array."""
    f = leaf_fold(ns)
    if f == 1:
        return arr[leaf_ids, slot]
    return arr[leaf_ids // f, (leaf_ids % f) * ns + slot]


def leaf_set(arr, leaf_ids, slot, v, ns: int, where=None):
    """Scatter ``v`` into slot ``slot`` of leaves ``leaf_ids`` of a folded
    array; ``slot=None`` writes whole leaves (``v`` is ``[..., ns]``).
    Lanes where ``where`` is False write back the value they address, so
    masked lanes aimed at the scratch leaf leave it as it was. Only the
    addressed leaves' lanes change, never a neighbour in the same row."""
    if where is not None:
        if slot is None:
            v = jnp.where(where[..., None], v, leaf_rows(arr, leaf_ids, ns))
        else:
            v = jnp.where(where, v, leaf_at(arr, leaf_ids, slot, ns))
    f = leaf_fold(ns)
    if slot is None:
        if f == 1:
            return arr.at[leaf_ids].set(v)
        leaf_ids = leaf_ids[..., None]
        slot = jnp.arange(ns, dtype=jnp.int32)
    if f == 1:
        return arr.at[leaf_ids, slot].set(v)
    return arr.at[leaf_ids // f, (leaf_ids % f) * ns + slot].set(v)


def leaf_counts(occ, ns: int):
    """Occupied slots per leaf, ``int32 [LC]``, from the folded ``occ``."""
    f = leaf_fold(ns)
    if f == 1:
        return occ.sum(-1).astype(jnp.int32)
    per_group = [occ[:, g * ns:(g + 1) * ns].sum(-1) for g in range(f)]
    return jnp.stack(per_group, axis=1).reshape(-1).astype(jnp.int32)


class LeafStats(NamedTuple):
    tag_candidates: jnp.ndarray  # int32 [B] slots passing the hashtag filter
    lines_touched: jnp.ndarray   # int32 [B]

    @staticmethod
    def zeros(b: int):
        z = jnp.zeros((b,), jnp.int32)
        return LeafStats(z, z)


def verify_candidates(a, cand: jnp.ndarray, kid: jnp.ndarray,
                      qb: jnp.ndarray, ql: jnp.ndarray,
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact-match verification over the hashtag candidate mask.

    Checks candidates one at a time in slot order (a ``lax.while_loop``
    whose trip count is the deepest candidate rank any still-unmatched lane
    needs — typically 1): per round one ``[B, L]`` key gather and compare,
    instead of materializing all ``[B, ns, L]`` leaf key bytes. This is the
    paper's line 36-38 claim executed literally — key cache lines are
    touched *only* for candidates — and it is observationally identical to
    the all-at-once verify: ``found``/``slot`` match bit for bit (first
    matching candidate wins in both formulations; slot 0 when none).
    """
    B, ns = cand.shape
    crank = jnp.cumsum(cand.astype(jnp.int32), axis=-1) - 1  # cand rank/slot
    n_cand = cand.sum(-1).astype(jnp.int32)
    lane = jnp.arange(ns, dtype=jnp.int32)[None, :]

    def cond(c):
        checked, found, _ = c
        return ((~found) & (checked < n_cand)).any()

    def body(c):
        checked, found, slot = c
        active = (~found) & (checked < n_cand)
        is_k = cand & (crank == checked[:, None])
        s = jnp.min(jnp.where(is_k, lane, ns), axis=-1)
        s = jnp.where(active, jnp.minimum(s, ns - 1), 0)
        kd = jnp.maximum(kid[jnp.arange(B), s], 0)
        akb = a.key_bytes[kd]                               # [B, L]
        akl = a.key_lens[kd]
        eqk = (akb == qb).all(-1) & (akl == ql) & active
        slot = jnp.where(eqk, s, slot)
        return checked + active.astype(jnp.int32), found | eqk, slot

    init = (jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
            jnp.zeros((B,), jnp.int32))
    _, found, slot = jax.lax.while_loop(cond, body, init)
    return found, slot


def probe(tree: FBTree, leaf_ids: jnp.ndarray, qb: jnp.ndarray, ql: jnp.ndarray,
          collect_stats: bool = True,
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, LeafStats]:
    """Find each query's slot in its leaf.

    Returns (found [B]bool, slot [B]int32, val [B], stats). The hashtag filter
    narrows candidates exactly as the paper's ``compare_equal(tags, tag)``;
    verification compares full key bytes (lines 36-38) candidate-by-candidate
    (:func:`verify_candidates` — key lines touched only for candidates, both
    here and in the Pallas wrapper ``kernels/leaf_probe``).
    ``collect_stats=False`` skips the counter reductions and returns
    ``stats=None`` (the candidate mask itself is load-bearing and stays).
    """
    a = tree.arrays
    ns = tree.config.ns
    qtag = fnv1a_tags(qb, ql)
    tags = leaf_rows(a.leaf_tags_f, leaf_ids, ns)     # [B, ns]
    occ = leaf_rows(a.leaf_occ_f, leaf_ids, ns)
    cand = (tags == qtag[:, None]) & occ
    kid = leaf_rows(a.leaf_keyid_f, leaf_ids, ns)     # [B, ns]
    found, slot = verify_candidates(a, cand, kid, qb, ql)
    val = jnp.take_along_axis(leaf_rows(a.leaf_val_f, leaf_ids, ns),
                              slot[:, None], axis=-1)[:, 0]
    val = jnp.where(found, val, 0)
    if not collect_stats:
        return found, slot, val, None
    n_cand = cand.sum(-1).astype(jnp.int32)
    kw_lines = (ql + 63) // 64
    stats = LeafStats(
        tag_candidates=n_cand,
        # modeled: control+tags row (ns bytes -> ns/64 lines) + bitmap word +
        # per-candidate kv pointer line + key line(s)
        lines_touched=(max(1, ns // 64) + 1 + n_cand * (1 + kw_lines)).astype(jnp.int32),
    )
    return found, slot, val, stats


def find_free_slots(occ_row: jnp.ndarray, count: jnp.ndarray) -> jnp.ndarray:
    """Rank free slots of a leaf row: returns int32 [ns] where entry r is the
    slot index of the r-th free slot (ns if fewer free slots exist)."""
    ns = occ_row.shape[-1]
    free = ~occ_row
    order = jnp.argsort(jnp.where(free, jnp.arange(ns), ns + jnp.arange(ns)))
    nfree = free.sum()
    rank_valid = jnp.arange(ns) < jnp.minimum(nfree, count)
    return jnp.where(rank_valid, order, ns)
