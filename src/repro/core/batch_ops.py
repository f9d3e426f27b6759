"""Bulk-synchronous batched tree operations with latch-free-update semantics.

The paper's latch-free update (§4.4) shrinks the critical section to a single
CAS install; reads and unrelated updates never block. The TPU-native analogue
(DESIGN.md §2): operations are batched, everything except the final install
(traversal, hashtag probing, validation) runs data-parallel, and the only
serialized step is one scatter whose conflicts are resolved by a
*deterministic reduction* — last-writer-wins by per-op sequence number,
mirroring "updates only contend on the same key-value pairs".

Inserts use the link-technique-equivalent bulk split: overflowing leaves are
repacked into sorted chunks; the first chunk stays at the original node id so
parent child pointers stay valid (exactly the paper's "transfer the greater
half into the new node n'"), new anchors propagate bottom-up, and versions are
bumped for insert/remove but *not* for update (§4.2, Fig. 7).

Every tree array carries one trailing scratch row (index ``shape[0]-1``) that
masked scatters dump into; watermarks never allocate it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs

from .branch import BranchStats
from .fbtree import (BIG, EMPTY, FBTree, Level, TreeArrays, _device_build_jit,
                     chunk_of_pos, chunk_start, recompute_inner_meta,
                     sort_pool)
from .keys import compare_padded, fnv1a_tags, pack_words_j
from .leaf import leaf_counts, leaf_rows, leaf_set, probe
from .traverse import TraversalEngine, resolve_engine

__all__ = [
    "OpReport", "BuildReport", "lookup_batch", "update_batch", "insert_batch",
    "remove_batch", "range_scan", "rebuild", "dedupe_last_wins",
    "traverse_path", "traverse_probe", "gather_live_sorted",
    "build_from_snapshot",
]


class OpReport(NamedTuple):
    found: jnp.ndarray          # bool [B]
    conflicts: jnp.ndarray      # int32 scalar — ops superseded inside batch
    splits: jnp.ndarray         # int32 scalar — leaves split
    error: jnp.ndarray          # bool scalar — capacity violated
    feat_rounds: jnp.ndarray    # int32 [B]
    suffix_bs: jnp.ndarray      # int32 [B]
    key_compares: jnp.ndarray   # int32 [B]
    lines_touched: jnp.ndarray  # int32 [B]
    tag_candidates: jnp.ndarray  # int32 [B]


def _report(found, bstats: BranchStats, lstats=None, conflicts=0, splits=0,
            error=False):
    """``bstats``/``lstats`` may be ``None`` (stats-free engines,
    DESIGN.md §3): counters come back all-zero, ``found`` stays exact."""
    b = found.shape[0]
    z = jnp.zeros((b,), jnp.int32)
    if bstats is None:
        bstats = BranchStats.zeros(b)
    return OpReport(
        found=found,
        conflicts=jnp.asarray(conflicts, jnp.int32),
        splits=jnp.asarray(splits, jnp.int32),
        error=jnp.asarray(error, bool),
        feat_rounds=bstats.feat_rounds,
        suffix_bs=bstats.suffix_bs,
        key_compares=bstats.key_compares,
        lines_touched=bstats.lines_touched + (lstats.lines_touched if lstats else z),
        tag_candidates=(lstats.tag_candidates if lstats else z),
    )


@functools.partial(jax.jit, static_argnames=("sibling_check", "engine"))
def traverse_path(tree: FBTree, qb, ql, sibling_check: bool = True,
                  engine: Optional[TraversalEngine] = None):
    """Root-to-leaf traversal recording the node id at every level.

    Delegates to the traversal engine (backend selection); kept as
    the stable call-site API for ops and benchmarks. Jitted (engine is
    static), so benchmarks can time the bare descent without probe work.
    """
    return resolve_engine(engine).traverse(tree, qb, ql,
                                           sibling_check=sibling_check)


def _traverse_probe(tree: FBTree, qb, ql, engine, sibling_check=True):
    """The shared descend+probe pipeline every point op runs: one engine
    descent, one hashtag leaf probe. Returns
    (leaf_ids, path, found, slot, val, branch_stats, leaf_stats).

    Stats may be ``None`` under a stats-free engine — ``_report``
    zero-fills.

    The probe's ops carry the named scope ``leaf_probe`` (the descent's
    carry ``descent``, see ``TraversalEngine.traverse``): metadata only,
    so a device trace can split the program's time between the two.
    """
    eng = resolve_engine(engine)
    leaf_ids, path, bstats = eng.traverse(
        tree, qb, ql, sibling_check=sibling_check)
    with jax.named_scope("leaf_probe"):
        found, slot, val, lstats = probe(tree, leaf_ids, qb, ql,
                                         collect_stats=eng.collect_stats)
    return leaf_ids, path, found, slot, val, bstats, lstats


@functools.partial(jax.jit, static_argnames=("engine", "sibling_check"))
def traverse_probe(tree: FBTree, qb, ql,
                   engine: Optional[TraversalEngine] = None,
                   sibling_check: bool = True):
    """Jitted public traverse+probe (see ``_traverse_probe``)."""
    return _traverse_probe(tree, qb, ql, engine, sibling_check)


def dedupe_last_wins(qb, ql, seq) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Deterministic in-batch conflict resolution: highest seq per key wins."""
    words = pack_words_j(qb)                      # [B, W]
    B, W = words.shape
    perm = jnp.argsort(seq, stable=True)

    def resort(col, perm):
        return jnp.take(perm, jnp.argsort(jnp.take(col, perm), stable=True))

    perm = resort(ql, perm)                       # length = least significant
    for col in range(W - 1, -1, -1):
        perm = resort(words[:, col], perm)
    sb = jnp.take(words, perm, axis=0)
    sl = jnp.take(ql, perm)
    same_next = jnp.concatenate([
        (sb[1:] == sb[:-1]).all(-1) & (sl[1:] == sl[:-1]),
        jnp.zeros((1,), bool)])
    keep_sorted = ~same_next                      # last of each equal-run wins
    winners = jnp.zeros((B,), bool).at[perm].set(keep_sorted)
    return winners, (B - keep_sorted.sum()).astype(jnp.int32)


def rowwise_lex_argsort(kb, kl, valid):
    """argsort rows of kb [R,T,L] by (valid desc, key bytes asc, len asc)."""
    R, T, L = kb.shape
    words = pack_words_j(kb)                      # [R, T, W]
    W = words.shape[-1]
    perm = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (R, T))

    def resort(col_vals, perm):
        v = jnp.take_along_axis(col_vals, perm, axis=-1)
        idx = jnp.argsort(v, axis=-1, stable=True)
        return jnp.take_along_axis(perm, idx, axis=-1)

    perm = resort(kl, perm)
    for col in range(W - 1, -1, -1):
        perm = resort(words[..., col], perm)
    perm = resort((~valid).astype(jnp.int32), perm)  # invalid → end
    return perm


def _seg_head_rank(sorted_ids: jnp.ndarray):
    """(is_head, rank-within-run) for a sorted id array."""
    n = sorted_ids.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    is_head = jnp.concatenate([jnp.ones((1,), bool),
                               sorted_ids[1:] != sorted_ids[:-1]])
    head_pos = jnp.where(is_head, idx, 0)
    head_pos = jax.lax.associative_scan(jnp.maximum, head_pos)
    return is_head, idx - head_pos


# --------------------------------------------------------------------------
# lookup / update / remove
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sibling_check", "engine"))
def _lookup_batch_jit(tree: FBTree, qb, ql, sibling_check: bool = True,
                      engine: Optional[TraversalEngine] = None):
    _, _, found, slot, val, bstats, lstats = _traverse_probe(
        tree, qb, ql, engine, sibling_check)
    return val, _report(found, bstats, lstats)


def lookup_batch(tree: FBTree, qb, ql, sibling_check: bool = True,
                 engine: Optional[TraversalEngine] = None):
    """Batched point lookup. Returns (vals [B], report).

    Telemetry (DESIGN.md §9): the launch always runs under the span
    ``op.lookup`` — a profiler annotation, plus the host-time histogram
    ``span.op.lookup`` with ``repro.obs`` enabled. Only with it enabled,
    and after the span, the report's device counters drain into the
    registry: one host sync per batch. With it off nothing waits for the
    device; the traced program is identical either way.
    """
    with obs.span("op.lookup"):
        val, rep = _lookup_batch_jit(tree, qb, ql, sibling_check, engine)
    obs.drain_op_report("lookup", rep)
    return val, rep


@functools.partial(jax.jit, static_argnames=("engine",))
def _update_batch_jit(tree: FBTree, qb, ql, vals,
                      engine: Optional[TraversalEngine] = None, mask=None):
    """Blind value update for existing keys (latch-free CAS analogue).

    Does NOT bump leaf versions (§4.2 — readers never restart on updates).
    ``mask`` (bool [B], optional) is the routed-op hook (DESIGN.md §7):
    lanes with ``mask=False`` never write — the shard router passes
    ``owner == s`` so only a key's owning shard commits it. ``found`` is
    reported for every lane regardless of mask.
    """
    B = qb.shape[0]
    a, ns = tree.arrays, tree.config.ns
    dump = a.leaf_high.shape[0] - 1
    winners, conflicts = dedupe_last_wins(qb, ql, jnp.arange(B, dtype=jnp.int32))
    if mask is not None:
        winners = winners & mask
    leaf_ids, _, found, slot, _, bstats, lstats = _traverse_probe(
        tree, qb, ql, engine)
    do = winners & found
    li = jnp.where(do, leaf_ids, dump)
    lv = leaf_set(a.leaf_val_f, li, slot, vals.astype(a.leaf_val_f.dtype),
                  ns, where=do)
    return tree.replace(leaf_val_f=lv), _report(found, bstats, lstats,
                                                conflicts=conflicts)


def update_batch(tree: FBTree, qb, ql, vals,
                 engine: Optional[TraversalEngine] = None, mask=None):
    """Instrumented wrapper over the jitted blind update (see the jit
    body's docstring; same obs contract as :func:`lookup_batch`)."""
    with obs.span("op.update"):
        tree2, rep = _update_batch_jit(tree, qb, ql, vals, engine, mask)
    obs.drain_op_report("update", rep)
    return tree2, rep


@functools.partial(jax.jit, static_argnames=("engine",))
def _remove_batch_jit(tree: FBTree, qb, ql,
                      engine: Optional[TraversalEngine] = None, mask=None):
    """Tombstone removal (slot cleared, version bumped). ``mask`` gates
    writes exactly as in :func:`update_batch` (routed-op hook)."""
    B = qb.shape[0]
    a, ns = tree.arrays, tree.config.ns
    dump = a.leaf_high.shape[0] - 1
    winners, conflicts = dedupe_last_wins(qb, ql, jnp.arange(B, dtype=jnp.int32))
    if mask is not None:
        winners = winners & mask
    leaf_ids, _, found, slot, _, bstats, lstats = _traverse_probe(
        tree, qb, ql, engine)
    do = winners & found
    li = jnp.where(do, leaf_ids, dump)
    occ = leaf_set(a.leaf_occ_f, li, slot, False, ns, where=do)
    kid = leaf_set(a.leaf_keyid_f, li, slot, EMPTY, ns, where=do)
    ver = a.leaf_version.at[li].add(do.astype(jnp.int32))
    return (tree.replace(leaf_occ_f=occ, leaf_keyid_f=kid, leaf_version=ver),
            _report(found, bstats, lstats, conflicts=conflicts))


def remove_batch(tree: FBTree, qb, ql,
                 engine: Optional[TraversalEngine] = None, mask=None):
    """Instrumented wrapper over the jitted tombstone removal (same obs
    contract as :func:`lookup_batch`)."""
    with obs.span("op.remove"):
        tree2, rep = _remove_batch_jit(tree, qb, ql, engine, mask)
    obs.drain_op_report("remove", rep)
    return tree2, rep


# --------------------------------------------------------------------------
# insert (upsert)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("engine",))
def _prepare_insert(tree: FBTree, qb, ql, vals,
                    engine: Optional[TraversalEngine] = None, mask=None):
    """Dedupe, update existing keys in place, append new key bytes to pool.

    ``mask`` (routed-op hook): masked-out lanes lose the dedupe outright,
    so they neither update in place nor append to the pool — the shard
    layer inserts each key only into its owning shard."""
    B = qb.shape[0]
    a, ns = tree.arrays, tree.config.ns
    ldump = a.leaf_high.shape[0] - 1
    kdump = a.key_bytes.shape[0] - 1
    winners, conflicts = dedupe_last_wins(qb, ql, jnp.arange(B, dtype=jnp.int32))
    if mask is not None:
        winners = winners & mask
    leaf_ids, _, found, slot, _, bstats, lstats = _traverse_probe(
        tree, qb, ql, engine)

    upd = winners & found
    li = jnp.where(upd, leaf_ids, ldump)
    lv = leaf_set(a.leaf_val_f, li, slot, vals.astype(a.leaf_val_f.dtype),
                  ns, where=upd)

    is_new = winners & ~found
    offs = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    kid_op = jnp.where(is_new, a.key_count + offs, EMPTY)
    n_new = is_new.sum().astype(jnp.int32)
    err = (a.key_count + n_new) > kdump
    dst = jnp.where(is_new & (kid_op < kdump), kid_op, kdump)
    kb_new = a.key_bytes.at[dst].set(jnp.where(is_new[:, None], qb, a.key_bytes[dst]))
    kl_new = a.key_lens.at[dst].set(jnp.where(is_new, ql, a.key_lens[dst]))
    kt_new = a.key_tags.at[dst].set(
        jnp.where(is_new, fnv1a_tags(qb, ql), a.key_tags[dst]))

    tree2 = tree.replace(leaf_val_f=lv, key_bytes=kb_new, key_lens=kl_new,
                         key_tags=kt_new, key_count=a.key_count + n_new)
    return tree2, kid_op, is_new, _report(found, bstats, lstats,
                                          conflicts=conflicts, error=err)


def _make_insert_round(cfg, max_ov: int, ins_cap: int,
                       engine: Optional[TraversalEngine] = None):
    """Build the jitted per-round insert function (static shapes)."""
    ns, fs, L = cfg.ns, cfg.fs, cfg.key_width
    lfill = cfg.leaf_fill
    ifill = cfg.inner_fill
    C_MAX = -(-(ns + ins_cap) // lfill) + 1
    # worst-case anchors arriving at one parent: every one of its <= ns ov
    # children contributes C_MAX-1 new chunks (one extra level of slack; the
    # error flag + raise in insert_batch is the backstop for pathologies)
    IN_CAP = min(max_ov, ns) * (C_MAX - 1) + ns

    def _repack_rows(kb_store, kl_store, item_a, item_b, item_valid, row_valid,
                     fill, c_max):
        """Sort row workspaces and chunk them. Returns dict of chunking state."""
        akb = kb_store[jnp.maximum(item_a, 0)]
        akl = jnp.where(item_valid, kl_store[jnp.maximum(item_a, 0)], 0)
        sperm = rowwise_lex_argsort(akb, akl, item_valid)
        g = lambda x: jnp.take_along_axis(x, sperm, axis=-1)
        item_a, item_b, item_valid = g(item_a), g(item_b), g(item_valid)
        T = item_a.shape[1]
        Tcnt = item_valid.sum(-1).astype(jnp.int32)
        n_chunks = jnp.where(row_valid, -(-Tcnt // fill), 0).astype(jnp.int32)
        base = Tcnt // jnp.maximum(n_chunks, 1)
        rem = Tcnt - base * jnp.maximum(n_chunks, 1)
        pos = jnp.arange(T, dtype=jnp.int32)[None, :]
        chunk = chunk_of_pos(pos, base[:, None], rem[:, None])
        chunk = jnp.where(item_valid, jnp.minimum(chunk, c_max - 1), c_max - 1)
        slot_in_chunk = pos - chunk_start(chunk, base[:, None], rem[:, None])
        cidx = jnp.arange(c_max, dtype=jnp.int32)[None, :]
        cstart = chunk_start(cidx, base[:, None], rem[:, None])
        chunk_exists = (cidx < n_chunks[:, None]) & row_valid[:, None]
        csize = (base[:, None] + (cidx < rem[:, None])).astype(jnp.int32)
        cmin = jnp.take_along_axis(item_a, jnp.minimum(cstart, T - 1), axis=-1)
        return dict(a=item_a, b=item_b, valid=item_valid, Tcnt=Tcnt,
                    n_chunks=n_chunks, chunk=chunk, slot=slot_in_chunk,
                    cidx=cidx, chunk_exists=chunk_exists, csize=csize, cmin=cmin)

    def round_fn(tree: FBTree, kid_op, pending, vals):
        a = tree.arrays
        B = kid_op.shape[0]
        LC = a.leaf_high.shape[0]
        ldump = LC - 1
        qb = a.key_bytes[jnp.maximum(kid_op, 0)]
        ql = jnp.where(pending, a.key_lens[jnp.maximum(kid_op, 0)], 0)
        leaf_ids, path, _ = resolve_engine(engine).traverse(
            tree, qb, ql, sibling_check=False)
        leaf_ids = jnp.where(pending, leaf_ids, ldump)

        perm = jnp.argsort(jnp.where(pending, leaf_ids, BIG), stable=True)
        s_leaf = jnp.take(leaf_ids, perm)
        s_pending = jnp.take(pending, perm)
        s_kid = jnp.take(kid_op, perm)
        s_val = jnp.take(vals, perm)
        is_head, rank = _seg_head_rank(s_leaf)

        cnt_leaf = jnp.zeros((LC,), jnp.int32).at[
            jnp.where(s_pending, s_leaf, ldump)].add(s_pending.astype(jnp.int32))
        occ_cnt = leaf_counts(a.leaf_occ_f, ns)
        fits_leaf = (occ_cnt + cnt_leaf) <= ns

        # ---------- fit path ----------
        s_fit = s_pending & fits_leaf[s_leaf]
        occ_rows = leaf_rows(a.leaf_occ_f, s_leaf, ns)
        free_order = jnp.argsort(occ_rows.astype(jnp.int32), axis=-1, stable=True)
        slot = jnp.take_along_axis(free_order, jnp.minimum(rank, ns - 1)[:, None],
                                   axis=-1)[:, 0]
        li = jnp.where(s_fit, s_leaf, ldump)
        put = lambda arr, new: leaf_set(arr, li, slot, new, ns, where=s_fit)
        leaf_keyid = put(a.leaf_keyid_f, s_kid)
        leaf_val = put(a.leaf_val_f, s_val.astype(a.leaf_val_f.dtype))
        leaf_tags = put(a.leaf_tags_f, a.key_tags[jnp.maximum(s_kid, 0)])
        leaf_occ = put(a.leaf_occ_f, True)
        leaf_version = a.leaf_version.at[li].add(s_fit.astype(jnp.int32))
        leaf_ordered = a.leaf_ordered.at[li].set(
            jnp.where(s_fit, False, a.leaf_ordered[li]))
        done_sorted = s_fit

        # ---------- overflow path ----------
        ov_head = is_head & s_pending & ~fits_leaf[s_leaf]
        ov_head_pos = jnp.argsort(
            jnp.where(ov_head, jnp.arange(B, dtype=jnp.int32), BIG),
            stable=True)[:max_ov]
        ov_valid = jnp.take(ov_head, ov_head_pos)
        ov_leaf = jnp.where(ov_valid, jnp.take(s_leaf, ov_head_pos), EMPTY)
        ov_repop = jnp.where(ov_valid, jnp.take(perm, ov_head_pos), 0)

        ov_rank_of_leaf = jnp.full((LC,), BIG).at[
            jnp.where(ov_valid, ov_leaf, ldump)].set(
            jnp.where(ov_valid, jnp.arange(max_ov, dtype=jnp.int32), BIG))
        op_ovr = ov_rank_of_leaf[s_leaf]
        s_proc = s_pending & ~fits_leaf[s_leaf] & (op_ovr < max_ov) & (rank < ins_cap)
        done_sorted = done_sorted | s_proc

        ovl = jnp.where(ov_valid, ov_leaf, ldump)
        vdt = a.leaf_val_f.dtype
        ws_kid = jnp.concatenate(
            [leaf_rows(a.leaf_keyid_f, ovl, ns),
             jnp.full((max_ov, ins_cap), EMPTY, jnp.int32)], axis=1)
        ws_val = jnp.concatenate(
            [leaf_rows(a.leaf_val_f, ovl, ns),
             jnp.zeros((max_ov, ins_cap), vdt)], axis=1)
        ws_valid = jnp.concatenate(
            [leaf_rows(a.leaf_occ_f, ovl, ns) & ov_valid[:, None],
             jnp.zeros((max_ov, ins_cap), bool)], axis=1)
        ri = jnp.where(s_proc, op_ovr, max_ov - 1)
        ci = jnp.where(s_proc, ns + jnp.minimum(rank, ins_cap - 1), 0)
        selp = lambda new, old: jnp.where(s_proc, new, old)
        ws_kid = ws_kid.at[ri, ci].set(selp(s_kid, ws_kid[ri, ci]))
        ws_val = ws_val.at[ri, ci].set(selp(s_val.astype(vdt), ws_val[ri, ci]))
        ws_valid = ws_valid.at[ri, ci].set(selp(True, ws_valid[ri, ci]))

        rp = _repack_rows(a.key_bytes, a.key_lens, ws_kid, ws_val, ws_valid,
                          ov_valid, lfill, C_MAX)

        new_per_row = jnp.maximum(rp["n_chunks"] - 1, 0)
        new_base = a.leaf_count + jnp.cumsum(new_per_row) - new_per_row
        err = (a.leaf_count + new_per_row.sum()) > cfg.leaf_cap

        dst_leaf = jnp.where(rp["chunk"] == 0, ovl[:, None],
                             new_base[:, None] + rp["chunk"] - 1)
        dst_leaf = jnp.where(rp["valid"] & (rp["chunk"] < rp["n_chunks"][:, None]),
                             dst_leaf, ldump)

        clr = ovl
        clear = lambda arr, v: leaf_set(arr, clr, None, v, ns, where=ov_valid)
        leaf_occ = clear(leaf_occ, False)
        leaf_keyid = clear(leaf_keyid, EMPTY)

        fvalid = rp["valid"].reshape(-1)
        fl = jnp.where(fvalid, dst_leaf.reshape(-1), ldump)
        fsl = jnp.where(fvalid, jnp.clip(rp["slot"], 0, ns - 1).reshape(-1), ns - 1)
        fkid = rp["a"].reshape(-1)
        w = lambda arr, val: leaf_set(arr, fl, fsl, val, ns, where=fvalid)
        leaf_keyid = w(leaf_keyid, fkid)
        leaf_val = w(leaf_val, rp["b"].reshape(-1))
        leaf_tags = w(leaf_tags, a.key_tags[jnp.maximum(fkid, 0)])
        leaf_occ = w(leaf_occ, jnp.ones_like(fvalid))

        cidx, chunk_exists, cmin = rp["cidx"], rp["chunk_exists"], rp["cmin"]
        chunk_leaf = jnp.where(cidx == 0, ovl[:, None], new_base[:, None] + cidx - 1)
        next_chunk_leaf = jnp.where(cidx + 1 < rp["n_chunks"][:, None],
                                    new_base[:, None] + cidx,
                                    a.leaf_next[ovl][:, None])
        chunk_high = jnp.where(
            cidx + 1 < rp["n_chunks"][:, None],
            jnp.take_along_axis(cmin, jnp.minimum(cidx + 1, C_MAX - 1), axis=-1),
            a.leaf_high[ovl][:, None])
        wmask = chunk_exists.reshape(-1)
        wl = jnp.where(wmask, chunk_leaf.reshape(-1), ldump)
        leaf_next = a.leaf_next.at[wl].set(
            jnp.where(wmask, next_chunk_leaf.reshape(-1), a.leaf_next[wl]))
        leaf_high = a.leaf_high.at[wl].set(
            jnp.where(wmask, chunk_high.reshape(-1), a.leaf_high[wl]))
        leaf_version = leaf_version.at[wl].add(wmask.astype(jnp.int32))
        leaf_ordered = leaf_ordered.at[wl].set(
            jnp.where(wmask, True, leaf_ordered[wl]))
        leaf_count = a.leaf_count + new_per_row.sum().astype(jnp.int32)
        n_splits = ov_valid.sum().astype(jnp.int32)

        arrays = a._replace(
            leaf_keyid_f=leaf_keyid, leaf_val_f=leaf_val, leaf_tags_f=leaf_tags,
            leaf_occ_f=leaf_occ, leaf_high=leaf_high, leaf_next=leaf_next,
            leaf_version=leaf_version, leaf_ordered=leaf_ordered,
            leaf_count=leaf_count)

        # tuples for the parent level: (parent node, anchor kid, child, rep-op)
        tup_mask = (chunk_exists & (cidx >= 1)).reshape(-1)
        tup_repop = jnp.broadcast_to(ov_repop[:, None], (max_ov, C_MAX)).reshape(-1)
        tup_parent = jnp.where(tup_mask, jnp.take(path[-1], tup_repop), EMPTY)
        tup_anchor = jnp.where(tup_mask, cmin.reshape(-1), EMPTY)
        tup_child = jnp.where(tup_mask, chunk_leaf.reshape(-1), EMPTY)

        new_levels = list(arrays.levels)
        for lvl in range(len(arrays.levels) - 1, -1, -1):
            parent_path = path[lvl - 1] if lvl > 0 else None
            (lvl2, tup_parent, tup_anchor, tup_child, tup_repop, e) = _inner_insert(
                new_levels[lvl], arrays, tup_parent, tup_anchor, tup_child,
                tup_repop, parent_path)
            new_levels[lvl] = lvl2
            err = err | e
        arrays = arrays._replace(levels=tuple(new_levels))

        done_orig = jnp.zeros((B,), bool).at[perm].set(done_sorted)
        new_pending = pending & ~done_orig
        return FBTree(tree.config, arrays), new_pending, n_splits, err

    def _inner_insert(level: Level, arrays: TreeArrays,
                      tup_parent, tup_anchor, tup_child, tup_repop, parent_path):
        """Insert (anchor, child) tuples into one inner level; emit next tuples."""
        NT = tup_parent.shape[0]
        capn = level.knum.shape[0]
        ndump = capn - 1
        kb_store, kl_store = arrays.key_bytes, arrays.key_lens
        is_root = parent_path is None

        tv = tup_parent >= 0
        perm = jnp.argsort(jnp.where(tv, tup_parent, BIG), stable=True)
        sp = jnp.take(tup_parent, perm)
        sa = jnp.take(tup_anchor, perm)
        sc = jnp.take(tup_child, perm)
        sr = jnp.take(tup_repop, perm)
        stv = jnp.take(tv, perm)
        is_head, rank = _seg_head_rank(sp)

        R = max_ov
        head_pos = jnp.argsort(jnp.where(is_head & stv,
                                         jnp.arange(NT, dtype=jnp.int32), BIG),
                               stable=True)[:R]
        row_valid = jnp.take(is_head & stv, head_pos)
        row_node = jnp.where(row_valid, jnp.take(sp, head_pos), EMPTY)
        row_repop = jnp.where(row_valid, jnp.take(sr, head_pos), 0)
        rank_of_node = jnp.full((capn,), BIG).at[
            jnp.where(row_valid, row_node, ndump)].set(
            jnp.where(row_valid, jnp.arange(R, dtype=jnp.int32), BIG))
        op_row = rank_of_node[jnp.maximum(sp, 0)]
        s_ok = stv & (op_row < R) & (rank < IN_CAP)
        err = (stv & ~s_ok).any()

        rn = jnp.where(row_valid, row_node, ndump)
        lane = jnp.arange(ns, dtype=jnp.int32)[None, :]
        ws_anchor = jnp.concatenate(
            [level.anchors[rn], jnp.full((R, IN_CAP), EMPTY, jnp.int32)], axis=1)
        ws_child = jnp.concatenate(
            [level.children[rn], jnp.full((R, IN_CAP), EMPTY, jnp.int32)], axis=1)
        ws_valid = jnp.concatenate(
            [(lane < level.knum[rn][:, None]) & row_valid[:, None],
             jnp.zeros((R, IN_CAP), bool)], axis=1)
        ri = jnp.where(s_ok, op_row, R - 1)
        ci = jnp.where(s_ok, ns + jnp.minimum(rank, IN_CAP - 1), 0)
        selp = lambda new, old: jnp.where(s_ok, new, old)
        ws_anchor = ws_anchor.at[ri, ci].set(selp(sa, ws_anchor[ri, ci]))
        ws_child = ws_child.at[ri, ci].set(selp(sc, ws_child[ri, ci]))
        ws_valid = ws_valid.at[ri, ci].set(selp(True, ws_valid[ri, ci]))

        CI_MAX = -(-(ns + IN_CAP) // ifill) + 1
        rp = _repack_rows(kb_store, kl_store, ws_anchor, ws_child, ws_valid,
                          row_valid, ifill, CI_MAX)
        n_chunks = rp["n_chunks"]
        if is_root:
            err = err | (n_chunks > 1).any() | (rp["Tcnt"] > ns).any()
            n_chunks = jnp.minimum(n_chunks, 1)

        new_per_row = jnp.maximum(n_chunks - 1, 0)
        new_base = level.count + jnp.cumsum(new_per_row) - new_per_row
        err = err | ((level.count + new_per_row.sum()) > ndump)

        dst_node = jnp.where(rp["chunk"] == 0, rn[:, None],
                             new_base[:, None] + rp["chunk"] - 1)
        dst_node = jnp.where(rp["valid"] & (rp["chunk"] < n_chunks[:, None]),
                             dst_node, ndump)

        anchors_new = level.anchors.at[rn].set(
            jnp.where(row_valid[:, None], EMPTY, level.anchors[rn]))
        children_new = level.children.at[rn].set(
            jnp.where(row_valid[:, None], EMPTY, level.children[rn]))
        fvalid = (rp["valid"] & (rp["slot"] < ns) & (rp["slot"] >= 0)
                  & (rp["chunk"] < n_chunks[:, None])).reshape(-1)
        fn = jnp.where(fvalid, dst_node.reshape(-1), ndump)
        fsl = jnp.where(fvalid, jnp.clip(rp["slot"], 0, ns - 1).reshape(-1), ns - 1)
        anchors_new = anchors_new.at[fn, fsl].set(
            jnp.where(fvalid, rp["a"].reshape(-1), anchors_new[fn, fsl]))
        children_new = children_new.at[fn, fsl].set(
            jnp.where(fvalid, rp["b"].reshape(-1), children_new[fn, fsl]))

        cidx = rp["cidx"]
        chunk_exists = (cidx < n_chunks[:, None]) & row_valid[:, None]
        csize = jnp.minimum(rp["csize"], ns)
        cnode = jnp.where(cidx == 0, rn[:, None], new_base[:, None] + cidx - 1)
        wm = chunk_exists.reshape(-1)
        wn = jnp.where(wm, cnode.reshape(-1), ndump)
        knum_new = level.knum.at[wn].set(
            jnp.where(wm, csize.reshape(-1), level.knum[wn]))

        sub_anch = anchors_new[wn]
        sub_knum = knum_new[wn]
        pl, pf, ft = recompute_inner_meta(kb_store, kl_store, sub_anch,
                                           sub_knum, fs)
        plen_new = level.plen.at[wn].set(jnp.where(wm, pl, level.plen[wn]))
        prefix_new = level.prefix.at[wn].set(
            jnp.where(wm[:, None], pf, level.prefix[wn]))
        feats_new = level.features.at[wn].set(
            jnp.where(wm[:, None, None], ft, level.features[wn]))
        count_new = level.count + new_per_row.sum().astype(jnp.int32)

        level2 = Level(knum=knum_new, plen=plen_new, prefix=prefix_new,
                       features=feats_new, children=children_new,
                       anchors=anchors_new, count=count_new)

        nt_mask = (chunk_exists & (cidx >= 1)).reshape(-1)
        nt_repop = jnp.broadcast_to(row_repop[:, None], (R, CI_MAX)).reshape(-1)
        if is_root:
            nt_parent = jnp.full((R * CI_MAX,), EMPTY, jnp.int32)
        else:
            nt_parent = jnp.where(nt_mask, jnp.take(parent_path, nt_repop), EMPTY)
        nt_anchor = jnp.where(nt_mask, rp["cmin"].reshape(-1), EMPTY)
        nt_child = jnp.where(nt_mask, cnode.reshape(-1), EMPTY)
        return level2, nt_parent, nt_anchor, nt_child, nt_repop, err

    return jax.jit(round_fn)


_ROUND_CACHE = {}


def insert_batch(tree: FBTree, qb, ql, vals, max_ov: int = 128,
                 ins_cap: int = None, max_rounds: int = 64,
                 engine: Optional[TraversalEngine] = None, mask=None):
    """Batched upsert. Returns (tree', report, rounds).

    Orchestrates: dedupe/update/append (one jitted call) + split rounds
    (jitted, bounded work per round) until no ops are pending. ``ins_cap``
    bounds keys absorbed per leaf per round (default 4*ns — monotone-append
    workloads funnel a whole batch into the rightmost leaf). ``mask``
    (bool [B], optional) is the routed-op hook: masked-out lanes are
    no-ops — no in-place update, no pool append, never pending.

    Telemetry: same obs contract as :func:`lookup_batch`, plus an
    ``op.rounds`` counter (split rounds taken, labeled ``op=insert``). The
    span covers the split rounds and their own host syncs (each round
    reads ``pending`` and the error flag), which the insert needs with
    telemetry on or off.
    """
    with obs.span("op.insert"):
        tree2, rep, rounds = _insert_batch_impl(
            tree, qb, ql, vals, max_ov, ins_cap, max_rounds, engine, mask)
    obs.drain_op_report("insert", rep)
    obs.counter("op.rounds", op="insert").inc(rounds)
    return tree2, rep, rounds


def _insert_batch_impl(tree: FBTree, qb, ql, vals, max_ov: int = 128,
                       ins_cap: int = None, max_rounds: int = 64,
                       engine: Optional[TraversalEngine] = None, mask=None):
    qb = jnp.asarray(qb)
    ql = jnp.asarray(ql)
    vals = jnp.asarray(vals)
    max_ov = min(max_ov, qb.shape[0])   # can't overflow more leaves than ops
    if ins_cap is None:
        ins_cap = 4 * tree.config.ns
    # normalize so engine=None and an explicit default engine share one
    # round cache entry / jit specialization
    engine = resolve_engine(engine)
    key = (tree.config, max_ov, ins_cap, engine)
    if key not in _ROUND_CACHE:
        _ROUND_CACHE[key] = _make_insert_round(tree.config, max_ov, ins_cap,
                                               engine)
    round_fn = _ROUND_CACHE[key]

    tree, kid_op, pending, rep = _prepare_insert(tree, qb, ql, vals,
                                                 engine=engine, mask=mask)
    if bool(rep.error):
        raise RuntimeError("insert_batch: key pool capacity exceeded")
    total_splits = jnp.int32(0)
    rounds = 0
    while rounds < max_rounds:
        if not bool(pending.any()):
            break
        tree, pending, n_splits, e = round_fn(tree, kid_op, pending, vals)
        if bool(e):
            raise RuntimeError("insert_batch: capacity violated (leaf/node/"
                               "root overflow) — grow TreeConfig caps")
        total_splits = total_splits + n_splits
        rounds += 1
    if bool(pending.any()):
        raise RuntimeError("insert_batch: ops still pending after "
                           f"{max_rounds} rounds (capacity exhausted?)")
    rep = rep._replace(splits=total_splits)
    return tree, rep, rounds


# --------------------------------------------------------------------------
# range scan
# --------------------------------------------------------------------------

def _range_scan_jnp(tree: FBTree, qb, ql, max_items: int,
                    eng: TraversalEngine, force_sort: bool = False):
    """jnp chain-walk reference for the range scan (DESIGN.md §6).

    One engine descent to the start leaf, then an early-exit
    ``lax.while_loop`` over the sibling chain: lanes retire as they reach
    ``max_items`` or chain end, so short chains stop immediately and
    tombstone-drained chains are walked to completion (the old fixed
    ``ceil(max_items / (leaf_fill // 2)) + 1`` hop bound both over-walked
    and under-filled).

    Lazy rearrangement (§4.5): each hop sorts via ``rowwise_lex_argsort``
    only under a ``lax.cond`` that fires when some *active* lane sits on a
    leaf with its ``leaf_ordered`` bit clear — when every visited leaf is
    ordered, emission is a plain occupancy cumsum in slot order (ordered
    leaves store keys ascending) and, past hop 0, no key bytes are gathered
    at all. Hop 0 is peeled: it is the only hop that needs key bytes
    unconditionally (the start-key compare), and the only hop that filters
    ``key >= query``; hop ≥ 1 leaves emit every occupied slot (the chain
    ascends).

    ``rearranged`` counts the dirty leaves each lane actually visited (the
    leaves a pointer-stable implementation would rearrange); with the
    engine's static ``collect_stats`` off the counter is never traced and
    comes back all-zero. ``force_sort=True`` (static) disables the ordered
    fast path — the always-sort baseline ``benchmarks/scan.py`` A/Bs
    against; outputs are bit-identical either way.
    """
    a = tree.arrays
    ns = tree.config.ns
    B = qb.shape[0]
    dump = a.leaf_high.shape[0] - 1
    cs = eng.collect_stats
    leaf_ids, _, _ = eng.traverse(tree, qb, ql)
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, ns))

    # one scratch column at index max_items for masked scatter dumps
    out_kid = jnp.full((B, max_items + 1), EMPTY, jnp.int32)
    out_val = jnp.zeros((B, max_items + 1), a.leaf_val_f.dtype)
    emitted = jnp.zeros((B,), jnp.int32)

    def emit_to(out_kid, out_val, emitted, kid, val, emit):
        rank = jnp.cumsum(emit.astype(jnp.int32), axis=-1) - 1
        dstpos = emitted[:, None] + rank
        ok = emit & (dstpos < max_items) & (dstpos >= 0)
        dp = jnp.where(ok, dstpos, max_items)     # dump to scratch column
        out_kid = out_kid.at[bidx, dp].set(
            jnp.where(ok, kid, out_kid[bidx, dp]))
        out_val = out_val.at[bidx, dp].set(
            jnp.where(ok, val, out_val[bidx, dp]))
        emitted = jnp.minimum(emitted + emit.sum(-1), max_items)
        return out_kid, out_val, emitted

    # ---- hop 0 (peeled): start-key compare — key bytes gathered here and,
    # on later hops, only inside the dirty-leaf sort branch
    cur = leaf_ids
    kid = leaf_rows(a.leaf_keyid_f, cur, ns)      # [B, ns]
    val = leaf_rows(a.leaf_val_f, cur, ns)
    occ = leaf_rows(a.leaf_occ_f, cur, ns)
    kb = a.key_bytes[jnp.maximum(kid, 0)]         # [B, ns, L]
    kl = jnp.where(occ, a.key_lens[jnp.maximum(kid, 0)], 0)
    dirty = ~a.leaf_ordered[cur]

    def _as_is(ops):
        return ops

    def _sorted0(ops):
        kid, val, occ, kb, kl = ops
        perm = rowwise_lex_argsort(kb, kl, occ)
        g = lambda x: jnp.take_along_axis(x, perm, axis=-1)
        return (g(kid), g(val), g(occ),
                jnp.take_along_axis(kb, perm[:, :, None], axis=1), g(kl))

    pred = jnp.zeros((), bool) if force_sort else ~dirty.any()
    kid, val, occ, kb, kl = jax.lax.cond(pred, _as_is, _sorted0,
                                         (kid, val, occ, kb, kl))
    emit = occ & (compare_padded(kb, kl, qb[:, None, :], ql[:, None]) >= 0)
    out_kid, out_val, emitted = emit_to(out_kid, out_val, emitted,
                                        kid, val, emit)
    nxt = a.leaf_next[cur]
    cur = jnp.where((nxt >= 0) & (emitted < max_items), nxt, dump)

    # ---- hops 1+: early-exit chain walk (every key of an active leaf
    # emits — the ascending chain guarantees key >= query past hop 0)
    def w_cond(c):
        return (c[0] != dump).any()

    def w_body(c):
        if cs:
            cur, emitted, out_kid, out_val, rearr = c
        else:
            cur, emitted, out_kid, out_val = c
        active = cur != dump
        kid = leaf_rows(a.leaf_keyid_f, cur, ns)
        val = leaf_rows(a.leaf_val_f, cur, ns)
        occ = leaf_rows(a.leaf_occ_f, cur, ns) & active[:, None]
        dirty = active & ~a.leaf_ordered[cur]

        def _sortedh(ops):
            kid, val, occ = ops
            kb = a.key_bytes[jnp.maximum(kid, 0)]
            kl = jnp.where(occ, a.key_lens[jnp.maximum(kid, 0)], 0)
            perm = rowwise_lex_argsort(kb, kl, occ)
            g = lambda x: jnp.take_along_axis(x, perm, axis=-1)
            return g(kid), g(val), g(occ)

        pred = jnp.zeros((), bool) if force_sort else ~dirty.any()
        kid, val, occ = jax.lax.cond(pred, _as_is, _sortedh, (kid, val, occ))
        out_kid2, out_val2, emitted2 = emit_to(out_kid, out_val, emitted,
                                               kid, val, occ)
        nxt = a.leaf_next[cur]
        cur = jnp.where(active & (nxt >= 0) & (emitted2 < max_items),
                        nxt, dump)
        if cs:
            return cur, emitted2, out_kid2, out_val2, \
                rearr + dirty.astype(jnp.int32)
        return cur, emitted2, out_kid2, out_val2

    carry = (cur, emitted, out_kid, out_val)
    if cs:
        carry = carry + (dirty.astype(jnp.int32),)
    final = jax.lax.while_loop(w_cond, w_body, carry)
    _, emitted, out_kid, out_val = final[:4]
    rearranged = final[4] if cs else jnp.zeros((B,), jnp.int32)
    return out_kid[:, :max_items], out_val[:, :max_items], emitted, rearranged


@functools.partial(jax.jit, static_argnames=("max_items", "engine"))
def _range_scan_jit(tree: FBTree, qb, ql, max_items: int = 64,
                    engine: Optional[TraversalEngine] = None):
    return _range_scan_jnp(tree, qb, ql, max_items, resolve_engine(engine))


def range_scan(tree: FBTree, qb, ql, max_items: int = 64,
               engine: Optional[TraversalEngine] = None):
    """Batched range scan: for each start key return up to ``max_items``
    ``(key_id, value)`` pairs in ascending key order, starting at the first
    key >= the query (lazy rearrangement: unsorted leaves are sorted on the
    fly, modeling §4.5; ordered leaves skip the sort entirely).

    The engine descends to each start leaf, then the jnp chain walk
    (:func:`_range_scan_jnp`) follows the sibling links (DESIGN.md §6).
    Returns ``(out_kid [B, max_items], out_val [B, max_items], emitted [B],
    rearranged [B])``; ``rearranged`` (dirty leaves visited) is all-zero
    under a stats-free engine.

    Telemetry: same obs contract as :func:`lookup_batch` — the span is
    ``op.scan``, and with telemetry enabled ``op.emitted``/
    ``op.rearranged`` counters drain from the scan outputs (one host
    sync, after the span).
    """
    if max_items < 1:
        raise ValueError(
            f"range_scan: max_items must be >= 1, got {max_items} — each "
            f"lane emits up to max_items (key, value) pairs")
    with obs.span("op.scan"):
        out_kid, out_val, emitted, rearranged = _range_scan_jit(
            tree, qb, ql, max_items, engine)
    if obs.enabled():
        em, re = jax.device_get((emitted, rearranged))
        obs.counter("op.calls", op="scan").inc()
        obs.counter("op.lanes", op="scan").inc(int(em.size))
        obs.counter("op.emitted", op="scan").inc(int(em.sum()))
        obs.counter("op.rearranged", op="scan").inc(int(re.sum()))
    return out_kid, out_val, emitted, rearranged


# --------------------------------------------------------------------------
# rebuild — device-side bulk re-construction (DESIGN.md §5)
# --------------------------------------------------------------------------

class BuildReport(NamedTuple):
    """Outcome of a device-side (re)build."""
    n_live: jnp.ndarray     # int32 — keys carried into the new tree
    n_leaves: jnp.ndarray   # int32 — leaves the fresh build allocated
    reclaimed: jnp.ndarray  # int32 — key-pool rows freed (tombstones, dupes)
    error: jnp.ndarray      # bool — capacity exceeded; discard the result


@jax.jit
def _live_pool(tree: FBTree):
    """The key pool with its live rows marked: ``(kb, kl, ktags, vals,
    invalid)``, the input of ``fbtree.sort_pool``. Every live key id sits in
    exactly one occupied leaf slot, so liveness and values scatter onto the
    pool, and the sort runs over its ``key_cap + 1`` rows rather than over
    every leaf slot (``ns`` times ``leaf_len``, several times more at a
    planned capacity)."""
    a, KC = tree.arrays, tree.config.key_cap
    # flattening a folded slot array gives the logical [LC, ns] order
    occ = a.leaf_occ_f.reshape(-1)                # [leaf_len * ns]
    # free slots all write False/0 into the scratch row KC: the duplicate
    # writes agree, and no live key id reaches it
    dst = jnp.where(occ, a.leaf_keyid_f.reshape(-1), KC)
    live = jnp.zeros((KC + 1,), bool).at[dst].set(occ)
    vals = jnp.zeros((KC + 1,), a.leaf_val_f.dtype).at[dst].set(
        jnp.where(occ, a.leaf_val_f.reshape(-1), 0))
    return a.key_bytes, a.key_lens, a.key_tags, vals, ~live


def gather_live_sorted(tree: FBTree):
    """Gather a tree's live key set into a sorted, compacted, pool-shaped
    snapshot: ``(kb, kl, ktags, vals, n_live)`` with rows ``[0, n_live)``
    holding the live keys ascending and zeros everywhere else — exactly the
    input contract of ``fbtree._device_build_from_sorted``.

    Pure jnp (composes under jit): :func:`rebuild` feeds it straight back
    into the device build, and the shard layer (DESIGN.md §7) concatenates
    the per-shard snapshots — already globally sorted, since shards are
    range-partitioned — to re-partition on ``repro.shard.rebalance``.
    """
    return sort_pool(*_live_pool(tree))


def build_from_snapshot(tree: FBTree, snapshot) -> Tuple[FBTree, BuildReport]:
    """Rebuild ``tree`` from its :func:`gather_live_sorted` snapshot through
    the device build core — the back half of :func:`rebuild`."""
    kb, kl, ktags, vv, n_live = snapshot
    arrays, err = _device_build_jit(cfg=tree.config, kb=kb, kl=kl,
                                    ktags=ktags, vals=vv, n=n_live)
    rep = BuildReport(n_live=n_live, n_leaves=arrays.leaf_count,
                      reclaimed=(tree.arrays.key_count - n_live
                                 ).astype(jnp.int32),
                      error=err)
    return FBTree(tree.config, arrays), rep


def rebuild(tree: FBTree) -> Tuple[FBTree, BuildReport]:
    """Compact a split-fragmented tree by re-running the device bulk build.

    Gathers the live (key id, value) pairs from the leaves
    (:func:`gather_live_sorted`: packed-word sort, invalid rows last, pool
    re-packed front-to-back) and reconstructs every level through
    ``fbtree._device_build_from_sorted``.
    Three jitted stages: marking the live pool rows, the pool sort
    (``fbtree.sort_pool``) and the build core, which is the very program
    ``bulk_build(device=True)`` runs, so a config compiles it once.

    Semantics w.r.t. the §2 protocol (DESIGN.md §5): a rebuild is a
    bulk-synchronous barrier. Tombstoned keys are dropped and the pool is
    compacted, so *key ids are not stable across a rebuild*; leaf versions
    reset to zero and sibling links are relinked left-to-right. Results
    cached from before the barrier (leaf ids, key ids, versions) must be
    re-resolved by a fresh traversal. The output tree is exactly what
    ``bulk_build`` (host or device) would produce from the live key set.

    Telemetry: same obs contract as :func:`lookup_batch`; span
    ``op.rebuild``, and with telemetry enabled counters
    ``build.n_live``/``build.reclaimed`` labeled ``op=rebuild``.
    """
    with obs.span("op.rebuild"):
        tree2, rep = build_from_snapshot(tree, gather_live_sorted(tree))
    if obs.enabled():
        host = jax.device_get(rep)
        obs.counter("op.calls", op="rebuild").inc()
        obs.counter("build.n_live", op="rebuild").inc(int(host.n_live))
        obs.counter("build.reclaimed",
                    op="rebuild").inc(int(host.reclaimed))
    return tree2, rep
