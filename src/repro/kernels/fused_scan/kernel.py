"""Pallas TPU kernel: batched range scan — descent + leaf-chain walk in ONE
launch (the YCSB-E hot path, paper Fig. 17).

The jnp reference (``core.batch_ops._range_scan_jnp``) relaunches a gather +
sort + scatter pipeline per sibling hop through XLA; the level-wise
batch-search designs (BS-tree, the FPGA batch scan) show the win comes from
keeping the walk resident. This kernel tiles the *query batch* over the grid
and runs the whole scan inside the kernel body:

  1. the root→leaf descent — ``descend_levels`` + ``sibling_hop``, SHARED
     with ``kernels/fused_descent`` so both kernels resolve bit-identical
     start leaves (stats-free: ``range_scan`` never returns BranchStats);
  2. a peeled hop 0 with the in-kernel start-key compare — the ONLY hop
     that gathers key bytes unconditionally;
  3. an early-exit ``while_loop`` over the sibling chain: every key of an
     active leaf emits (the chain ascends, so hop ≥ 1 keys are all ≥ the
     start key), lanes retire as they hit ``max_items`` or chain end.

Lazy rearrangement (paper §4.5) in-kernel: each hop's emission order comes
from a ``lax.cond`` — when every active lane's leaf has its ``leaf_ordered``
bit set, ranks are a plain occupancy cumsum (no key traffic at all); only a
dirty leaf pays the rank-by-count sort. Sorting is *rank-by-count* rather
than argsort (rank(j) = #{emitted i : key_i < key_j} over order-preserving
packed words): tree keys are unique, so strict 'less' reproduces the jnp
reference's stable lexsort emission order bit for bit, and the [TB, ns, ns]
compare is a vector reduction instead of a data-dependent permutation.

Emission is scatter-free: a slot with in-row rank r lands at output column
``emitted + r`` via a one-hot reduction over the slot axis (`_merge_emit`) —
destination positions are unique per row, so the reduction is exact. Static
``collect_stats`` drops the ``rearranged`` accumulator and output from the
compiled kernel; emitted pairs are bit-identical either way.

Like ``fused_descent``, whose level loop it reuses, this kernel runs only
in the Pallas interpreter (the CPU test backend): Mosaic refuses the
per-lane row gathers, and whole-array VMEM blocks cannot hold a
deployment-sized tree. ``core.traverse`` refuses the ``"fused"`` backend on
a TPU; a chip version would stream the chain through double-buffered leaf
blocks fetched from HBM (ROADMAP A5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.keys import pack_words_j

from ..fused_descent.kernel import (_cmp3, descend_levels, descent_tile,
                                    sibling_hop)

__all__ = ["fused_scan_kernel", "descent_tile"]


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _cmp3_slots(kb, kl, qb, ql):
    """3-way compare of every leaf-slot key against its lane's query:
    ``kb [TB, ns, L]`` / ``kl [TB, ns]`` vs ``qb [TB, L]`` / ``ql [TB, 1]``
    → ``[TB, ns]``. Flattens slots into rows and defers to the shared
    ``fused_descent._cmp3`` — one definition of the parity-critical padded
    compare (bytes first, length tie-break)."""
    TB, ns, L = kb.shape
    qb_rows = jnp.broadcast_to(qb[:, None, :], (TB, ns, L)).reshape(-1, L)
    ql_rows = jnp.broadcast_to(ql, (TB, ns)).reshape(-1, 1)
    c3 = _cmp3(kb.reshape(-1, L), kl.reshape(-1, 1), qb_rows, ql_rows)
    return c3.reshape(TB, ns)


def _rank_among(kb, kl, emit):
    """Ascending rank of each emitted slot among its row's emitted slots.

    rank(j) = #{emitted i : key_i < key_j}, computed over order-preserving
    packed words. Tree keys are unique, so the strict compare reproduces the
    jnp reference's stable lexsort order exactly. [TB, ns] int32.
    """
    words = pack_words_j(kb)                      # [TB, ns, W]
    TB, ns = emit.shape
    lt = jnp.zeros((TB, ns, ns), bool)
    eq = jnp.ones((TB, ns, ns), bool)
    for w in range(words.shape[-1]):
        aw = words[..., w]
        lt = lt | (eq & (aw[:, :, None] < aw[:, None, :]))
        eq = eq & (aw[:, :, None] == aw[:, None, :])
    lt = lt | (eq & (kl[:, :, None] < kl[:, None, :]))
    return jnp.sum((emit[:, :, None] & lt).astype(jnp.int32), axis=1)


def _merge_emit(out_kid, out_val, emitted, kid, val, emit, rank,
                max_items: int):
    """Scatter-free merge of one leaf's emitted slots into the output block.

    The slot with in-row rank r lands at column ``emitted + r`` through a
    one-hot reduction over the slot axis — destinations are unique per row,
    columns ≥ ``max_items`` fall off the iota and are dropped, matching the
    jnp reference's scratch-column clamp.
    """
    TB, ns = kid.shape
    dstpos = emitted + rank                        # [TB, ns]
    cols = _iota((TB, ns, max_items), 2)
    onehot = emit[:, :, None] & (dstpos[:, :, None] == cols)
    hit = onehot.any(axis=1)                       # [TB, max_items]
    out_kid = jnp.where(
        hit, jnp.sum(jnp.where(onehot, kid[:, :, None], 0), axis=1), out_kid)
    out_val = jnp.where(
        hit, jnp.sum(jnp.where(onehot, val[:, :, None],
                               jnp.zeros((), out_val.dtype)), axis=1), out_val)
    emitted = jnp.minimum(
        emitted + jnp.sum(emit.astype(jnp.int32), axis=-1, keepdims=True),
        max_items)
    return out_kid, out_val, emitted


def _kernel(*refs, n_levels: int, fs: int, ns: int, L: int, max_items: int,
            collect_stats: bool):
    it = iter(refs)
    qb = next(it)[...]                        # [TB, L] u8
    ql = next(it)[...]                        # [TB, 1] i32
    knum_a = next(it)[...]                    # [n_levels, C]
    plen_a = next(it)[...]
    prefix_a = next(it)[...]                  # [n_levels, C, L]
    feats_a = next(it)[...]                   # [n_levels, C, fs, ns]
    child_a = next(it)[...]                   # [n_levels, C, ns]
    anch_a = next(it)[...]
    key_bytes = next(it)[...]                 # [KC, L] u8
    key_lens = next(it)[...][:, 0]            # [KC]
    leaf_high = next(it)[...][:, 0]           # [LC]
    leaf_next = next(it)[...][:, 0]
    leaf_keyid = next(it)[...]                # [LC, ns] i32
    leaf_val = next(it)[...]                  # [LC, ns]
    leaf_occ = next(it)[...]                  # [LC, ns] u8
    leaf_ordered = next(it)[...][:, 0]        # [LC] u8
    kid_ref = next(it)
    val_ref = next(it)
    emitted_ref = next(it)
    rearr_ref = next(it) if collect_stats else None

    TB = qb.shape[0]
    dump = leaf_next.shape[0] - 1             # scratch row = retired lane

    # ---------------- descent + sibling hop (shared with fused_descent) ---
    nid, _, _ = descend_levels(
        qb, ql, knum_a, plen_a, prefix_a, feats_a, child_a, anch_a,
        key_bytes, key_lens, n_levels=n_levels, fs=fs, ns=ns, L=L,
        collect_stats=False)
    nid, _ = sibling_hop(nid, qb, ql, key_bytes, key_lens,
                         leaf_high, leaf_next)

    out_kid = jnp.full((TB, max_items), -1, jnp.int32)
    out_val = jnp.zeros((TB, max_items), leaf_val.dtype)
    emitted = jnp.zeros((TB, 1), jnp.int32)

    def rows_at(cur):
        kid = jnp.take(leaf_keyid, cur, axis=0)           # [TB, ns]
        val = jnp.take(leaf_val, cur, axis=0)
        occ = jnp.take(leaf_occ, cur, axis=0) != 0
        return kid, val, occ

    def keys_at(kid, occ):
        kd = jnp.maximum(kid, 0).reshape(-1)
        kb = jnp.take(key_bytes, kd, axis=0).reshape(TB, ns, L)
        kl = jnp.where(occ, jnp.take(key_lens, kd).reshape(TB, ns), 0)
        return kb, kl

    # ---------------- hop 0 (peeled): in-kernel start-key compare ---------
    # the only hop that gathers key bytes unconditionally (the compare
    # needs them); the sort branch reuses the same gather
    cur = nid
    kid, val, occ = rows_at(cur)
    kb, kl = keys_at(kid, occ)
    dirty = jnp.take(leaf_ordered, cur) == 0
    emit = occ & (_cmp3_slots(kb, kl, qb, ql) >= 0)

    rank = jax.lax.cond(
        ~dirty.any(),
        lambda _: jnp.cumsum(emit.astype(jnp.int32), axis=-1) - 1,
        lambda _: _rank_among(kb, kl, emit),
        None)
    out_kid, out_val, emitted = _merge_emit(out_kid, out_val, emitted,
                                            kid, val, emit, rank, max_items)
    nxt = jnp.take(leaf_next, cur)
    cur = jnp.where((nxt >= 0) & (emitted[:, 0] < max_items), nxt, dump)
    rearr = dirty.astype(jnp.int32)[:, None] if collect_stats else None

    # ---------------- hops 1+: early-exit chain walk ----------------------
    # every key of an active leaf emits (ascending chain); the fast path
    # (all active leaves ordered) touches no key bytes at all
    def w_cond(c):
        return (c[0] != dump).any()

    def w_body(c):
        if collect_stats:
            cur, emitted, out_kid, out_val, rearr = c
        else:
            cur, emitted, out_kid, out_val = c
        active = cur != dump
        kid, val, occ = rows_at(cur)
        emit = occ & active[:, None]
        dirty = active & (jnp.take(leaf_ordered, cur) == 0)

        def _ordered(_):
            return jnp.cumsum(emit.astype(jnp.int32), axis=-1) - 1

        def _rearranged(_):
            kb, kl = keys_at(kid, occ)
            return _rank_among(kb, kl, emit)

        rank = jax.lax.cond(~dirty.any(), _ordered, _rearranged, None)
        out_kid, out_val, emitted = _merge_emit(
            out_kid, out_val, emitted, kid, val, emit, rank, max_items)
        nxt = jnp.take(leaf_next, cur)
        cur = jnp.where(active & (nxt >= 0) & (emitted[:, 0] < max_items),
                        nxt, dump)
        if collect_stats:
            return cur, emitted, out_kid, out_val, \
                rearr + dirty.astype(jnp.int32)[:, None]
        return cur, emitted, out_kid, out_val

    carry = (cur, emitted, out_kid, out_val)
    if collect_stats:
        carry = carry + (rearr,)
    final = jax.lax.while_loop(w_cond, w_body, carry)
    cur, emitted, out_kid, out_val = final[:4]

    kid_ref[...] = out_kid
    val_ref[...] = out_val
    emitted_ref[...] = emitted
    if collect_stats:
        rearr_ref[...] = final[4]


@functools.partial(
    jax.jit, static_argnames=("tile_b", "n_levels", "fs", "ns", "max_items",
                              "collect_stats", "interpret"))
def fused_scan_kernel(qb, ql, stacked_arrays, key_bytes, key_lens,
                      leaf_arrays, tile_b: int, n_levels: int, fs: int,
                      ns: int, max_items: int, collect_stats: bool,
                      interpret: bool):
    """One pallas_call for descent + sibling hop + leaf-chain range scan.

    ``stacked_arrays = (knum, plen, prefix, features, children, anchors)``
    stacked over levels; ``leaf_arrays = (high, next, keyid, val, occ_u8,
    ordered_u8)``. B must be a multiple of tile_b (ops.py pads). Queries
    tile over the grid; tree state rides as whole-array VMEM blocks, so
    this compiles for the interpreter only (see the module docstring).
    """
    B, L = qb.shape
    assert B % tile_b == 0, (B, tile_b)
    grid = (B // tile_b,)

    tiled = lambda blk: pl.BlockSpec(
        blk, lambda i: (i,) + (0,) * (len(blk) - 1), memory_space=pltpu.VMEM)
    whole = lambda a: pl.BlockSpec(
        a.shape, lambda i, _nd=a.ndim: (0,) * _nd, memory_space=pltpu.VMEM)

    tree_state = list(stacked_arrays) + [key_bytes, key_lens] + list(leaf_arrays)
    inputs = [qb, ql] + tree_state
    in_specs = [tiled((tile_b, L)), tiled((tile_b, 1))]
    in_specs += [whole(a) for a in tree_state]

    val_dtype = leaf_arrays[3].dtype
    out_shape = [jax.ShapeDtypeStruct((B, max_items), jnp.int32),
                 jax.ShapeDtypeStruct((B, max_items), val_dtype),
                 jax.ShapeDtypeStruct((B, 1), jnp.int32)]
    out_specs = [tiled((tile_b, max_items)), tiled((tile_b, max_items)),
                 tiled((tile_b, 1))]
    if collect_stats:
        out_shape.append(jax.ShapeDtypeStruct((B, 1), jnp.int32))
        out_specs.append(tiled((tile_b, 1)))

    kern = functools.partial(_kernel, n_levels=n_levels, fs=fs, ns=ns, L=L,
                             max_items=max_items, collect_stats=collect_stats)
    return pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret)(*inputs)
