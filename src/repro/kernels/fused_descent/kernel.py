"""Pallas TPU kernel: the WHOLE root→leaf descent in one launch.

The per-level engine (``kernels/feature_branch``) relaunches a kernel per
inner level and re-gathers node rows through XLA between launches; the
level-synchronous batched-descent designs (BS-tree, FPGA level-wise batch
search) show the win comes from keeping the descent resident. This kernel
tiles the *query batch* over the grid and loops the levels **inside** the
kernel body (unrolled — ``n_levels`` is static):

  per level-step: gather the tile's node rows (knum/plen/prefix/features/
  children/anchors) from the stacked ``[n_levels, C_max, ...]`` pytree into
  VMEM once, run the prefix compare + feature-comparison rounds (same
  masked-iota formulation as ``feature_branch``), then a suffix binary
  search clipped to the widest *surviving* equal run (a ``while_loop``, not
  a fixed ``ns.bit_length()`` unroll — lanes decided by prefix/feature/
  trivial nodes have their runs zeroed and cost nothing).

Epilogues, fused behind the same launch:
  * blink-style sibling hop (paper §4.3, bounded ``N_HOPS``);
  * the hashtag leaf probe (paper Fig. 6 lines 30-42) incl. full-key
    verification against the key pool — ``traverse_probe`` becomes ONE
    kernel launch instead of (n_levels + 1) launches plus XLA glue.

Static ``collect_stats`` drops every counter accumulator and stats output
from the compiled kernel; leaf ids / paths / probe results are bit-identical
either way (the parity suite pins this).

Tile sizing is ns-aware: per-tile VMEM scales with ``ns`` (feature rows,
anchor gathers, the [TB, ns, L] probe verify), so the tile cap halves from
256 at the paper's ns=64 to 128 at the TPU-natural ns=128
(:func:`descent_tile`).

This kernel runs only in the Pallas interpreter (the CPU test backend). It
does not compile for a TPU: Mosaic refuses its in-kernel row gathers by
per-lane node id ("Only 2D gather is supported"), and the tree state rides
as whole-array VMEM blocks, which a deployment-sized tree (about 3 GB at
2^25 keys) can never fit. ``core.traverse`` therefore refuses the
``"fused"`` backend on a TPU; a chip version keeps the tree in HBM and
fetches rows per lane by DMA (ROADMAP A5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..feature_branch.kernel import auto_tile, feature_compare_rounds

N_HOPS = 2          # bounded sibling hops; matches core.branch._SIBLING_HOPS
LANE_BUDGET = 32768  # tile_b * ns lanes held per gathered node-row block


def descent_tile(B: int, ns: int, floor: int = 8) -> int:
    """ns-aware tile: largest power of two ≤ B within the lane budget.

    ns=64 → cap 512, ns=128 → cap 256; a B=32 serving batch still gets a
    pad-free 32-row tile (the shared :func:`auto_tile` rule, with the cap
    derived from ``ns`` instead of a fixed default).
    """
    return auto_tile(B, max(floor, LANE_BUDGET // max(ns, 1)), floor)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _cmp3(ab, al, bb, bl):
    """3-way padded-key compare with length tie-break. [TB, L] × [TB, 1]."""
    TB, L = ab.shape
    diff = ab.astype(jnp.int32) - bb.astype(jnp.int32)
    nzm = diff != 0
    anynz = nzm.any(axis=-1, keepdims=True)
    pos = _iota((TB, L), 1)
    first_idx = jnp.min(jnp.where(nzm, pos, L), axis=-1, keepdims=True)
    first = jnp.take_along_axis(diff, jnp.minimum(first_idx, L - 1), axis=-1)
    return jnp.where(anynz, jnp.sign(first), jnp.sign(al - bl))


def _prefix_cmp(qb, prefix, plen):
    """First-diff compare of qb vs prefix over the first plen bytes."""
    TB, L = qb.shape
    pos = _iota((TB, L), 1)
    m = pos < plen
    diff = (qb.astype(jnp.int32) - prefix.astype(jnp.int32)) * m
    nzm = diff != 0
    anynz = nzm.any(axis=-1, keepdims=True)
    first_idx = jnp.min(jnp.where(nzm, pos, L), axis=-1, keepdims=True)
    first = jnp.take_along_axis(diff, jnp.minimum(first_idx, L - 1), axis=-1)
    return jnp.where(anynz, jnp.sign(first), 0)


def descend_levels(qb, ql, knum_a, plen_a, prefix_a, feats_a, child_a,
                   anch_a, key_bytes, key_lens, *, n_levels: int, fs: int,
                   ns: int, L: int, collect_stats: bool):
    """The in-kernel root→leaf descent over the stacked level arrays.

    SHARED between the fused descent kernel below and the fused range-scan
    kernel (``kernels/fused_scan``) — the parity contract (DESIGN.md §3)
    requires both to resolve bit-identical leaves, so there is exactly one
    definition of the level loop. Returns ``(nid, path_cols, stat_accs)``
    where ``stat_accs = (fr_acc, sb_acc, kc_acc, li_acc)`` (all-zero
    ``[TB, 1]`` columns when ``collect_stats`` is off — the accumulator
    arithmetic is never traced then).
    """
    TB = qb.shape[0]
    lines_per_row = max(1, ns // 64)
    kw_lines = (ql + 63) // 64                # [TB, 1]
    z = jnp.zeros((TB, 1), jnp.int32)
    fr_acc, sb_acc, kc_acc, li_acc = z, z, z, z

    nid = jnp.zeros((TB,), jnp.int32)         # root = node 0 of level 0
    path_cols = []

    for l in range(n_levels):
        path_cols.append(nid)
        kn = jnp.take(knum_a[l], nid)[:, None]            # [TB, 1]
        pl_ = jnp.take(plen_a[l], nid)[:, None]
        prefix = jnp.take(prefix_a[l], nid, axis=0)       # [TB, L]
        feats = jnp.take(feats_a[l], nid, axis=0)         # [TB, fs, ns]
        childs = jnp.take(child_a[l], nid, axis=0)        # [TB, ns]
        anch = jnp.take(anch_a[l], nid, axis=0)

        pcmp = _prefix_cmp(qb, prefix, pl_)               # [TB, 1]
        qpos = pl_ + _iota((TB, fs), 1)
        qfeat = jnp.take_along_axis(qb, jnp.clip(qpos, 0, L - 1), axis=-1)
        qfeat = jnp.where(qpos < L, qfeat, 0).astype(jnp.uint8)

        # shared with the per-level kernel — one definition of the
        # parity-critical compare loop
        idx, resolved, run_lo, run_hi, rounds = feature_compare_rounds(
            feats, qfeat, kn, pcmp, fs=fs, ns=ns,
            collect_stats=collect_stats)
        kmax = jnp.maximum(kn - 1, 0)
        trivial = kn <= 1
        need_bs = resolved == 0               # = billed: excl. pcmp/trivial

        # suffix binary search over the surviving run, width-bounded
        lo_b = jnp.where(need_bs, run_lo, 0)
        hi_b = jnp.where(need_bs, run_hi + 1, 0)

        def bs_cond(c):
            return (c[0] < c[1]).any()

        def bs_body(c, anch=anch):
            lo_b, hi_b, kc = c
            active = lo_b < hi_b
            mid = jnp.clip((lo_b + hi_b) // 2, 0, ns - 1)
            aid = jnp.take_along_axis(anch, mid, axis=-1)   # [TB, 1]
            aid_safe = jnp.maximum(aid[:, 0], 0)
            akb = jnp.take(key_bytes, aid_safe, axis=0)     # [TB, L]
            akl = jnp.take(key_lens, aid_safe)[:, None]
            c3 = _cmp3(akb, akl, qb, ql)                    # anchor vs query
            go_right = c3 <= 0
            lo_b = jnp.where(active & go_right, mid + 1, lo_b)
            hi_b = jnp.where(active & ~go_right, mid, hi_b)
            if collect_stats:
                kc = kc + active.astype(jnp.int32)
            return lo_b, hi_b, kc

        lo_b, _, key_cmp = jax.lax.while_loop(bs_cond, bs_body, (lo_b, hi_b, z))
        bs_idx = jnp.clip(lo_b - 1, 0, kmax)
        idx = jnp.where(need_bs, bs_idx, idx)
        child = jnp.take_along_axis(childs, idx, axis=-1)   # [TB, 1]
        nid = child[:, 0]

        if collect_stats:
            nz_ = lambda x: jnp.where(trivial, 0, x)
            fr = rounds                       # already trivial-zeroed
            kc = nz_(key_cmp)
            fr_acc = fr_acc + fr
            sb_acc = sb_acc + need_bs.astype(jnp.int32)
            kc_acc = kc_acc + kc
            li_acc = li_acc + nz_(1 + fr * lines_per_row
                                  + kc * (1 + kw_lines) + 1)

    return nid, path_cols, (fr_acc, sb_acc, kc_acc, li_acc)


def sibling_hop(nid, qb, ql, key_bytes, key_lens, leaf_high, leaf_next):
    """Blink-style sibling-hop epilogue (§4.3), ``N_HOPS``-bounded — shared
    with the fused range-scan kernel. Returns ``(nid, hops [TB, 1])``."""
    hops = jnp.zeros((qb.shape[0], 1), jnp.int32)
    for _ in range(N_HOPS):
        hk = jnp.take(leaf_high, nid)[:, None]              # [TB, 1]
        nxt = jnp.take(leaf_next, nid)[:, None]
        has_hk = hk >= 0
        hk_safe = jnp.maximum(hk[:, 0], 0)
        hkb = jnp.take(key_bytes, hk_safe, axis=0)
        hkl = jnp.take(key_lens, hk_safe)[:, None]
        c3 = _cmp3(qb, ql, hkb, hkl)                        # query vs high key
        must = has_hk & (c3 >= 0) & (nxt >= 0)
        nid = jnp.where(must[:, 0], nxt[:, 0], nid)
        hops = hops + must.astype(jnp.int32)
    return nid, hops


def _kernel(*refs, n_levels: int, fs: int, ns: int, L: int,
            sibling_check: bool, with_probe: bool, collect_stats: bool):
    it = iter(refs)
    qb = next(it)[...]                        # [TB, L] u8
    ql = next(it)[...]                        # [TB, 1] i32
    qtag = next(it)[...] if with_probe else None   # [TB, 1] u8
    knum_a = next(it)[...]                    # [n_levels, C]
    plen_a = next(it)[...]
    prefix_a = next(it)[...]                  # [n_levels, C, L]
    feats_a = next(it)[...]                   # [n_levels, C, fs, ns]
    child_a = next(it)[...]                   # [n_levels, C, ns]
    anch_a = next(it)[...]
    key_bytes = next(it)[...]                 # [KC, L] u8
    key_lens = next(it)[...][:, 0]            # [KC]
    if sibling_check:
        leaf_high = next(it)[...][:, 0]       # [LC]
        leaf_next = next(it)[...][:, 0]
    if with_probe:
        leaf_tags = next(it)[...]             # [LC, ns] u8
        leaf_occ = next(it)[...]              # [LC, ns] u8
        leaf_keyid = next(it)[...]            # [LC, ns] i32
        leaf_val = next(it)[...]              # [LC, ns]
    leaf_ref = next(it)
    path_ref = next(it)
    if with_probe:
        found_ref, slot_ref, val_ref = next(it), next(it), next(it)
    if collect_stats:
        fr_ref, sb_ref, kc_ref, li_ref, sh_ref = (
            next(it), next(it), next(it), next(it), next(it))
        tc_ref = next(it) if with_probe else None

    TB = qb.shape[0]
    lane = _iota((TB, ns), 1)
    z = jnp.zeros((TB, 1), jnp.int32)

    # ---------------- descent: all inner levels, resident in-kernel --------
    nid, path_cols, (fr_acc, sb_acc, kc_acc, li_acc) = descend_levels(
        qb, ql, knum_a, plen_a, prefix_a, feats_a, child_a, anch_a,
        key_bytes, key_lens, n_levels=n_levels, fs=fs, ns=ns, L=L,
        collect_stats=collect_stats)

    # ---------------- epilogue: blink-style sibling hop (§4.3) ------------
    hops = z
    if sibling_check:
        nid, hops = sibling_hop(nid, qb, ql, key_bytes, key_lens,
                                leaf_high, leaf_next)

    leaf_ref[...] = nid[:, None]
    path_ref[...] = jnp.stack(path_cols, axis=-1)           # [TB, n_levels]

    # ---------------- epilogue: hashtag leaf probe (Fig. 6 l.30-42) -------
    if with_probe:
        tags = jnp.take(leaf_tags, nid, axis=0)             # [TB, ns]
        occ = jnp.take(leaf_occ, nid, axis=0)
        cand = (tags == qtag) & (occ != 0)
        kid = jnp.take(leaf_keyid, nid, axis=0)
        # candidate-by-candidate verification (mirrors
        # core.leaf.verify_candidates): one [TB, L] key gather per round,
        # trip count = deepest candidate rank an unmatched lane needs
        crank = jnp.cumsum(cand.astype(jnp.int32), axis=-1) - 1
        n_cand = jnp.sum(cand.astype(jnp.int32), axis=-1, keepdims=True)

        def v_cond(c):
            checked, found, _ = c
            return ((~found) & (checked < n_cand)).any()

        def v_body(c):
            checked, found, slot = c
            active = (~found) & (checked < n_cand)
            is_k = cand & (crank == checked)
            s = jnp.min(jnp.where(is_k, lane, ns), axis=-1, keepdims=True)
            s = jnp.where(active, jnp.minimum(s, ns - 1), 0)
            kd = jnp.maximum(jnp.take_along_axis(kid, s, axis=-1)[:, 0], 0)
            akb = jnp.take(key_bytes, kd, axis=0)           # [TB, L]
            akl = jnp.take(key_lens, kd)[:, None]
            eqk = ((akb == qb).all(-1, keepdims=True) & (akl == ql)
                   & active)
            slot = jnp.where(eqk, s, slot)
            return checked + active.astype(jnp.int32), found | eqk, slot

        _, found, slot = jax.lax.while_loop(
            v_cond, v_body, (z, jnp.zeros((TB, 1), jnp.bool_), z))
        vals = jnp.take(leaf_val, nid, axis=0)
        val = jnp.take_along_axis(vals, slot, axis=-1)
        found_ref[...] = found.astype(jnp.int32)
        slot_ref[...] = slot
        val_ref[...] = jnp.where(found, val, 0)

    if collect_stats:
        fr_ref[...] = fr_acc
        sb_ref[...] = sb_acc
        kc_ref[...] = kc_acc
        li_ref[...] = li_acc
        sh_ref[...] = hops
        if with_probe:
            tc_ref[...] = jnp.sum(cand.astype(jnp.int32), axis=-1,
                                  keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("tile_b", "n_levels", "fs", "ns",
                              "sibling_check", "with_probe", "collect_stats",
                              "interpret"))
def fused_descent_kernel(qb, ql, qtag, stacked_arrays, key_bytes, key_lens,
                         leaf_arrays, tile_b: int, n_levels: int, fs: int,
                         ns: int, sibling_check: bool, with_probe: bool,
                         collect_stats: bool, interpret: bool):
    """One pallas_call for descent (+ sibling hop + leaf probe).

    ``stacked_arrays = (knum, plen, prefix, features, children, anchors)``
    stacked over levels; ``leaf_arrays = (high, next)`` + ``(tags, occ_u8,
    keyid, val)`` when probing (pass ``()`` slices when a stage is off).
    B must be a multiple of tile_b (ops.py pads). Queries are tiled over the
    grid; tree state rides as whole-array VMEM blocks, so this compiles for
    the interpreter only (see the module docstring).
    """
    B, L = qb.shape
    assert B % tile_b == 0, (B, tile_b)
    grid = (B // tile_b,)

    tiled = lambda blk: pl.BlockSpec(
        blk, lambda i: (i,) + (0,) * (len(blk) - 1), memory_space=pltpu.VMEM)
    whole = lambda a: pl.BlockSpec(
        a.shape, lambda i, _nd=a.ndim: (0,) * _nd, memory_space=pltpu.VMEM)

    inputs = [qb, ql]
    in_specs = [tiled((tile_b, L)), tiled((tile_b, 1))]
    if with_probe:
        inputs.append(qtag)
        in_specs.append(tiled((tile_b, 1)))
    tree_state = list(stacked_arrays) + [key_bytes, key_lens] + list(leaf_arrays)
    inputs += tree_state
    in_specs += [whole(a) for a in tree_state]

    out_shape = [jax.ShapeDtypeStruct((B, 1), jnp.int32),        # leaf
                 jax.ShapeDtypeStruct((B, n_levels), jnp.int32)]  # path
    out_specs = [tiled((tile_b, 1)), tiled((tile_b, n_levels))]
    if with_probe:
        val_dtype = leaf_arrays[-1].dtype
        out_shape += [jax.ShapeDtypeStruct((B, 1), jnp.int32),
                      jax.ShapeDtypeStruct((B, 1), jnp.int32),
                      jax.ShapeDtypeStruct((B, 1), val_dtype)]
        out_specs += [tiled((tile_b, 1))] * 3
    if collect_stats:
        n_stats = 6 if with_probe else 5
        out_shape += [jax.ShapeDtypeStruct((B, 1), jnp.int32)] * n_stats
        out_specs += [tiled((tile_b, 1))] * n_stats

    kern = functools.partial(_kernel, n_levels=n_levels, fs=fs, ns=ns, L=L,
                             sibling_check=sibling_check,
                             with_probe=with_probe,
                             collect_stats=collect_stats)
    return pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret)(*inputs)
