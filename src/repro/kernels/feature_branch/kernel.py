"""Pallas TPU kernel for FB+-tree feature comparison (paper Fig. 6 lines 7-19).

Hardware adaptation (DESIGN.md §2): AVX-512 compares 64 anchor bytes per
instruction with 64-bit scalar mask registers; the TPU VPU operates on
(sublane, lane) = (8, 128) vector tiles. We therefore keep per-anchor masks
*vectorized* over the lane dimension (one lane per anchor) and replace the
paper's LZCNT/TZCNT bit tricks (`index_least1`, `countl_zero`) with masked
iota min/max reductions — cheaper than any cross-lane bit packing on TPU.
The natural TPU node size is ns=128 (one full lane row); ns=64 (the paper's
AVX-512 choice) half-fills lanes and is supported for faithfulness.

Inputs are per-query gathered node rows (the gather runs in XLA, which on TPU
lowers to efficient dynamic-slice streams; the kernel owns the compare/reduce
hot loop):
  feats [B, fs, ns] uint8   transposed feature rows
  qfeat [B, fs]     uint8   query bytes following each node's common prefix
  knum  [B, 1]      int32   anchors per node
  pcmp  [B, 1]      int32   3-way prefix compare result

Outputs:
  idx      [B, 1] int32  resolved child index (valid where resolved)
  resolved [B, 1] int32  1 = branch decided without suffix binary search
  run_lo/run_hi [B,1]    surviving equal-run bounds for the fallback search
  rounds   [B, 1] int32  feature rows consumed (paper-comparable counter) —
                         omitted when ``collect_stats=False`` (the stats-free
                         hot path compiles without the counter accumulator)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE_B = 256


def auto_tile(B: int, cap: int, floor: int = 8) -> int:
    """Largest power-of-two tile ≤ min(B, cap), floored at ``floor``.

    A B=32 serving batch gets tile_b=32 (pad-free) instead of being padded
    to the 256/512 throughput tile; odd batches pad only to the next tile
    boundary below ``cap``.
    """
    t = floor
    while t * 2 <= min(B, cap):
        t *= 2
    return t


def feature_compare_rounds(feats, qfeat, knum, pcmp, *, fs: int, ns: int,
                           collect_stats: bool):
    """The in-kernel feature-comparison round loop (paper Fig. 6 l.7-19),
    [TB, 1]-keepdims masked-iota formulation. SHARED between the per-level
    kernel below and the fused whole-descent kernel
    (``kernels/fused_descent``) — the parity contract requires both to be
    bit-identical, so there is exactly one definition.

    Returns ``(idx, resolved, run_lo, run_hi, rounds)``, all int32
    ``[TB, 1]``; the prefix/trivial overrides are folded in (``resolved``
    is 1 also where ``pcmp != 0`` or ``knum <= 1``), so ``resolved == 0``
    is exactly the billed suffix-fallback lane set and ``rounds`` is
    already zeroed on trivial nodes. ``rounds`` stays all-zero (and costs
    nothing) when ``collect_stats`` is off.

    Every compare runs on int32 and every mask is an int32 0/1 column:
    Mosaic refuses both the i8 -> i1 truncation a uint8 compare lowers to
    and the relayout of a broadcast i1 mask, so no bool array is carried.
    """
    TB = feats.shape[0]
    feats = feats.astype(jnp.int32)
    qfeat = qfeat.astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (TB, ns), 1)
    eq = jnp.where(lane < knum, 1, 0)           # [TB, ns] surviving anchors
    resolved = jnp.zeros((TB, 1), jnp.int32)
    idx = jnp.zeros((TB, 1), jnp.int32)
    rounds = jnp.zeros((TB, 1), jnp.int32)
    kmax = jnp.maximum(knum - 1, 0)

    for fid in range(fs):                       # unrolled: fs is 2..8
        qb = qfeat[:, fid:fid + 1]              # [TB, 1]
        frow = feats[:, fid, :]                 # [TB, ns]
        m = jnp.where(frow == qb, eq, 0)
        none_eq = 1 - jnp.max(m, axis=-1, keepdims=True)
        less = jnp.where(frow < qb, eq, 0)
        lo = jnp.min(jnp.where(eq != 0, lane, ns), axis=-1, keepdims=True)
        cnt_less = jnp.sum(less, axis=-1, keepdims=True)
        res_idx = jnp.clip(lo + cnt_less - 1, 0, kmax)
        newly = none_eq * (1 - resolved)
        idx = jnp.where(newly != 0, res_idx, idx)
        if collect_stats:
            rounds = rounds + (1 - resolved)
        resolved = jnp.maximum(resolved, none_eq)
        eq = jnp.where(resolved != 0, eq, m)

    run_lo = jnp.min(jnp.where(eq != 0, lane, ns), axis=-1, keepdims=True)
    run_hi = jnp.max(jnp.where(eq != 0, lane, -1), axis=-1, keepdims=True)

    idx = jnp.where(pcmp < 0, 0, idx)
    idx = jnp.where(pcmp > 0, kmax, idx)
    resolved = jnp.where(pcmp != 0, 1, resolved)
    trivial = knum <= 1
    idx = jnp.where(trivial, 0, idx)
    resolved = jnp.where(trivial, 1, resolved)
    if collect_stats:
        rounds = jnp.where(trivial, 0, rounds)
    return idx, resolved, run_lo, run_hi, rounds


def _kernel(feats_ref, qfeat_ref, knum_ref, pcmp_ref, *out_refs, fs: int,
            ns: int, collect_stats: bool):
    idx, resolved, run_lo, run_hi, rounds = feature_compare_rounds(
        feats_ref[...], qfeat_ref[...], knum_ref[...], pcmp_ref[...],
        fs=fs, ns=ns, collect_stats=collect_stats)
    out_refs[0][...] = idx
    out_refs[1][...] = resolved
    out_refs[2][...] = run_lo
    out_refs[3][...] = run_hi
    if collect_stats:
        out_refs[4][...] = rounds


@functools.partial(jax.jit,
                   static_argnames=("tile_b", "interpret", "collect_stats"))
def feature_branch_kernel(feats, qfeat, knum, pcmp, tile_b: int = DEFAULT_TILE_B,
                          *, interpret: bool, collect_stats: bool = True):
    """B must be a multiple of tile_b (ops.py pads). With
    ``collect_stats=False`` the rounds output (and its in-kernel
    accumulator) is dropped — 4 outputs instead of 5."""
    B, fs, ns = feats.shape
    assert B % tile_b == 0, (B, tile_b)
    grid = (B // tile_b,)
    n_out = 5 if collect_stats else 4
    out_sds = [jax.ShapeDtypeStruct((B, 1), jnp.int32)] * n_out
    kern = functools.partial(_kernel, fs=fs, ns=ns,
                             collect_stats=collect_stats)
    vec = lambda blk: pl.BlockSpec(blk, lambda i: (i,) + (0,) * (len(blk) - 1),
                                   memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[vec((tile_b, fs, ns)), vec((tile_b, fs)),
                  vec((tile_b, 1)), vec((tile_b, 1))],
        out_specs=[vec((tile_b, 1))] * n_out,
        out_shape=out_sds,
        interpret=interpret,
    )(feats, qfeat, knum, pcmp)
