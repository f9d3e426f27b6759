"""Pallas TPU kernel for hashtag leaf filtering (paper Fig. 6 lines 30-42).

``compare_equal(tags, tag) & bitmap`` over a whole lookup batch: one lane per
slot, candidates located with masked-iota reductions instead of TZCNT loops.
Exact key verification (line 37) needs data-dependent gathers from the key
pool and stays in XLA (see ops.py).

  tags [B, ns] u8, occ [B, ns] u8(0/1), qtag [B, 1] u8 ->
  cand [B, ns] i32 (0/1 mask), first [B, 1] i32 (ns if none),
  count [B, 1] i32

The bytes are widened to int32 before the compare and the mask stays int32:
Mosaic cannot relayout the broadcast i1 mask a uint8 compare produces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE_B = 512


def _kernel(tags_ref, occ_ref, qtag_ref, cand_ref, first_ref, count_ref, *,
            ns: int):
    tags = tags_ref[...].astype(jnp.int32)
    occ = occ_ref[...].astype(jnp.int32)
    qtag = qtag_ref[...].astype(jnp.int32)
    TB = tags.shape[0]
    cand = jnp.where(tags == qtag, occ, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (TB, ns), 1)
    first = jnp.min(jnp.where(cand != 0, lane, ns), axis=-1, keepdims=True)
    count = jnp.sum(cand, axis=-1, keepdims=True)
    cand_ref[...] = cand
    first_ref[...] = first
    count_ref[...] = count


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def leaf_probe_kernel(tags, occ, qtag, tile_b: int = DEFAULT_TILE_B, *,
                      interpret: bool):
    B, ns = tags.shape
    assert B % tile_b == 0
    vec = lambda blk: pl.BlockSpec(blk, lambda i: (i,) + (0,) * (len(blk) - 1),
                                   memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, ns=ns),
        grid=(B // tile_b,),
        in_specs=[vec((tile_b, ns)), vec((tile_b, ns)), vec((tile_b, 1))],
        out_specs=[vec((tile_b, ns)), vec((tile_b, 1)), vec((tile_b, 1))],
        out_shape=[jax.ShapeDtypeStruct((B, ns), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.int32)],
        interpret=interpret,
    )(tags, occ, qtag)
