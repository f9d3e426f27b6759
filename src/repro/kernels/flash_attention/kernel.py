"""Pallas TPU flash-attention forward kernel (the LM-side hot spot).

Why a kernel: the XLA scan-based flash path (models/attention._sdpa_flash)
bounds *peak* memory but each (q-tile × kv-tile) logits block still
round-trips HBM (dot outputs materialize) — the dry-run's §Roofline shows
attention-tile traffic dominating the 32k-prefill memory term. Pallas keeps
the [block_q, block_k] tile in VMEM across the dot → online-softmax → dot
chain, so HBM traffic reduces to the q/k/v/out streams.

Grid: (batch×heads, n_q_blocks, n_kv_blocks) with kv innermost; the carry
(m, l, acc) lives in VMEM scratch across the kv sweep (standard
flash-attention-2 schedule on the MXU).

Masking: causal / window / prefix-LM computed from iotas per tile, same
MaskSpec semantics as the jnp paths. Padded kv positions are masked via the
`kv_len` scalar. Validated in interpret mode against ref.py
(= models.attention._sdpa_small oracle); on-TPU execution uses the same
BlockSpecs with interpret=False.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, n_kv: int,
            kv_len: int, causal: bool, window: int, prefix_len: int,
            q_off_mult: int):
    """One (bh, iq, jk) grid step; kv (axis 2) is the innermost loop."""
    jk = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                  # [block_q, hd]
    k = k_ref[0]                                  # [block_k, hd]
    v = v_ref[0]                                  # [block_k, hv]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [bq, bk]

    qidx = iq * block_q * q_off_mult + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kidx = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kidx < kv_len
    if causal:
        cm = kidx <= qidx
        if window:
            cm &= kidx > qidx - window
        if prefix_len:
            cm |= kidx < prefix_len
        mask &= cm
    s = jnp.where(mask, s, NEG)

    m_prev = m_scr[...]                           # [bq, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(jk == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_q", "block_k", "causal",
                              "window", "prefix_len", "kv_len", "interpret"))
def flash_attention_kernel(q, k, v, *, scale: float, kv_len: int,
                           causal: bool = True, window: int = 0,
                           prefix_len: int = 0, block_q: int = 512,
                           block_k: int = 512, interpret: bool):
    """q [BH, S, hd], k/v [BH, T, hv] (heads pre-flattened, kv pre-repeated,
    S and T padded to block multiples). Returns [BH, S, hv]."""
    BH, S, hd = q.shape
    T = k.shape[1]
    hv = v.shape[-1]
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    n_q, n_kv = S // block_q, T // block_k
    grid = (BH, n_q, n_kv)
    kern = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k, n_kv=n_kv,
        kv_len=kv_len, causal=causal, window=window, prefix_len=prefix_len,
        q_off_mult=1)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, hv), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, hv), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, S, hv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
