"""Jitted wrapper: GQA layout handling + custom-vjp backward (recompute).

``flash_sdpa`` is a drop-in for models.attention.sdpa: it flattens
(batch, kv-head, rep) onto one grid axis, repeats kv per group, pads S/T to
block multiples, and calls the Pallas kernel (compiled on a TPU,
interpreted on the CPU test backend: ``kernels.interpret_mode``).
Backward recomputes attention with the jnp flash path (standard
flash-attention recompute strategy — no tile residuals are stored).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.attention import MaskSpec, _sdpa_flash

from .. import interpret_mode
from .kernel import flash_attention_kernel


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_sdpa(q, k, v, mask: MaskSpec, n_rep: int, scale: float):
    """q [B,S,H,hd], k/v [B,T,Hk,hv] -> [B,S,H*hv]."""
    return _flash_fwd_impl(q, k, v, mask, n_rep, scale)


def _flash_fwd_impl(q, k, v, mask, n_rep, scale, block: int = 512):
    B, S, H, hd = q.shape
    T, Hk = k.shape[1], k.shape[2]
    hv = v.shape[-1]
    bq = min(block, max(128, S))
    bk = min(block, max(128, T))
    Sp = -(-S // bq) * bq
    Tp = -(-T // bk) * bk
    qf = jnp.pad(q, [(0, 0), (0, Sp - S), (0, 0), (0, 0)])
    kf = jnp.pad(k, [(0, 0), (0, Tp - T), (0, 0), (0, 0)])
    vf = jnp.pad(v, [(0, 0), (0, Tp - T), (0, 0), (0, 0)])
    # [B,S,Hk,rep,hd] -> [B*Hk*rep, S, hd]; kv repeated across rep
    qf = qf.reshape(B, Sp, Hk, n_rep, hd).transpose(0, 2, 3, 1, 4) \
           .reshape(B * Hk * n_rep, Sp, hd)
    kf = jnp.repeat(kf.transpose(0, 2, 1, 3)[:, :, None], n_rep, axis=2) \
           .reshape(B * Hk * n_rep, Tp, hd)
    vf = jnp.repeat(vf.transpose(0, 2, 1, 3)[:, :, None], n_rep, axis=2) \
           .reshape(B * Hk * n_rep, Tp, hv)
    out = flash_attention_kernel(
        qf, kf, vf, scale=scale, kv_len=T,
        causal=(mask.kind == "causal"), window=mask.window,
        prefix_len=mask.prefix_len, block_q=bq, block_k=bk,
        interpret=interpret_mode())
    out = out.reshape(B, Hk, n_rep, Sp, hv).transpose(0, 3, 1, 2, 4)
    return out[:, :S].reshape(B, S, Hk * n_rep * hv)


def _fwd(q, k, v, mask, n_rep, scale):
    return _flash_fwd_impl(q, k, v, mask, n_rep, scale), (q, k, v)


def _bwd(mask, n_rep, scale, res, g):
    q, k, v = res
    # recompute-based backward through the jnp flash path (identical math)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _sdpa_flash(q_, k_, v_, mask, n_rep, scale),
        q, k, v)
    return vjp(g)


flash_sdpa.defvjp(_fwd, _bwd)
