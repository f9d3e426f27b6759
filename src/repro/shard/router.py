"""Replicated split-key router (DESIGN.md §7).

The router is the sharded tree's only global state: the per-shard minimum
keys from the build's balanced partition (``fbtree.sharded_partition``),
replicated to every dispatch site. Shard ``s`` owns the key range
``[split[s], split[s+1])``; shard 0 additionally owns everything below
``split[0]`` (so the router never rejects a key, mirroring how child 0 of
an inner node absorbs keys below ``anchors[0]``).

Routing uses the same packed-word compares the tree itself descends with
(``core.keys.pack_words_j``): split keys are packed once into the same
order-preserving int32 words at construction (by its numpy twin
``pack_words``), and :func:`route` resolves a query batch with one
``[B, S, W]`` vectorized 3-way compare — first differing word decides,
equal padded words fall back to the length tie-break, exactly
``core.keys.compare_padded``'s order at a quarter of the columns.

``ShardRouter`` is a NamedTuple of arrays (a pytree), so it rides through
``jax.jit`` as a traced input; the shard count is its shape. Its arrays
live on the host's CPU device: routed ops route every batch there, on the
host copy of the keys, so routing neither launches on nor waits for an
accelerator. JAX's CPU backend must therefore be present (on a TPU host
``JAX_PLATFORMS`` unset, or ``tpu,cpu``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import keys as K

__all__ = ["ShardRouter", "make_router", "route"]


class ShardRouter(NamedTuple):
    """Replicated routing table: one row per shard, ascending.

    ``split_bytes[s]`` / ``split_lens[s]`` are shard ``s``'s minimum key
    (kept in byte form so ``rebalance`` and repr/debugging can read them);
    ``split_words`` is the packed order-preserving int32 form
    :func:`route` compares against.
    """
    split_bytes: jnp.ndarray   # uint8 [S, L]
    split_lens: jnp.ndarray    # int32 [S]
    split_words: jnp.ndarray   # int32 [S, W] — pack_words(split_bytes)

    @property
    def n_shards(self) -> int:
        return int(self.split_bytes.shape[0])


def _host_device():
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "the shard router lives on the host's CPU device, and JAX has "
            "no CPU backend here: leave JAX_PLATFORMS unset or list cpu in "
            "it (e.g. JAX_PLATFORMS=tpu,cpu)") from e


def make_router(split_keys) -> ShardRouter:
    """Build a router from ``fbtree.sharded_partition``'s ``split_keys``
    (a sequence of ``(bytes_row, len)`` per shard, ascending), on the
    host's CPU device."""
    sb = np.stack([np.asarray(b, dtype=np.uint8) for b, _ in split_keys])
    sl = np.asarray([int(l) for _, l in split_keys], dtype=np.int32)
    return jax.device_put(
        ShardRouter(split_bytes=sb, split_lens=sl,
                    split_words=K.pack_words(sb)), _host_device())


@jax.jit
def route(router: ShardRouter, qb, ql) -> jnp.ndarray:
    """Owning shard id per query: ``int32 [B]``.

    ``owner[i]`` is the largest ``s`` with ``q_i >= split[s]`` (0 when the
    query sorts below every split key — shard 0's open left end). The
    compare is lexicographic over packed words with the length tie-break,
    identical in order to the byte compare the leaves use.
    """
    qw = K.pack_words_j(jnp.asarray(qb))               # [B, W]
    ql = jnp.asarray(ql).astype(jnp.int32)
    sw, sl = router.split_words, router.split_lens
    gt = (qw[:, None, :] > sw[None, :, :])             # [B, S, W]
    lt = (qw[:, None, :] < sw[None, :, :])
    d = gt.astype(jnp.int32) - lt.astype(jnp.int32)
    nz = d != 0
    idx = jnp.argmax(nz, axis=-1)                      # first differing word
    first = jnp.take_along_axis(d, idx[..., None], axis=-1)[..., 0]
    cmp = jnp.where(nz.any(-1), first,
                    jnp.sign(ql[:, None] - sl[None, :]))
    ge = (cmp >= 0).astype(jnp.int32)                  # splits ascending
    return jnp.maximum(ge.sum(-1) - 1, 0).astype(jnp.int32)
