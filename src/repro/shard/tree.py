"""ShardedTree container (DESIGN.md §7): one FBTree per shard + a
replicated split-key router.

The inner levels of every shard are ordinary FBTree levels over that
shard's own leaf/key arrays — "replicated inner levels, sharded leaf/key
pool" falls out of the range partition: each shard's (small) tree is fully
resident wherever its queries are routed, while the global key pool and
leaf chain exist only as the disjoint union of the per-shard arrays. All
shards share ONE ``TreeConfig``, so every batched op compiles once and
runs against any shard (and the dispatch loop reuses the same executable
across devices).

Invariants (`tests/test_shard_tree.py` pins them):

* **Range partition.** Shard ``s`` holds exactly the live keys in
  ``[split[s], split[s+1])`` (shard 0's range is open below). Routed
  inserts preserve this; only ``rebalance`` moves the boundaries.
* **Global order = shard order.** Concatenating the shards' sorted live
  key sets in shard order is the globally sorted live key set — the
  property the cross-shard range scan's merge relies on.
* **Parity.** Every batch op on a ShardedTree is bit-identical (values,
  found-ness, emitted counts, resolved key bytes) to the same op on one
  unsharded tree over the same keys, for any shard count.

Key ids are pool-local per shard; cross-shard APIs (``range_scan``) return
**global key ids** ``gkid = shard * (key_cap + 1) + kid`` (int64, EMPTY
stays -1) which :meth:`ShardedTree.key_rows` resolves back to bytes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from repro.core.fbtree import EMPTY, FBTree, TreeConfig

from .router import ShardRouter

__all__ = ["ShardedTree", "ShardHealth"]


class ShardHealth:
    """Mutable host-side health registry, shared across the functional
    ``replace`` chain (DESIGN.md §8).

    A shard is marked down when a routed dispatch exhausts its retries
    (``shard.ops._dispatch``); while down, ops skip its launches outright
    and report its lanes ``failed`` (mutations) or serve them ``degraded``
    from the last-barrier snapshot (lookups). The shard's *arrays* are
    always intact — only dispatch reachability is modeled — so
    ``rebalance()`` (which builds a fresh ShardedTree with fresh health)
    is the re-admission path and no committed op is ever lost.
    """

    def __init__(self, n_shards: int):
        self.ok = np.ones(int(n_shards), dtype=bool)
        self.reasons = [""] * int(n_shards)

    def is_ok(self, s: int) -> bool:
        return bool(self.ok[s])

    def mark_down(self, s: int, reason: str = ""):
        self.ok[s] = False
        self.reasons[s] = reason

    def mark_up(self, s: int):
        self.ok[s] = True
        self.reasons[s] = ""

    def reset(self):
        self.ok[:] = True
        self.reasons = [""] * self.ok.shape[0]

    @property
    def n_unhealthy(self) -> int:
        return int((~self.ok).sum())

    def __repr__(self):
        down = [f"{s}:{r or 'down'}" for s, r in enumerate(self.reasons)
                if not self.ok[s]]
        return (f"ShardHealth({self.ok.size} shards, "
                f"{'all ok' if not down else 'down ' + ', '.join(down)})")


@dataclasses.dataclass
class ShardedTree:
    """Host-side container: per-shard trees, router, optional placement.

    Not a jax pytree — dispatch is a host loop launching one jitted op per
    shard (async on that shard's device); only the per-shard FBTrees and
    the router live on device (the router on the host's CPU device).

    ``health`` is deliberately a *shared mutable* object: routed ops
    return a functionally-updated ShardedTree (``replace``), and a shard
    marked down mid-batch must stay down in every tree object derived
    from that lineage until a ``rebalance`` barrier re-admits it.
    ``snapshots`` are the per-shard trees as of the last barrier
    (build/rebalance) — the read-only fallback degraded lookups serve
    from; in-place commits advance ``shards`` but never ``snapshots``.
    """
    shards: Tuple[FBTree, ...]
    router: ShardRouter
    devices: Tuple = ()            # per-shard jax device (None = unplaced)
    mesh: object = None            # jax.sharding.Mesh | None (documentation
    #                                + bench introspection; ops only use
    #                                `devices`)
    health: ShardHealth = None     # shared across replace() lineage
    snapshots: Tuple[FBTree, ...] = ()   # last-barrier per-shard trees

    def __post_init__(self):
        if not self.devices:
            self.devices = (None,) * len(self.shards)
        if self.health is None:
            self.health = ShardHealth(len(self.shards))
        if not self.snapshots:
            self.snapshots = self.shards
        if len(self.devices) != len(self.shards):
            raise ValueError(
                f"ShardedTree: {len(self.devices)} devices for "
                f"{len(self.shards)} shards — one device slot per shard "
                f"(None for unplaced)")
        if self.router.n_shards != len(self.shards):
            raise ValueError(
                f"ShardedTree: router has {self.router.n_shards} split "
                f"keys for {len(self.shards)} shards — rebuild the router "
                f"with make_router over one min key per shard")
        if self.health.ok.size != len(self.shards):
            raise ValueError(
                f"ShardedTree: health tracks {self.health.ok.size} shards "
                f"but the tree has {len(self.shards)}")

    # ------------------------------------------------------------- shape
    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def config(self) -> TreeConfig:
        return self.shards[0].config

    @property
    def kid_stride(self) -> int:
        """Rows per shard key pool — the global-key-id stride."""
        return self.config.key_cap + 1

    @property
    def n_keys_live(self) -> int:
        return sum(t.n_keys_live for t in self.shards)

    def replace(self, **kw) -> "ShardedTree":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------- global kids
    def split_gkid(self, gkid: np.ndarray):
        """Decode global key ids -> (shard [.., ], local kid [..,]);
        EMPTY lanes map to (0, EMPTY)."""
        g = np.asarray(gkid, dtype=np.int64)
        ok = g >= 0
        shard = np.where(ok, g // self.kid_stride, 0).astype(np.int32)
        kid = np.where(ok, g % self.kid_stride, EMPTY).astype(np.int32)
        return shard, kid

    def key_rows(self, gkid: np.ndarray):
        """Resolve global key ids to ``(key_bytes uint8[.., L], lens
        int32[..])``; EMPTY ids resolve to zero rows."""
        shard, kid = self.split_gkid(gkid)
        L = self.config.key_width
        out_b = np.zeros(shard.shape + (L,), dtype=np.uint8)
        out_l = np.zeros(shard.shape, dtype=np.int32)
        for s, t in enumerate(self.shards):
            sel = (shard == s) & (kid >= 0)
            if not sel.any():
                continue
            kb = np.asarray(t.arrays.key_bytes)
            kl = np.asarray(t.arrays.key_lens)
            out_b[sel] = kb[kid[sel]]
            out_l[sel] = kl[kid[sel]]
        return out_b, out_l

    # ----------------------------------------------------- op delegation
    # thin method facade over repro.shard.ops (imported lazily to keep the
    # module graph acyclic); the functional API is the primary surface
    def lookup(self, qb, ql, engine=None, **kw):
        from . import ops
        return ops.lookup_batch(self, qb, ql, engine=engine, **kw)

    def update(self, qb, ql, vals, engine=None, **kw):
        from . import ops
        return ops.update_batch(self, qb, ql, vals, engine=engine, **kw)

    def insert(self, qb, ql, vals, engine=None, **kw):
        from . import ops
        return ops.insert_batch(self, qb, ql, vals, engine=engine, **kw)

    def remove(self, qb, ql, engine=None, **kw):
        from . import ops
        return ops.remove_batch(self, qb, ql, engine=engine, **kw)

    def range_scan(self, qb, ql, max_items: int = 64, engine=None, **kw):
        from . import ops
        return ops.range_scan(self, qb, ql, max_items=max_items,
                              engine=engine, **kw)

    def rebalance(self, device: bool = True, **kw):
        from . import ops
        return ops.rebalance(self, device=device, **kw)
