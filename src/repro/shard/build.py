"""Sharded bulk build (DESIGN.md §7): the §5 pipeline with step 1 — the
global sort — going distributed, steps 2–3 unchanged per shard.

``fbtree.sharded_partition`` sorts the key set once and splits it into
balanced contiguous runs; each run then feeds an ordinary per-shard
``bulk_build`` (host reference or the jit device pipeline — the §5 parity
contract holds shard by shard), and the runs' minimum keys become the
replicated router. Every shard shares one ``TreeConfig`` planned for
``per_shard_max_keys`` (default: the full ``max_keys``, so any single
shard can absorb the whole key set before a ``rebalance`` — skew-safe, at
S× pool memory; pass each shard's share, ``max_keys / n_shards`` plus
headroom, when memory matters, as a deployment filling its chips does).
Each shard is built on its own device, so no device carries another
shard's build temporaries. With ``device=True`` every shard's sort and
build is launched before any host sync (the capacity flags are read only
once all are queued), so the shards' builds overlap across chips instead
of running one after another; each shard is bit-identical to a one-tree
``bulk_build(device=True)`` of its slice. The build program compiles once
per chip: its executable is bound to the device it runs on.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np

from repro.core import keys as K
from repro.core.fbtree import (FBTree, TreeConfig, _check_capacity,
                                _launch_device_build, bulk_build,
                                sharded_partition)

from .mesh import make_shard_mesh, place_shard, shard_devices
from .router import make_router
from .tree import ShardedTree

__all__ = ["sharded_build"]


def sharded_build(ks: K.KeySet, vals, n_shards: int,
                  max_keys: Optional[int] = None,
                  per_shard_max_keys: Optional[int] = None,
                  device: bool = False, mesh: Any = "auto",
                  cfg: Optional[TreeConfig] = None, presorted: bool = False,
                  **plan_kw) -> ShardedTree:
    """Bulk-load a :class:`ShardedTree` from (possibly unsorted) unique keys.

    Arguments mirror ``bulk_build`` + ``TreeConfig.plan``:

    * ``n_shards``            number of range partitions (``ks.n >=
      n_shards``).
    * ``max_keys``            global capacity plan (default ``ks.n``).
    * ``per_shard_max_keys``  per-shard capacity (default ``max_keys``:
      every shard planned for the whole set — skew-safe).
    * ``device``              per-shard device build (§5 jit pipeline)
      instead of the host reference; both are bit-identical per shard.
    * ``mesh``                ``"auto"`` builds a 1-D shard mesh over the
      local devices; ``None`` skips placement (arrays stay on the default
      device); or pass an explicit ``jax.sharding.Mesh``. Shards are
      committed to mesh devices round-robin.
    * ``cfg``                 explicit shared per-shard ``TreeConfig``
      (overrides the plan; all shards must use one config so ops compile
      once).
    * ``presorted``           the keys are already in the global sort
      order — skip step 1's sort (rebalance's concatenated snapshots).
    * ``plan_kw``             forwarded to ``TreeConfig.plan`` (ns, fs,
      leaf_fill, val_dtype, stacked, ...).
    """
    if n_shards < 1:
        raise ValueError(
            f"sharded_build: n_shards must be >= 1, got {n_shards}")
    if ks.n < n_shards:
        raise ValueError(
            f"sharded_build: need at least one key per shard to define "
            f"the range partition (n={ks.n} < n_shards={n_shards}) — "
            f"lower n_shards or seed per-shard sentinel keys the way "
            f"serving.PrefixCache does")
    nv = np.asarray(vals).shape[0]
    if nv != ks.n:
        raise ValueError(
            f"sharded_build: {nv} values for {ks.n} keys — one value per "
            f"key")
    if cfg is not None and cfg.key_width != ks.width:
        raise ValueError(
            f"sharded_build: TreeConfig.key_width={cfg.key_width} but the "
            f"key set is packed to width {ks.width} — plan the config "
            f"with key_width={ks.width} (routing and descent compare "
            f"fixed-width padded rows)")
    if cfg is None:
        if max_keys is None:
            max_keys = ks.n
        if per_shard_max_keys is None:
            per_shard_max_keys = max_keys
        cfg = TreeConfig.plan(max_keys=int(per_shard_max_keys),
                              key_width=ks.width, **plan_kw)
    parts, split_keys = sharded_partition(ks, vals, n_shards,
                                          presorted=presorted)
    if mesh == "auto":
        mesh = make_shard_mesh(n_shards)
    devices = shard_devices(mesh, n_shards)
    for pks, _ in parts:            # every shard vetted before any launch
        _check_capacity(cfg, pks.n)
    trees, errs = [], []
    for (pks, pvals), dev in zip(parts, devices):
        # build where the shard lives: the device build's inputs and
        # temporaries land on `dev` (None: the default device), and placing
        # it is then a no-op. A device build is only launched here, so
        # every shard's build is queued before the first host sync below.
        with jax.default_device(dev):
            if device:
                arrays, err = _launch_device_build(cfg, pks,
                                                   np.asarray(pvals))
                trees.append(FBTree(cfg, arrays))
                errs.append(err)
            else:
                trees.append(bulk_build(cfg, pks, np.asarray(pvals)))
    if any(bool(err) for err in errs):  # pragma: no cover - as bulk_build
        raise RuntimeError("bulk_build(device=True): capacity exceeded")
    shards = tuple(place_shard(t, dev) for t, dev in zip(trees, devices))
    return ShardedTree(shards=shards, router=make_router(split_keys),
                       devices=devices, mesh=mesh)
