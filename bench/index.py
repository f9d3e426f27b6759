"""The system under test, behind one small interface per deployment.

Every op goes through the index's normal entries: ``repro.core.batch_ops``
for one tree on one chip, ``repro.shard`` for a range-sharded tree. Each
op method takes the batch's arrays and returns ``(results, failed)``:
a tuple of results, device arrays where the entry returns them, which
:func:`fetch` brings to the host, and the lanes the entry reported as not
served (``None`` where it reports none). Results per op: read ``(found,
vals)``; update, insert, remove ``(found,)``; rmw ``(found, vals,
found_of_the_update)``; scan ``(emitted, key_ids, vals)``.
"""
from __future__ import annotations

import numpy as np

from . import ycsb


def fetch(results):
    return tuple(np.asarray(r) for r in results)


def _start_copy(results):
    for r in results:
        copy = getattr(r, "copy_to_host_async", None)
        if copy is not None:
            copy()
    return results


def plan(conf: dict, max_keys: int):
    from repro.core.fbtree import TreeConfig
    t = conf["tree"]
    return TreeConfig.plan(max_keys=int(max_keys), key_width=ycsb.KEY_WIDTH,
                           ns=int(t["ns"]), fs=int(t["fs"]))


def engine_of(conf: dict):
    from repro.core.traverse import TraversalEngine
    t = conf["tree"]
    return TraversalEngine(backend=t["engine"],
                           collect_stats=bool(t["collect_stats"]))


class OneChip:
    """One ``FBTree`` on the default device."""

    def __init__(self, conf: dict, nums: np.ndarray, vals: np.ndarray,
                 phase):
        import jax
        import jax.numpy as jnp

        from repro.core import batch_ops
        from repro.core import keys as K
        from repro.core.fbtree import bulk_build
        self.B = batch_ops
        self.jnp = jnp
        self.engine = engine_of(conf)
        n = nums.shape[0]
        self.config = plan(conf, n + int(conf["tree"]["headroom_keys"]))
        with phase("upload"):
            parts = jax.device_put(ycsb.number_parts(nums))
            dvals = jax.device_put(np.asarray(vals, np.int32))
            kb = jax.jit(ycsb.device_key_bytes)(parts)
            kl = jnp.full((n,), ycsb.KEY_LEN, jnp.int32)
            jax.block_until_ready((kb, kl, dvals))
            del parts
        with phase("build"):
            self.tree = bulk_build(self.config, K.KeySet(kb, kl), dvals,
                                   device=True)
            jax.block_until_ready(self.tree)
        del kb, kl, dvals
        self.devices = [jax.devices()[0]]

    def put(self, *arrays):
        import jax
        return tuple(None if a is None else jax.device_put(a) for a in arrays)

    def read(self, kb, kl):
        vals, rep = self.B.lookup_batch(self.tree, kb, kl, engine=self.engine)
        return _start_copy((rep.found, vals)), None

    def update(self, kb, kl, vals):
        self.tree, rep = self.B.update_batch(self.tree, kb, kl, vals,
                                             engine=self.engine)
        return _start_copy((rep.found,)), None

    def rmw(self, kb, kl):
        got, rep = self.B.lookup_batch(self.tree, kb, kl, engine=self.engine)
        self.tree, rep2 = self.B.update_batch(self.tree, kb, kl, got + 1,
                                              engine=self.engine)
        return _start_copy((rep.found, got, rep2.found)), None

    def insert(self, kb, kl, vals):
        self.tree, rep, _ = self.B.insert_batch(self.tree, kb, kl, vals,
                                                engine=self.engine)
        return _start_copy((rep.found,)), None

    def remove(self, kb, kl):
        self.tree, rep = self.B.remove_batch(self.tree, kb, kl,
                                             engine=self.engine)
        return _start_copy((rep.found,)), None

    def scan(self, kb, kl, max_items: int):
        kid, vals, emitted, _ = self.B.range_scan(
            self.tree, kb, kl, max_items=max_items, engine=self.engine)
        return _start_copy((emitted, kid, vals)), None

    def scan_keys(self, kids: np.ndarray) -> np.ndarray:
        """Key bytes of scan results' key ids (``-1`` gives a zero row)."""
        jnp = self.jnp
        rows = jnp.take(self.tree.arrays.key_bytes,
                        jnp.asarray(np.maximum(kids, 0)), axis=0)
        return np.where((kids >= 0)[..., None], np.asarray(rows), 0)

    def close(self):
        self.tree = None


class RangeSharded:
    """A ``ShardedTree``: one range shard per chip, each planned for its
    share, the split-key router replicated."""

    def __init__(self, conf: dict, nums: np.ndarray, vals: np.ndarray,
                 phase):
        import jax

        from repro import shard as SH
        from repro.core import keys as K
        self.SH = SH
        self.engine = engine_of(conf)
        chips = int(conf["chips"])
        n = nums.shape[0]
        share = -(-n // chips) + int(conf["tree"]["headroom_keys"])
        self.config = plan(conf, share)
        with phase("upload"):
            keyfn = jax.jit(ycsb.device_key_bytes)
            step = 1 << 22
            kb = np.empty((n, ycsb.KEY_WIDTH), np.uint8)
            for s in range(0, n, step):
                kb[s:s + step] = np.asarray(keyfn(ycsb.number_parts(
                    nums[s:s + step])))
            kl = np.full(n, ycsb.KEY_LEN, np.int32)
        with phase("build"):
            self.st = SH.sharded_build(
                K.KeySet(kb, kl), np.asarray(vals, np.int32), chips,
                per_shard_max_keys=share, device=True, presorted=True,
                ns=self.config.ns, fs=self.config.fs,
                mesh=SH.make_shard_mesh(chips, jax.devices()[:chips]))
            jax.block_until_ready(self.st.shards)
        self.devices = list(self.st.devices)

    def put(self, *arrays):
        return arrays

    def read(self, kb, kl):
        vals, rep = self.SH.lookup_batch(self.st, kb, kl, engine=self.engine)
        return (rep.found, vals), rep.failed

    def update(self, kb, kl, vals):
        self.st, rep = self.SH.update_batch(self.st, kb, kl, vals,
                                            engine=self.engine)
        return (rep.found,), rep.failed

    def rmw(self, kb, kl):
        got, rep = self.SH.lookup_batch(self.st, kb, kl, engine=self.engine)
        self.st, rep2 = self.SH.update_batch(
            self.st, kb, kl, (got.astype(np.int64) + 1).astype(np.int32),
            engine=self.engine)
        return (rep.found, got, rep2.found), rep.failed | rep2.failed

    def insert(self, kb, kl, vals):
        self.st, rep, _ = self.SH.insert_batch(self.st, kb, kl, vals,
                                               engine=self.engine)
        return (rep.found,), rep.failed

    def remove(self, kb, kl):
        self.st, rep = self.SH.remove_batch(self.st, kb, kl,
                                            engine=self.engine)
        return (rep.found,), rep.failed

    def scan(self, kb, kl, max_items: int):
        gk, vals, emitted, _, failed = self.SH.range_scan(
            self.st, kb, kl, max_items=max_items, engine=self.engine)
        return (emitted, gk, vals), failed

    def scan_keys(self, kids: np.ndarray) -> np.ndarray:
        rows, _ = self.st.key_rows(kids)
        return np.where((kids >= 0)[..., None], rows, 0)

    def close(self):
        self.st = None


DEPLOYMENTS = {"single": OneChip, "range_sharded": RangeSharded}
