"""The control and the planted faults that ``correct`` has to catch.

None of these runs in the benchmark's own runs. ``control`` is the
index's own descent and leaf probe with the key check dropped: a read
trusts the first slot whose one-byte hashtag matches, the shortcut that
would tempt a later change (the leaf probe then reads no key bytes). It
breaks the configuration's guarantee that reads are exact. The faults
break the timed path underneath the harness:

* ``lost_writes``: a mutation returns its tree unchanged;
* ``half_batch``: only the first half of each sub-batch reaches the
  index; the rest read as absent and are acknowledged unwritten;
* ``altered_answer``: lane 0 of each read returns its value plus one, and
  lane 0 of each update writes its value plus one;
* ``stale_reads``: reads see the tree as of the window's start;
* ``no_exchange``: a sharded read asks shard 0 alone, so lanes routed to
  the other shards are never answered by their owner.
"""
from __future__ import annotations

import functools

import numpy as np

FAULTS = ("control", "lost_writes", "half_batch", "altered_answer",
          "stale_reads", "no_exchange")


@functools.lru_cache(maxsize=None)
def _tag_only_lookup():
    import jax
    import jax.numpy as jnp

    from repro.core.keys import fnv1a_tags
    from repro.core.traverse import resolve_engine

    @functools.partial(jax.jit, static_argnames=("engine",))
    def lookup(tree, qb, ql, engine):
        leaf, _, _ = resolve_engine(engine).traverse(tree, qb, ql)
        a = tree.arrays
        cand = (a.leaf_tags[leaf] == fnv1a_tags(qb, ql)[:, None]) \
            & a.leaf_occ[leaf]
        found = cand.any(-1)
        slot = jnp.argmax(cand, axis=-1)
        val = jnp.take_along_axis(a.leaf_val[leaf], slot[:, None], -1)[:, 0]
        return found, jnp.where(found, val, 0)
    return lookup


class Faulty:
    """Wraps an index from ``index.py`` and breaks it as ``fault`` says."""

    def __init__(self, index, fault: str):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
        self.inner = index
        self.fault = fault
        self.first = getattr(index, "tree", None)

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def _half(self, *arrays):
        return tuple(None if a is None else a[:a.shape[0] // 2]
                     for a in arrays)

    def read(self, kb, kl):
        f, inner = self.fault, self.inner
        if f == "control":
            return _tag_only_lookup()(inner.tree, kb, kl, inner.engine), None
        if f == "stale_reads":
            now, inner.tree = inner.tree, self.first
            try:
                return inner.read(kb, kl)
            finally:
                inner.tree = now
        if f == "half_batch":
            (found, vals), failed = inner.read(*self._half(kb, kl))
            k = kb.shape[0]
            pad = k - found.shape[0]
            return (np.concatenate([np.asarray(found), np.zeros(pad, bool)]),
                    np.concatenate([np.asarray(vals),
                                    np.zeros(pad, np.int32)])), failed
        if f == "altered_answer":
            (found, vals), failed = inner.read(kb, kl)
            vals = np.asarray(vals).copy()
            vals[0] += 1
            return (found, vals), failed
        if f == "no_exchange":
            from repro.core import batch_ops as B
            st = inner.st
            vals, rep = B.lookup_batch(st.shards[0], kb, kl,
                                       engine=inner.engine)
            return (rep.found, vals), None
        return inner.read(kb, kl)

    def _mutate(self, name, kb, kl, *rest):
        f, inner = self.fault, self.inner
        if f == "lost_writes":
            before = (getattr(inner, "tree", None), getattr(inner, "st", None))
            out = getattr(inner, name)(kb, kl, *rest)
            inner.tree = before[0]
            if before[1] is not None:
                inner.st = before[1]
            return out
        if f == "half_batch":
            (found,), failed = getattr(inner, name)(
                *self._half(kb, kl, *rest))
            pad = kb.shape[0] - np.asarray(found).shape[0]
            return (np.concatenate([np.asarray(found),
                                    np.ones(pad, bool)]),), failed
        if f == "altered_answer" and rest:
            vals = np.asarray(rest[0]).copy()
            vals[0] += 1
            return getattr(inner, name)(kb, kl, vals)
        return getattr(inner, name)(kb, kl, *rest)

    def update(self, kb, kl, vals):
        return self._mutate("update", kb, kl, vals)

    def insert(self, kb, kl, vals):
        return self._mutate("insert", kb, kl, vals)

    def remove(self, kb, kl):
        return self._mutate("remove", kb, kl)
