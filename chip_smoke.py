#!/usr/bin/env python3
"""Drive the index's main path once on a TPU at deployment size and check
every result exactly against a plain host reference.

    python chip_smoke.py                  # one chip: 2^25 YCSB keys
    python chip_smoke.py --chips 4        # only the four-chip sharded phase
    python chip_smoke.py --cpu-rehearsal  # tiny size on the CPU backend

The deployment is a YCSB-style key-value index: keys are ``user`` plus 19
decimal digits (23 bytes, ``key_width=24``), drawn from ``--seed``, values
int32. One process, the default ``jnp`` traversal engine, stats off. The
one-chip run bulk-builds the tree on the device and then runs, each through
the normal entry points (``repro.core.batch_ops``, ``repro.serving``):

  build, lookup, update, insert (clustered, so leaves split), remove,
  range_scan (max_items=100), rebuild, a lookup through the ``pallas``
  engine (bit-identical to ``jnp`` required), and PrefixCache waves.

The reference is the live key set as a sorted key matrix with its values,
searched with ``np.searchsorted`` over a void view (memcmp order, which is
the tree's order for these equal-length keys).

Before the last line it prints one JSON line per phase: the first call's
time (compile included) and a steady repeat's, both ending in
``block_until_ready``, plus the device's ``peak_bytes_in_use``. The last
line is ``{"ok": true, "device": {...}}``, printed only when every check
passed on a TPU (or, with ``--cpu-rehearsal``, on the CPU). Any failed
check or exception exits non-zero without it.

``--chips 4`` runs only the four-chip phase: a range-sharded tree, one
shard per chip, each planned for its share of the keys, checked lane for
lane against the reference and against a one-chip tree on device 0.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

KEY_W = 24          # YCSB key "user" + 19 digits = 23 bytes, zero-padded
KEY_LEN = 23
MAX_ITEMS = 100     # YCSB-E scan length is uniform in [1, 100]
BATCH = 4096        # lanes per batch op
REHEARSAL_BATCH = 256


class CheckFailed(AssertionError):
    pass


def check(ok, what: str) -> None:
    if not bool(ok):
        raise CheckFailed(what)


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


# --------------------------------------------------------------------------
# data: YCSB keys from a seed, vectorised
# --------------------------------------------------------------------------

def ycsb_numbers(rng, n: int) -> np.ndarray:
    """``n`` distinct record numbers below 10^19, in random order."""
    got = np.unique(rng.integers(0, 10**19, size=n + n // 16 + 64,
                                 dtype=np.uint64))
    while got.size < n:
        more = rng.integers(0, 10**19, size=n - got.size + 64,
                            dtype=np.uint64)
        got = np.unique(np.concatenate([got, more]))
    return rng.permutation(got)[:n]


def ycsb_keys(nums: np.ndarray) -> np.ndarray:
    """``uint8 [n, KEY_W]`` rows: ``user`` + the number as 19 zero-padded
    digits, one zero pad byte."""
    kb = np.zeros((nums.shape[0], KEY_W), np.uint8)
    kb[:, :4] = np.frombuffer(b"user", np.uint8)
    x = nums.copy()
    for pos in range(KEY_LEN - 1, 3, -1):
        kb[:, pos] = (x % 10).astype(np.uint8) + ord("0")
        x //= 10
    return kb


def lens_of(kb: np.ndarray) -> np.ndarray:
    return np.full((kb.shape[0],), KEY_LEN, np.int32)


def between(rng, lo: int, hi: int, k: int, taken: np.ndarray) -> np.ndarray:
    """``k`` distinct numbers strictly inside ``(lo, hi)`` and not in the
    sorted array ``taken``: a key cluster that lands in a few leaves."""
    out = np.zeros((0,), np.uint64)
    while out.size < k:
        c = rng.integers(lo + 1, hi, size=2 * k, dtype=np.uint64)
        pos = np.minimum(np.searchsorted(taken, c), taken.size - 1)
        c = c[taken[pos] != c]
        out = np.unique(np.concatenate([out, c]))
    return rng.permutation(out)[:k]


class Reference:
    """The live key set as a sorted key matrix plus its values."""

    def __init__(self, kb: np.ndarray, vals: np.ndarray):
        self.kb = np.ascontiguousarray(kb)
        self.vals = np.asarray(vals, np.int32).copy()
        self._reindex()

    @staticmethod
    def void(kb: np.ndarray) -> np.ndarray:
        return np.ascontiguousarray(kb).view(
            np.dtype((np.void, KEY_W))).reshape(-1)

    def _reindex(self):
        self.v = self.void(self.kb)

    @property
    def n(self) -> int:
        return self.kb.shape[0]

    def find(self, qkb):
        q = self.void(qkb)
        i = np.searchsorted(self.v, q)
        ic = np.minimum(i, self.n - 1)
        return (i < self.n) & (self.v[ic] == q), ic

    def lookup(self, qkb):
        hit, i = self.find(qkb)
        return hit, np.where(hit, self.vals[i], 0).astype(np.int32)

    def upsert(self, qkb, vals):
        hit, i = self.find(qkb)
        self.vals[i[hit]] = vals[hit]
        new = ~hit
        order = np.argsort(self.void(qkb[new]), kind="stable")
        nkb, nv = qkb[new][order], vals[new][order]
        pos = np.searchsorted(self.v, self.void(nkb))
        self.kb = np.insert(self.kb, pos, nkb, axis=0)
        self.vals = np.insert(self.vals, pos, nv)
        self._reindex()

    def remove(self, qkb):
        hit, i = self.find(qkb)
        self.kb = np.delete(self.kb, i[hit], axis=0)
        self.vals = np.delete(self.vals, i[hit])
        self._reindex()

    def scan(self, qkb, max_items: int):
        """Keys and values of the slice starting at the first key >= each
        query: ``(emitted [B], kb [B, max_items, L], vals [B, max_items])``,
        zero past ``emitted``."""
        i = np.searchsorted(self.v, self.void(qkb))
        idx = i[:, None] + np.arange(max_items)[None, :]
        ok = idx < self.n
        idx = np.where(ok, idx, 0)
        kb = np.where(ok[..., None], self.kb[idx], 0).astype(np.uint8)
        vals = np.where(ok, self.vals[idx], 0).astype(np.int32)
        return ok.sum(-1).astype(np.int32), kb, vals


# --------------------------------------------------------------------------
# timing on the device
# --------------------------------------------------------------------------

def timed(fn):
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def peak_bytes(device):
    """``peak_bytes_in_use`` of one device, or a list for several (None
    where the backend reports no memory stats)."""
    if isinstance(device, (list, tuple)):
        return [peak_bytes(d) for d in device]
    stats = device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def phase(name, first_s, steady_s, device, **extra):
    emit(phase=name, first_s=first_s, steady_s=steady_s,
         peak_bytes_in_use=peak_bytes(device), **extra)


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------

def make_data(seed: int, n: int, n_fresh: int):
    """Loaded keys (random order) with values, the sorted reference, and
    a pool of fresh record numbers that are not loaded."""
    rng = np.random.default_rng(seed)
    nums = ycsb_numbers(rng, n + n_fresh)
    loaded, fresh = nums[:n], nums[n:]
    kb = ycsb_keys(loaded)
    vals = rng.integers(0, 2**31 - 1, size=n, dtype=np.int32)
    order = np.argsort(loaded)
    ref = Reference(kb[order], vals[order])
    w = ref.kb.view(">u8")                 # the reference must be sorted
    a, b = w[:-1], w[1:]
    asc = (a[:, 0] < b[:, 0]) | ((a[:, 0] == b[:, 0]) & (
        (a[:, 1] < b[:, 1]) | ((a[:, 1] == b[:, 1]) & (a[:, 2] < b[:, 2]))))
    check(asc.all(), "reference key matrix is strictly ascending")
    return rng, kb, vals, ref, np.sort(loaded), fresh


def run_one_chip(n: int, seed: int, batch: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import batch_ops as B
    from repro.core import keys as K
    from repro.core.fbtree import TreeConfig, bulk_build
    from repro.core.traverse import TraversalEngine
    from repro.serving import PrefixCache, chain_keys

    dev = jax.devices()[0]
    eng = TraversalEngine(collect_stats=False)
    Bn = batch
    t0 = time.perf_counter()
    rng, kb, vals, ref, sorted_nums, fresh = make_data(seed, n, 16 * Bn)
    emit(phase="data", keys=n, key_width=KEY_W, batch=Bn,
         host_s=time.perf_counter() - t0)
    fresh_at = iter(range(0, fresh.size, Bn // 2))

    def fresh_keys(k):
        s = next(fresh_at)
        check(s + k <= fresh.size, "fresh key pool exhausted")
        return ycsb_keys(fresh[s:s + k])

    def hits(k):
        return ref.kb[rng.choice(ref.n, size=k, replace=False)]

    def mixed(k):
        q = np.concatenate([hits(k - k // 2), fresh_keys(k // 2)])
        return q[rng.permutation(k)]

    def dev_q(qkb):
        return jnp.asarray(qkb), jnp.asarray(lens_of(qkb))

    def check_lookup(qkb, got_vals, rep, what):
        want_hit, want_vals = ref.lookup(qkb)
        found = np.asarray(rep.found)
        check(np.array_equal(found, want_hit), f"{what}: found differs")
        check(np.array_equal(np.where(found, np.asarray(got_vals), 0),
                             want_vals), f"{what}: values differ")

    def lookup(qkb, engine=eng):
        return B.lookup_batch(tree, *dev_q(qkb), engine=engine)

    # ---- build (on the device, headroom for the inserts below) ----
    cfg = TreeConfig.plan(max_keys=n + 8 * Bn, key_width=KEY_W)
    ks = K.KeySet(kb, lens_of(kb))
    tree, t1 = timed(lambda: bulk_build(cfg, ks, vals, device=True))
    again, t2 = timed(lambda: bulk_build(cfg, ks, vals, device=True))
    del again
    check(int(tree.arrays.key_count) == n, "build: key count")
    tree_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))
    phase("build", t1, t2, dev, n_levels=cfg.n_levels, ns=cfg.ns,
          leaf_cap=cfg.leaf_cap, tree_bytes=tree_bytes)

    # ---- lookup: hits and misses ----
    q = mixed(Bn)
    (v, rep), t1 = timed(lambda: lookup(q))
    check_lookup(q, v, rep, "lookup")
    (v, rep), t2 = timed(lambda: lookup(q))
    check_lookup(q, v, rep, "lookup repeat")
    phase("lookup", t1, t2, dev, lanes=Bn)

    # ---- update, then read back ----
    u = hits(2 * Bn)
    nv = rng.integers(0, 2**31 - 1, size=2 * Bn, dtype=np.int32)
    times = []
    for h in range(2):
        qk, qv = u[h * Bn:(h + 1) * Bn], nv[h * Bn:(h + 1) * Bn]
        (tree, rep), t = timed(
            lambda: B.update_batch(tree, *dev_q(qk), jnp.asarray(qv),
                                   engine=eng))
        check(np.asarray(rep.found).all(), "update: every key present")
        ref.upsert(qk, qv)
        times.append(t)
    for h in range(2):
        qk = u[h * Bn:(h + 1) * Bn]
        check_lookup(qk, *lookup(qk), "update read-back")
    phase("update", times[0], times[1], dev, lanes=Bn)

    # ---- insert: half clustered between two neighbours (leaf splits) ----
    taken = np.sort(np.concatenate([sorted_nums, fresh]))
    times, splits, rounds, inserted = [], 0, [], []
    for h in range(2):
        p = int(rng.integers(0, n - 65))
        cl = between(rng, int(sorted_nums[p]), int(sorted_nums[p + 64]),
                     Bn // 2, taken)
        qk = np.concatenate([ycsb_keys(cl), fresh_keys(Bn - Bn // 2)])
        qk = qk[rng.permutation(Bn)]
        qv = rng.integers(0, 2**31 - 1, size=Bn, dtype=np.int32)
        (tree, rep, r), t = timed(
            lambda: B.insert_batch(tree, *dev_q(qk), jnp.asarray(qv),
                                   engine=eng))
        check(not bool(rep.error), "insert: capacity error")
        ref.upsert(qk, qv)
        times.append(t)
        splits += int(rep.splits)
        rounds.append(r)
        inserted.append((qk, sorted_nums[p]))
    check(splits > 0, "insert: the clustered keys split leaves")
    for qk, lo in inserted:
        check_lookup(qk, *lookup(qk), "insert read-back")
        # the split leaves' old and new keys, in key order
        j = int(np.searchsorted(ref.v, ref.void(ycsb_keys(np.array([lo]))))[0])
        around = ref.kb[max(0, j - 8):][:Bn]
        check_lookup(around, *lookup(around), "insert neighbours")
    check(int(tree.n_keys_live) == ref.n, "insert: live key count")
    phase("insert", times[0], times[1], dev, lanes=Bn, splits=splits,
          rounds=rounds)

    # ---- remove: inserted, loaded and absent keys; then read back ----
    times = []
    gone = []
    for h in range(2):
        ins = inserted[h][0]
        qk = np.concatenate([ins[:Bn // 4], hits(Bn // 2),
                             fresh_keys(Bn - Bn // 4 - Bn // 2)])
        qk = qk[rng.permutation(Bn)]
        want_hit, _ = ref.find(qk)
        (tree, rep), t = timed(
            lambda: B.remove_batch(tree, *dev_q(qk), engine=eng))
        check(np.array_equal(np.asarray(rep.found), want_hit),
              "remove: found differs")
        ref.remove(qk)
        times.append(t)
        gone.append(qk)
    for qk in gone:
        v, rep = lookup(qk)
        check(not np.asarray(rep.found).any(), "remove: key still found")
        check_lookup(qk, v, rep, "remove read-back")
    check(int(tree.n_keys_live) == ref.n, "remove: live key count")
    phase("remove", times[0], times[1], dev, lanes=Bn)

    # ---- range scan ----
    tail = ycsb_keys(np.array([10**19 - 1], np.uint64))   # past every key
    last = ref.kb[-3:]                                    # short results
    qs = np.concatenate([hits(Bn // 2), fresh_keys(Bn // 4), tail, last,
                         gone[0][:Bn - Bn // 2 - Bn // 4 - 4]])
    qs = qs[rng.permutation(Bn)]

    def scan():
        return B.range_scan(tree, *dev_q(qs), max_items=MAX_ITEMS,
                            engine=eng)
    (kid, sv, em, _), t1 = timed(scan)
    (kid, sv, em, _), t2 = timed(scan)
    want_em, want_kb, want_v = ref.scan(qs, MAX_ITEMS)
    em = np.asarray(em)
    check(np.array_equal(em, want_em), "range_scan: emitted counts")
    kid = np.asarray(kid)
    ok = np.arange(MAX_ITEMS)[None, :] < em[:, None]
    check(((kid >= 0) == ok).all(), "range_scan: key ids past emitted")
    got_kb = np.asarray(jnp.take(tree.arrays.key_bytes,
                                 jnp.asarray(np.maximum(kid, 0)), axis=0))
    check(np.array_equal(np.where(ok[..., None], got_kb, 0), want_kb),
          "range_scan: keys differ from the reference slice")
    check(np.array_equal(np.where(ok, np.asarray(sv), 0), want_v),
          "range_scan: values differ from the reference slice")
    phase("range_scan", t1, t2, dev, lanes=Bn, max_items=MAX_ITEMS,
          emitted=int(em.sum()))

    # ---- rebuild, then lookups again ----
    (tree, brep), t1 = timed(lambda: B.rebuild(tree))
    check(not bool(brep.error), "rebuild: capacity error")
    check(int(brep.n_live) == ref.n, "rebuild: live key count")
    (tree, brep), t2 = timed(lambda: B.rebuild(tree))
    check(int(brep.reclaimed) == 0, "rebuild is idempotent")
    q = np.concatenate([mixed(Bn - Bn // 4), gone[1][:Bn // 4]])
    check_lookup(q, *lookup(q), "lookup after rebuild")
    phase("rebuild", t1, t2, dev, n_live=int(brep.n_live))

    # ---- the Pallas level backend: bit-identical to jnp ----
    pallas = TraversalEngine("pallas", collect_stats=False)
    q = mixed(Bn)
    v_j, rep_j = lookup(q)
    (v_p, rep_p), t1 = timed(lambda: lookup(q, pallas))
    (v_p, rep_p), t2 = timed(lambda: lookup(q, pallas))
    check(np.array_equal(np.asarray(v_p), np.asarray(v_j))
          and np.array_equal(np.asarray(rep_p.found),
                             np.asarray(rep_j.found)),
          "pallas lookup is not bit-identical to jnp")
    check_lookup(q, v_p, rep_p, "pallas lookup")
    phase("lookup_pallas", t1, t2, dev, lanes=Bn)
    del tree

    run_prefix_cache(rng, dev, PrefixCache, chain_keys)


def run_prefix_cache(rng, dev, PrefixCache, chain_keys) -> None:
    """Match/publish waves of sessions that share system prompts; the
    reference is the set of published block digests with their pages.

    The three waves run twice, each time on a fresh cache with the same
    requests: the first pass compiles every batch shape the waves make, the
    second repeats exactly those shapes and is the steady time."""
    block = 16
    prompts = [rng.integers(0, 32000, size=4 * block, dtype=np.int32)
               for _ in range(8)]

    def session(k):
        turn = rng.integers(0, 32000, size=int(rng.integers(2, 7)) * block,
                            dtype=np.int32)
        return np.concatenate([prompts[k % len(prompts)], turn])

    reqs = [session(k) for k in range(32)]
    longer = [np.concatenate([r, rng.integers(0, 32000, size=2 * block,
                                              dtype=np.int32)])
              for r in reqs]
    fresh = [session(k) for k in range(32)]

    def wave(cache, published, reqs, what):
        want_pub = dict(published)                  # digest -> page id
        t0 = time.perf_counter()
        hit_blocks, pages = cache.match(reqs)
        for toks, h in zip(reqs, hit_blocks):
            ids = cache.publish(toks, h)
            check(ids is not None, f"{what}: publish refused")
            for d, pg in zip(chain_keys(toks, block)[h:], ids):
                published[d] = int(pg)
        dt = time.perf_counter() - t0
        for toks, h, pg in zip(reqs, hit_blocks, pages):
            want = 0
            for d in chain_keys(toks, block):
                if d not in want_pub:
                    break
                want += 1
            check(h == want, f"{what}: {h} hit blocks, expected {want}")
            check(pg == [want_pub[d] for d in chain_keys(toks, block)[:h]],
                  f"{what}: hit pages differ")
        return dt, sum(hit_blocks)

    def waves():
        cache = PrefixCache(n_pages=8192, block_tokens=block,
                            max_keys=1 << 16)
        published = {}
        t1, h1 = wave(cache, published, reqs, "wave 1")
        check(h1 == 0, "wave 1 hits an empty cache")
        t2, h2 = wave(cache, published, longer, "wave 2")
        check(h2 == sum(len(r) // block for r in reqs),
              "wave 2 hits every block wave 1 published")
        t3, h3 = wave(cache, published, fresh, "wave 3")
        check(h3 >= 32 * 4, "wave 3 hits the shared system prompts")
        return [t1, t2, t3], [h1, h2, h3]

    first, hits = waves()
    steady, hits_again = waves()
    check(hits_again == hits, "the repeat pass hits as the first did")
    emit(phase="prefix_cache", first_s=sum(first), steady_s=sum(steady),
         wave_first_s=first, wave_steady_s=steady, hit_blocks=hits,
         peak_bytes_in_use=peak_bytes(dev))


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------

def run_four_chips(n: int, seed: int, batch: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro import shard as SH
    from repro.core import batch_ops as B
    from repro.core import keys as K
    from repro.core.fbtree import TreeConfig, bulk_build
    from repro.core.traverse import TraversalEngine

    devs = jax.devices()[:4]
    eng = TraversalEngine(collect_stats=False)
    Bn = batch
    rng, kb, vals, ref, sorted_nums, fresh = make_data(seed, n, 4 * Bn)
    emit(phase="data", keys=n, key_width=KEY_W, batch=Bn)

    with jax.default_device(devs[0]):
        cfg1 = TreeConfig.plan(max_keys=n, key_width=KEY_W)
        one, t = timed(lambda: bulk_build(
            cfg1, K.KeySet(kb, lens_of(kb)), vals, device=True))
    phase("build_one_chip", t, None, devs[0])

    # each shard planned for its share; the keys are already sorted
    share = -(-n // 4)
    sks = K.KeySet(ref.kb, lens_of(ref.kb))
    st, t = timed(lambda: SH.sharded_build(
        sks, ref.vals, 4, per_shard_max_keys=share, device=True,
        presorted=True))
    placed = [{d for x in jax.tree_util.tree_leaves(tr) for d in x.devices()}
              for tr in st.shards]
    check(all(len(p) == 1 for p in placed)
          and len({next(iter(p)) for p in placed}) == 4,
          f"one shard per device: {placed}")
    check([next(iter(p)) for p in placed] == list(st.devices),
          "shards sit on their assigned devices")
    phase("build_sharded", t, None, devs, shard_keys=[
        int(tr.arrays.key_count) for tr in st.shards],
          shard_key_cap=st.config.key_cap,
          devices=[str(next(iter(p))) for p in placed])

    def dev_q(qkb):
        return jnp.asarray(qkb), jnp.asarray(lens_of(qkb))

    # ---- routed lookups, across the shard boundaries ----
    splits = np.asarray(st.router.split_bytes)
    bounds = np.searchsorted(ref.v, ref.void(splits))
    edge = np.concatenate([ref.kb[bounds[1:]], ref.kb[bounds[1:] - 1]])
    gaps = np.concatenate([ycsb_keys(between(
        rng, int(sorted_nums[b - 1]), int(sorted_nums[b]), 2, sorted_nums))
        for b in bounds[1:]])
    rest = Bn - edge.shape[0] - gaps.shape[0]
    q = np.concatenate([edge, gaps, ref.kb[rng.choice(n, rest // 2,
                                                      replace=False)],
                        ycsb_keys(fresh[:rest - rest // 2])])
    q = q[rng.permutation(Bn)]
    (v_s, rep_s), t1 = timed(lambda: SH.lookup_batch(st, *dev_q(q),
                                                     engine=eng))
    (v_s, rep_s), t2 = timed(lambda: SH.lookup_batch(st, *dev_q(q),
                                                     engine=eng))
    want_hit, want_v = ref.lookup(q)
    v_1, rep_1 = B.lookup_batch(one, *dev_q(q), engine=eng)
    check(np.array_equal(rep_s.found, want_hit)
          and np.array_equal(np.where(want_hit, v_s, 0), want_v),
          "sharded lookup vs reference")
    check(np.array_equal(rep_s.found, np.asarray(rep_1.found))
          and np.array_equal(v_s, np.asarray(v_1)),
          "sharded lookup vs the one-chip tree")
    check(len(set(rep_s.owner.tolist())) == 4, "lookups reach every shard")
    phase("lookup_sharded", t1, t2, devs, lanes=Bn,
          per_shard=np.bincount(rep_s.owner, minlength=4).tolist())

    # ---- cross-shard range scan: starts just below each boundary ----
    below = np.concatenate([ref.kb[bounds[1:] - k] for k in (1, 37, 99)])
    qs = np.concatenate([below, ref.kb[rng.choice(n, Bn - below.shape[0],
                                                  replace=False)]])
    qs = qs[rng.permutation(Bn)]

    def scan():
        return SH.range_scan(st, *dev_q(qs), max_items=MAX_ITEMS,
                             engine=eng)
    (gk, sv, em, _, failed), t1 = timed(scan)
    (gk, sv, em, _, failed), t2 = timed(scan)
    check(not failed.any(), "sharded scan: failed lanes")
    want_em, want_kb, want_v = ref.scan(qs, MAX_ITEMS)
    ok = np.arange(MAX_ITEMS)[None, :] < em[:, None]
    got_kb, _ = st.key_rows(gk)
    check(np.array_equal(em, want_em)
          and np.array_equal(np.where(ok[..., None], got_kb, 0), want_kb)
          and np.array_equal(np.where(ok, sv, 0), want_v),
          "sharded scan vs reference")
    kid1, v1, em1, _ = B.range_scan(one, *dev_q(qs), max_items=MAX_ITEMS,
                                    engine=eng)
    kb1 = np.asarray(jnp.take(one.arrays.key_bytes,
                              jnp.maximum(kid1, 0), axis=0))
    check(np.array_equal(em, np.asarray(em1))
          and np.array_equal(np.where(ok, sv, 0),
                             np.where(ok, np.asarray(v1), 0))
          and np.array_equal(np.where(ok[..., None], got_kb, 0),
                             np.where(ok[..., None], kb1, 0)),
          "sharded scan vs the one-chip tree")
    # lanes whose last key lives in a later shard than their start key
    last_kb = want_kb[np.arange(Bn), np.maximum(want_em - 1, 0)]
    crossed = int((np.asarray(SH.route(st.router, *dev_q(last_kb)))
                   > np.asarray(SH.route(st.router, *dev_q(qs)))).sum())
    check(crossed >= 3 * 3, "scans cross the shard boundaries")
    phase("range_scan_sharded", t1, t2, devs, lanes=Bn,
          max_items=MAX_ITEMS, crossing_lanes=crossed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--keys", type=int, default=None,
                    help="loaded keys (default 2^25; 2^12 with "
                         "--cpu-rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip sharded phase")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU backend at a tiny size (kernels "
                         "interpreted); never a measurement")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        # before jax is imported: the backend and device count are fixed
        # at its first use
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    import jax
    devices = jax.devices()
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if devices[0].platform != want:
        sys.exit(f"chip_smoke: JAX found {devices[0].platform!r} devices, "
                 f"not {want!r}; --cpu-rehearsal runs the CPU rehearsal")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} device(s)")
    from repro.jax_cache import use_compile_cache
    emit(phase="setup", compile_cache=use_compile_cache(ROOT),
         platform=devices[0].platform, kind=devices[0].device_kind,
         count=len(devices))
    n = args.keys or (2**12 if args.cpu_rehearsal else 2**25)
    batch = REHEARSAL_BATCH if args.cpu_rehearsal else BATCH
    if args.chips == 4:
        run_four_chips(n, args.seed, batch)
    else:
        run_one_chip(n, args.seed, batch)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
