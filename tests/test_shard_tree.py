"""Sharded-tree subsystem (DESIGN.md §7): routing, cross-shard op parity,
spill-to-next-shard scans, rebalance, and the routed-op mask hooks.

The central contract is *parity*: every batch op on a ``ShardedTree`` is
bit-identical — values, found-ness, emitted counts, resolved key bytes —
to the same op on ONE unsharded tree over the same keys, for shard counts
{1, 2, 4}, across engine backends, on ordered and dirtied leaves alike.
All shard counts and the unsharded reference share one ``TreeConfig`` so
the whole matrix reuses one jit specialization per op.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

from repro import shard as S
from repro.core import batch_ops as B
from repro.core import keys as K
from repro.core.fbtree import EMPTY, TreeConfig, bulk_build, sharded_partition
from repro.core.traverse import TraversalEngine

from benchmarks.common import make_dataset

SHARD_COUNTS = (1, 2, 4)
# shard dispatch passes the engine through untouched; engine parity is
# tests/test_traverse_parity.py's job
ENGINES = (TraversalEngine("jnp"),)


def _dataset_tree(ds_name, n_keys, seed, dirty=False):
    """(unsharded reference tree, KeySet, shared cfg, vals). ``dirty``
    in-place-inserts perturbed keys so some leaves drop ``leaf_ordered``."""
    keys, width = make_dataset(ds_name, n_keys, seed=seed)
    ks = K.make_keyset(keys, width)
    cfg = TreeConfig.plan(max_keys=3 * n_keys, key_width=width)
    vals = np.arange(len(keys), dtype=np.int32)
    return ks, cfg, vals


def _build_both(ks, cfg, vals, n_shards, dirty_ks=None):
    """Unsharded reference + ShardedTree from the same keys (+ optional
    dirtying insert applied to both)."""
    ref = bulk_build(cfg, ks, vals)
    stree = S.sharded_build(ks, vals, n_shards, cfg=cfg)
    if dirty_ks is not None:
        dv = np.arange(dirty_ks.n, dtype=np.int32) + (1 << 20)
        ref, _, _ = B.insert_batch(ref, dirty_ks.bytes, dirty_ks.lens, dv)
        stree, _, _ = S.insert_batch(stree, dirty_ks.bytes, dirty_ks.lens,
                                     dv)
    return ref, stree


def _perturbed_queries(ks, rng, n, miss_frac=0.33):
    """Query batch mixing existing keys and perturbed (mostly-miss) keys."""
    picks = rng.integers(0, ks.n, size=n)
    qb = ks.bytes[picks].copy()
    ql = ks.lens[picks].copy()
    flip = rng.random(n) < miss_frac
    qb[flip, -1] ^= 0xA5
    return qb, ql


def _assert_scan_parity(ref, stree, qb, ql, max_items, engine, ctx):
    k_ref, v_ref, em_ref, _ = B.range_scan(ref, qb, ql,
                                           max_items=max_items,
                                           engine=engine)
    gk, v_sh, em_sh, _, failed = S.range_scan(stree, qb, ql,
                                              max_items=max_items,
                                              engine=engine)
    assert not failed.any(), ctx        # fault-free scans never degrade
    assert (np.asarray(em_ref) == em_sh).all(), ctx
    assert (np.asarray(v_ref) == v_sh).all(), ctx
    # key ids are pool-local — parity is on the resolved key bytes
    sb, sl = stree.key_rows(gk)
    k_ref = np.asarray(k_ref)
    rb = np.asarray(ref.arrays.key_bytes)[np.maximum(k_ref, 0)]
    rb = np.where((k_ref >= 0)[..., None], rb, 0)
    rl = np.where(k_ref >= 0, np.asarray(ref.arrays.key_lens)[
        np.maximum(k_ref, 0)], 0)
    assert (sb == rb).all() and (sl == rl).all(), ctx
    # EMPTY past emitted on both sides
    past = np.arange(max_items)[None, :] >= em_sh[:, None]
    assert (gk[past] == EMPTY).all(), ctx


@settings(deadline=None, max_examples=4,
          suppress_health_check=list(HealthCheck))
@given(st.sampled_from(("rand-int", "ycsb", "url")), st.booleans(),
       st.integers(0, 2**31 - 1))
def test_shard_op_parity(ds_name, dirty, seed):
    """The §7 parity property: lookup / update / insert / remove /
    range_scan on a ShardedTree ≡ the unsharded op, for shard counts
    {1, 2, 4} × engines × ordered/dirty leaves."""
    n_keys = 300
    ks, cfg, vals = _dataset_tree(ds_name, n_keys, seed % 1000)
    rng = np.random.default_rng(seed)
    dirty_ks = None
    if dirty:
        # perturb existing keys: in-place fit inserts that clear
        # leaf_ordered mid-range (same recipe as the scan suite)
        db, dl = _perturbed_queries(ks, rng, 40, miss_frac=1.0)
        uniq = {(bytes(db[i].tobytes()), int(dl[i])) for i in range(40)}
        uniq -= {(bytes(ks.bytes[i].tobytes()), int(ks.lens[i]))
                 for i in range(ks.n)}
        db = np.stack([np.frombuffer(b, np.uint8) for b, _ in uniq])
        dl = np.asarray([l for _, l in uniq], np.int32)
        dirty_ks = K.KeySet(db, dl)

    qb, ql = _perturbed_queries(ks, rng, 48)
    upd_vals = rng.integers(0, 1 << 20, size=48).astype(np.int32)

    for n_shards in SHARD_COUNTS:
        ref, stree = _build_both(ks, cfg, vals, n_shards, dirty_ks)
        if dirty:
            n_dirty = sum(
                int((~np.asarray(t.arrays.leaf_ordered)
                     [:int(t.arrays.leaf_count)]).sum())
                for t in stree.shards)
            assert n_dirty > 0, "dirtying produced no unordered leaves"
        for eng in ENGINES:
            ctx = (ds_name, n_shards, eng.backend, dirty)
            # ---- lookup
            v_ref, rep_ref = B.lookup_batch(ref, qb, ql, engine=eng)
            v_sh, rep_sh = S.lookup_batch(stree, qb, ql, engine=eng)
            f_ref = np.asarray(rep_ref.found)
            assert (f_ref == rep_sh.found).all(), ctx
            assert (np.asarray(v_ref)[f_ref] == v_sh[f_ref]).all(), ctx
            # ---- range scan (covers the spill-to-next-shard path: some
            # queries start near shard boundaries by construction)
            _assert_scan_parity(ref, stree, qb[:16], ql[:16], 48, eng, ctx)

        # ---- mutations (jnp engine; backends share the descent parity
        # suite, and mutation state is engine-independent)
        eng = ENGINES[0]
        ctx = (ds_name, n_shards, "mutations", dirty)
        r2, rep_r = B.update_batch(ref, qb, ql, upd_vals, engine=eng)
        s2, rep_s = S.update_batch(stree, qb, ql, upd_vals, engine=eng)
        assert (np.asarray(rep_r.found) == rep_s.found).all(), ctx
        assert int(rep_r.conflicts) == int(rep_s.conflicts), ctx

        r3, rep_r, _ = B.insert_batch(r2, qb, ql, upd_vals, engine=eng)
        s3, rep_s, _ = S.insert_batch(s2, qb, ql, upd_vals, engine=eng)
        assert (np.asarray(rep_r.found) == rep_s.found).all(), ctx
        assert r3.n_keys_live == s3.n_keys_live, ctx

        r4, rep_r = B.remove_batch(r3, qb[::2], ql[::2], engine=eng)
        s4, rep_s = S.remove_batch(s3, qb[::2], ql[::2], engine=eng)
        assert (np.asarray(rep_r.found) == rep_s.found).all(), ctx
        assert r4.n_keys_live == s4.n_keys_live, ctx

        # post-mutation read-back: every surviving write is identical
        v_ref, rep_ref = B.lookup_batch(r4, qb, ql, engine=eng)
        v_sh, rep_sh = S.lookup_batch(s4, qb, ql, engine=eng)
        f_ref = np.asarray(rep_ref.found)
        assert (f_ref == rep_sh.found).all(), ctx
        assert (np.asarray(v_ref)[f_ref] == v_sh[f_ref]).all(), ctx
        _assert_scan_parity(r4, s4, qb[:8], ql[:8], 64, eng, ctx)


def test_router_boundaries():
    """Router contract: shard s owns [split[s], split[s+1]); shard 0's
    range is open below; equal-to-split routes right; the length
    tie-break matches the byte-compare order."""
    splits = [b"b", b"dd", b"f"]
    router = S.make_router([(np.frombuffer(k.ljust(4, b"\x00"), np.uint8),
                             len(k)) for k in splits])
    qs = [b"a", b"b", b"c", b"d", b"dd", b"dd\x01", b"ddd", b"e", b"f", b"z"]
    ks = K.make_keyset(qs, 4)
    owner = np.asarray(S.route(router, jnp.asarray(ks.bytes),
                               jnp.asarray(ks.lens)))
    #      a  b  c  d  dd dd. ddd e  f  z
    want = [0, 0, 0, 0, 1, 1, 1, 1, 2, 2]
    assert owner.tolist() == want, owner.tolist()


def _route_reference(splits, lens, qb, ql):
    """Plain owner per query: the last split key (bytes, then length) at
    or below it, 0 below every split key."""
    keys = [(bytes(b), int(n)) for b, n in zip(splits, lens)]
    out = []
    for b, n in zip(qb, ql):
        q = (bytes(b), int(n))
        out.append(max([s for s, k in enumerate(keys) if q >= k],
                       default=0))
    return np.asarray(out, np.int32)


def _route_queries(case, st, rng):
    """A query batch for :func:`test_host_route_matches_router`."""
    W = st.config.key_width
    if case == "edges":
        sb = np.asarray(st.router.split_bytes)
        sl = np.asarray(st.router.split_lens)
        rows, lens = [], []
        for b, n in zip(sb, sl):
            for m in (n - 1, n, n + 1):      # the length tie-break decides
                if 1 <= m <= W:
                    r = b.copy()
                    r[m:] = 0
                    rows.append(r)
                    lens.append(m)
        for r, m in ((np.zeros(W, np.uint8), 1),            # below split[0]
                     (np.r_[1, 2, np.zeros(W - 2)], 2),
                     (np.full(W, 0xFF), W),                 # all-0xFF
                     (np.r_[np.full(3, 0xFF), np.zeros(W - 3)], 3)):
            rows.append(np.asarray(r, np.uint8))
            lens.append(m)
        return np.stack(rows), np.asarray(lens, np.int32)
    ql = rng.integers(1, W + 1, size=256).astype(np.int32)
    qb = rng.integers(0, 256, size=(256, W)).astype(np.uint8)
    qb[np.arange(W)[None, :] >= ql[:, None]] = 0
    return qb, ql


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("case", ("random", "edges", "device_batch"))
def test_host_route_matches_router(monkeypatch, case, n_shards):
    """Every batch, a device batch too, is routed once on the host's CPU
    device, where the router lives, over host copies of the keys; the
    owners are bit-identical to ``route`` and to a plain byte compare —
    below the first split key, on a split key, across the length
    tie-break (keys that differ only in trailing zero bytes), all-0xFF."""
    keys = [bytes([8 + 6 * i, 7]) + b"\0" * z
            for i in range(40) for z in range(3)]
    ks = K.make_keyset(keys, 8)
    cfg = TreeConfig.plan(max_keys=512, key_width=8)
    st = S.sharded_build(ks, np.arange(ks.n, dtype=np.int32), n_shards,
                         cfg=cfg)
    qb, ql = _route_queries(case, st, np.random.default_rng(n_shards))
    routed = []

    def spy(router, b, l):
        routed.append(all(isinstance(x, np.ndarray) for x in (b, l)))
        return S.route(router, b, l)
    monkeypatch.setattr(S.ops, "route", spy)
    assert st.router.split_words.devices() == {jax.devices("cpu")[0]}
    args = ((jnp.asarray(qb), jnp.asarray(ql)) if case == "device_batch"
            else (qb, ql))
    owner = S.ops._owner_masks(st, *args, "lookup")
    assert routed == [True]                     # once, over host keys
    assert isinstance(owner, np.ndarray) and owner.dtype == np.int32
    want = np.asarray(S.route(st.router, jnp.asarray(qb), jnp.asarray(ql)))
    assert np.array_equal(owner, want)
    assert np.array_equal(owner, _route_reference(
        np.asarray(st.router.split_bytes), np.asarray(st.router.split_lens),
        qb, ql))
    if case == "edges" and n_shards > 1:
        assert set(owner.tolist()) == set(range(n_shards))


def test_sharded_partition_invariants():
    """Balanced contiguous runs, ascending split keys, sizes differ by at
    most one, and the runs concatenate back to the sorted key set."""
    rng = np.random.default_rng(11)
    keys = sorted({int(x) for x in rng.integers(0, 2**62, size=200)})
    ks = K.make_keyset(keys, 8)
    vals = np.arange(len(keys), dtype=np.int32)
    parts, split_keys = sharded_partition(ks, vals, 3)
    sizes = [p.n for p, _ in parts]
    assert sum(sizes) == len(keys) and max(sizes) - min(sizes) <= 1
    glued = np.concatenate([p.bytes for p, _ in parts])
    order = K.lex_sort_indices(ks)
    assert (glued == ks.bytes[order]).all()
    for (p, pv), (mb, ml) in zip(parts, split_keys):
        assert (p.bytes[0] == mb).all() and int(p.lens[0]) == ml
    # presorted=True on already-sorted input (the rebalance path) is
    # identical to the sorting path
    sks = K.KeySet(ks.bytes[order], ks.lens[order])
    parts2, split2 = sharded_partition(sks, vals[order], 3, presorted=True)
    for (p, pv), (p2, pv2) in zip(parts, parts2):
        assert (p.bytes == p2.bytes).all() and (pv == pv2).all()
    with pytest.raises(ValueError, match="at least one key per shard"):
        sharded_partition(K.make_keyset(keys[:2], 8), vals[:2], 3)


def test_routed_mask_hook():
    """The batch_ops routed-op hook: mask=False lanes are no-ops for
    update / remove / insert (no write, no pool append, not pending)."""
    rng = np.random.default_rng(5)
    keys = sorted({int(x) for x in rng.integers(0, 2**62, size=100)})
    ks = K.make_keyset(keys, 8)
    cfg = TreeConfig.plan(max_keys=400, key_width=8)
    t = bulk_build(cfg, ks, np.arange(len(keys), dtype=np.int32))
    qb, ql = ks.bytes[:8], ks.lens[:8]
    mask = jnp.asarray([True, False] * 4)

    t2, rep = B.update_batch(t, qb, ql, jnp.full((8,), 999, jnp.int32),
                             mask=mask)
    v, _ = B.lookup_batch(t2, qb, ql)
    assert (np.asarray(v) == np.where(np.asarray(mask), 999,
                                      np.arange(8))).all()
    assert np.asarray(rep.found).all()      # found reported mask-blind

    t3, rep = B.remove_batch(t2, qb, ql, mask=mask)
    _, rep2 = B.lookup_batch(t3, qb, ql)
    assert (np.asarray(rep2.found) == ~np.asarray(mask)).all()

    # insert: masked-out NEW keys must not append to the pool
    nks = K.make_keyset([int(x) + 1 for x in keys[:4]], 8)
    kc0 = int(t3.arrays.key_count)
    t4, rep, _ = B.insert_batch(t3, nks.bytes, nks.lens,
                                np.arange(4, dtype=np.int32),
                                mask=jnp.asarray([True, True, False, False]))
    assert int(t4.arrays.key_count) == kc0 + 2
    _, rep3 = B.lookup_batch(t4, nks.bytes, nks.lens)
    assert np.asarray(rep3.found).tolist() == [True, True, False, False]


def test_host_results_keep_device_value_dtype():
    """The arrays a routed lookup and scan combine into have the dtype
    the shards' ``leaf_val`` holds, an empty batch's too: a 64-bit
    ``val_dtype`` with 64-bit JAX off gives 32-bit results, as the device
    arrays do."""
    keys = list(range(0, 600, 3))
    ks = K.make_keyset(keys, 8)
    vals = np.arange(len(keys), dtype=np.int32)
    cfg = TreeConfig.plan(max_keys=800, key_width=8, val_dtype=jnp.int64)
    st = S.sharded_build(ks, vals, 4, cfg=cfg)
    want = st.shards[0].arrays.leaf_val.dtype
    v, rep = S.lookup_batch(st, ks.bytes, ks.lens)
    assert rep.found.all() and v.dtype == want
    assert (v == vals).all()
    v, rep = S.lookup_batch(st, ks.bytes[:0], ks.lens[:0])   # no lanes
    assert v.shape == rep.found.shape == (0,) and v.dtype == want
    _, sv, em, _, _ = S.range_scan(st, ks.bytes[:2], ks.lens[:2], max_items=4)
    assert sv.dtype == want and int(em[0]) == 4


def test_rebalance_recovers_skew():
    """Skewed ingest concentrates keys in one shard; rebalance re-splits
    evenly, preserves every (key, value), and refreshes the router."""
    rng = np.random.default_rng(9)
    base = sorted({int(x) for x in rng.integers(0, 2**40, size=160)})
    ks = K.make_keyset(base, 8)
    vals = np.arange(len(base), dtype=np.int32)
    st = S.sharded_build(ks, vals, 4, max_keys=2000)
    # skew: every new key routes to the LAST shard (beyond current max)
    hot = [int(x) + 2**50 for x in range(200)]
    hks = K.make_keyset(hot, 8)
    st2, _, _ = S.insert_batch(st, hks.bytes, hks.lens,
                               np.arange(200, dtype=np.int32) + 1000)
    counts = [int(t.n_keys_live) for t in st2.shards]
    assert counts[-1] >= 200, counts
    st3, rep = S.rebalance(st2)
    assert rep.n_live == st2.n_keys_live == st3.n_keys_live
    after = list(rep.counts_after)
    assert max(after) - min(after) <= 1, after
    # router moved: splits now cover the hot range
    assert after != list(rep.counts_before)
    # every key still reads back with its value
    allb = np.concatenate([ks.bytes, hks.bytes])
    alll = np.concatenate([ks.lens, hks.lens])
    v, rep2 = S.lookup_batch(st3, allb, alll)
    assert rep2.found.all()
    want = np.concatenate([vals, np.arange(200, dtype=np.int32) + 1000])
    assert (v == want).all()
    # n_shards == 1 degenerates to rebuild: same live set, one shard
    st1 = S.sharded_build(ks, vals, 1, max_keys=2000)
    st1b, rep1 = S.rebalance(st1)
    ref, _ = B.rebuild(st1.shards[0])
    assert st1b.shards[0].n_keys_live == ref.n_keys_live


def test_scan_spills_across_shards():
    """A scan starting in the last leaves of shard s must continue into
    shard s+1 (and further) until max_items — the continuation the leaf
    chain would have provided unsharded."""
    keys = list(range(0, 1200, 3))
    ks = K.make_keyset(keys, 8)
    vals = np.arange(len(keys), dtype=np.int32)
    cfg = TreeConfig.plan(max_keys=1600, key_width=8)
    ref = bulk_build(cfg, ks, vals)
    st = S.sharded_build(ks, vals, 4, cfg=cfg)
    # start just below each shard boundary → must cross into later shards
    starts = [int(K.decode_uint64(np.asarray(sb[:8], np.uint8)[None])[0])
              for sb in np.asarray(st.router.split_bytes)[1:]]
    starts = [s - 2 for s in starts] + [0]
    sks = K.make_keyset(starts, 8)
    M = 150   # > one shard's tail, forces multi-shard merge
    _assert_scan_parity(ref, st, sks.bytes, sks.lens, M,
                        TraversalEngine("jnp"), "boundary spill")
    # drain-to-end: max_items beyond the whole key set stops at the last key
    gk, v, em, _, _ = S.range_scan(st, sks.bytes[-1:], sks.lens[-1:],
                                   max_items=512)
    assert int(em[0]) == len(keys)


def test_scan_spill_into_unhealthy_shard():
    """Degraded-serving contract (DESIGN.md §8): a scan lane that must
    continue into an unhealthy shard is flagged ``failed`` with a
    prefix-correct emission — never a silently truncated 'complete'
    result and never a stale-snapshot splice (contiguity would lie)."""
    from repro.core.faults import FaultPlan, FaultSpec, RetryPolicy
    keys = list(range(0, 1200, 3))
    ks = K.make_keyset(keys, 8)
    vals = np.arange(len(keys), dtype=np.int32)
    cfg = TreeConfig.plan(max_keys=1600, key_width=8)
    st = S.sharded_build(ks, vals, 4, cfg=cfg)
    fast = RetryPolicy(max_attempts=2, sleep=lambda s: None)
    plan = FaultPlan((FaultSpec("shard.dispatch.range_scan",
                                "drop_shard", shard=1),))
    # lane 0 starts just below the shard-1 boundary (must spill into the
    # dropped shard); lane 1 lives entirely inside healthy shard 3
    b1 = np.asarray(st.router.split_bytes)[1]
    # 7 below the boundary: two shard-0 keys (stride 3) precede the spill
    start0 = int(K.decode_uint64(b1[None, :8].astype(np.uint8))[0]) - 7
    start3 = int(K.decode_uint64(np.asarray(
        st.router.split_bytes)[3][None, :8].astype(np.uint8))[0])
    sks = K.make_keyset([start0, start3], 8)
    M = 40  # > shard-0 tail for lane 0, < shard-3 size for lane 1
    gk, v, em, _, failed = S.range_scan(st, sks.bytes, sks.lens,
                                        max_items=M, faults=plan,
                                        retry=fast)
    assert failed.tolist() == [True, False]
    assert st.health.is_ok(1) is False     # retries exhausted -> marked
    # lane 0's emissions are exactly the healthy prefix (shard 0's tail),
    # bit-identical to the fault-free scan up to that point
    gk2, v2, em2, _, f2 = S.range_scan(
        S.sharded_build(ks, vals, 4, cfg=cfg), sks.bytes, sks.lens,
        max_items=M)
    assert not f2.any() and int(em2[0]) == M
    n0 = int(em[0])
    assert 0 < n0 < M, n0                  # partial, and visibly so
    assert (gk[0, :n0] == gk2[0, :n0]).all()
    assert (v[0, :n0] == v2[0, :n0]).all()
    assert (gk[0, n0:] == EMPTY).all()     # no phantom tail
    # the healthy lane is untouched by the other lane's failure
    assert int(em[1]) == int(em2[1])
    assert (gk[1] == gk2[1]).all() and (v[1] == v2[1]).all()


def test_scan_clustered_owners():
    """Regression: a batch whose owners skip middle shards (e.g. {0, 3})
    must still scan the later owners — the shard loop may find no active
    lane at shard 1/2 (lane 0 already filled) but cannot stop there."""
    keys = list(range(0, 1600, 4))
    ks = K.make_keyset(keys, 8)
    vals = np.arange(len(keys), dtype=np.int32)
    cfg = TreeConfig.plan(max_keys=2000, key_width=8)
    ref = bulk_build(cfg, ks, vals)
    st = S.sharded_build(ks, vals, 4, cfg=cfg)
    # lane 0 starts (and fills) in shard 0; lane 1 starts in the LAST shard
    last_min = np.asarray(st.router.split_bytes)[-1]
    qb = np.stack([ks.bytes[0], last_min])
    ql = np.asarray([int(ks.lens[0]), 8], np.int32)
    M = 20  # small: lane 0 fills inside shard 0
    _assert_scan_parity(ref, st, qb, ql, M, TraversalEngine("jnp"),
                        "clustered owners")


def test_shard_public_surface():
    """__all__ exports exist and the deep modules aren't required."""
    import repro.serving as serving
    import repro.shard as shard
    for name in shard.__all__:
        assert hasattr(shard, name), name
    for name in serving.__all__:
        assert hasattr(serving, name), name
    assert "ShardedTree" in shard.__all__
    assert "PrefixCache" in serving.__all__


def test_sharded_prefix_cache_roundtrip(rng):
    """The optional sharded cache mode (DESIGN.md §7): match/publish/evict
    /compact through the shard layer, hits identical to the unsharded
    cache."""
    from repro.serving import PrefixCache
    pc1 = PrefixCache(n_pages=256, block_tokens=16, max_keys=4096)
    pc4 = PrefixCache(n_pages=256, block_tokens=16, max_keys=4096,
                      n_shards=4)
    sysp = rng.integers(0, 500, size=64).astype(np.int32)
    r1 = np.concatenate([sysp, rng.integers(0, 500, 32)]).astype(np.int32)
    r2 = np.concatenate([sysp, rng.integers(0, 500, 32)]).astype(np.int32)
    for pc in (pc1, pc4):
        hit, _ = pc.match([r1])
        assert hit == [0]
        pc.publish(r1, 0)
        hit, pages = pc.match([r2])
        assert hit == [4] and len(pages[0]) == 4
    assert pc4.tree.n_shards == 4
    # a small live set is NOT fragmentation: one leaf per shard is the
    # sharded floor, so the evict-time trigger must not thrash compacts
    assert pc4.frag_factor < pc4.compact_factor
    rep = pc4.compact()          # cross-shard barrier; pages survive
    assert pc4.stats["rebuilds"] == 1
    assert pc4.frag_factor < pc4.compact_factor   # ... and stays cleared
    hit, _ = pc4.match([r1])
    assert hit == [6]


FORCED_MESH_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from repro import shard as S
from repro.core import batch_ops as B, keys as K
from repro.core.fbtree import TreeConfig, bulk_build
assert len(jax.devices()) == 4
rng = np.random.default_rng(1)
keys = sorted({int(x) for x in rng.integers(0, 2**62, size=300)})[:256]
ks = K.make_keyset(keys, 8)
vals = np.arange(len(keys), dtype=np.int32)
cfg = TreeConfig.plan(max_keys=1024, key_width=8)
st = S.sharded_build(ks, vals, 4, cfg=cfg, device=True)
devs = {list(t.arrays.key_count.devices())[0] for t in st.shards}
assert len(devs) == 4, devs
ref = bulk_build(cfg, ks, vals)
v_ref, rep_ref = B.lookup_batch(ref, ks.bytes, ks.lens)
f_ref = np.asarray(rep_ref.found)
want_owner = np.asarray(S.route(st.router, jax.numpy.asarray(ks.bytes),
                                jax.numpy.asarray(ks.lens)))
route = S.ops.route


def host_route_only(router, qb, ql):
    # routed on the host's CPU device over host keys: nothing launched on
    # a shard's device and waited for
    assert router.split_words.devices() == {jax.devices("cpu")[0]}
    assert isinstance(qb, np.ndarray) and isinstance(ql, np.ndarray)
    return route(router, qb, ql)


S.ops.route = host_route_only
home = st.devices[0]
for q in (np.arange(ks.n), np.flatnonzero(want_owner % 2 == 0)):
    v_sh, rep = S.lookup_batch(st, ks.bytes[q], ks.lens[q])
    for x in (v_sh, rep.found):                # futures on shard 0's device
        assert isinstance(x, jax.Array) and x.devices() == {home}
    assert np.array_equal(np.asarray(v_sh), np.asarray(v_ref)[q])
    assert np.array_equal(np.asarray(rep.found), f_ref[q]) and f_ref.all()
    assert np.array_equal(rep.owner, want_owner[q])
    hit = len(set(want_owner[q].tolist()))
    assert (rep.shards_hit, rep.shards_skipped) == (hit, 4 - hit)
assert hit == 2
S.ops.route = route
gk, v, em, _, _ = S.range_scan(st, ks.bytes[:4], ks.lens[:4], max_items=64)
kr, vr, er, _ = B.range_scan(ref, ks.bytes[:4], ks.lens[:4], max_items=64)
assert (np.asarray(er) == em).all() and (np.asarray(vr) == v).all()
print("OK")
"""


def test_forced_multi_device_mesh():
    """End-to-end on a real 4-device mesh (forced CPU devices — the env
    must be set before jax imports, hence the subprocess): one shard per
    device, ops parity across devices. A host batch's lookup routes on
    the host's CPU device and returns device arrays on shard 0's device,
    bit-identical once fetched, with the owners and the hit and skip
    counts of the route."""
    import os
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # the parent may hold the accelerator
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if "PYTHONPATH" in env else "")
    out = subprocess.run([sys.executable, "-c", FORCED_MESH_SNIPPET],
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


CONCURRENT_BUILD_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
from repro import shard as S
from repro.core import keys as K
from repro.core.fbtree import TreeConfig, bulk_build, sharded_partition
assert len(jax.devices()) == 4
rng = np.random.default_rng(3)
keys = sorted({int(x) for x in rng.integers(0, 2**62, size=1200)})[:1024]
ks = K.make_keyset(keys, 8)
vals = rng.integers(0, 2**31 - 1, size=len(keys), dtype=np.int32)
cfg = TreeConfig.plan(max_keys=512, key_width=8)
st = S.sharded_build(ks, vals, 4, cfg=cfg, device=True)
out = {"identical": [], "devices": []}
parts, _ = sharded_partition(ks, vals, 4)
for t, (pks, pv), dev in zip(st.shards, parts, jax.devices()):
    with jax.default_device(dev):
        alone = bulk_build(cfg, pks, pv, device=True)
    a, b = jax.tree_util.tree_leaves(t), jax.tree_util.tree_leaves(alone)
    out["identical"].append(len(a) == len(b) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(a, b)))
    out["devices"].append(sorted({d.id for x in a for d in x.devices()}))
small = TreeConfig.plan(max_keys=128, key_width=8)   # a shard holds 256
try:
    S.sharded_build(ks, vals, 4, cfg=small, device=True)
    out["over_capacity"] = None
except AssertionError as e:
    out["over_capacity"] = str(e)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def concurrent_build():
    """One forced four-device process builds a four-shard tree with
    ``sharded_build(device=True)`` (every shard's build launched before any
    host sync), each shard's slice alone with ``bulk_build(device=True)``,
    and an over-capacity sharded build."""
    import json
    import os
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if "PYTHONPATH" in env else "")
    out = subprocess.run([sys.executable, "-c", CONCURRENT_BUILD_SNIPPET],
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_concurrent_sharded_build_matches_per_shard_build(concurrent_build):
    """Each shard of the concurrently launched build is bit-identical to
    its slice built alone, and lives on its own device."""
    assert concurrent_build["identical"] == [True] * 4
    assert concurrent_build["devices"] == [[0], [1], [2], [3]]


def test_concurrent_sharded_build_raises_capacity_error(concurrent_build):
    """An over-capacity shard raises the same capacity error as a one-tree
    ``bulk_build`` does, before any shard is launched."""
    assert concurrent_build["over_capacity"] == "key_cap exceeded"
