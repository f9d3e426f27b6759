"""The folded leaf store (DESIGN.md §1): ``leaf_fold`` leaves per 128-lane
row, ``f`` = 4, 2 and 1 for ``ns`` = 32, 64 and 128, with a leaf capacity
that is not a multiple of ``f``.

Every batch op on a folded tree answers as a plain dict plus sorted-list
oracle does, ``fsck`` passes after each mutation, masked lanes land in the
scratch leaf alone, and the slot helpers agree with the logical
``[LC, ns]`` view."""
import bisect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batch_ops as B
from repro.core import fsck
from repro.core import keys as K
from repro.core.fbtree import EMPTY, TreeConfig, bulk_build
from repro.core.leaf import leaf_at, leaf_counts, leaf_rows, leaf_set

KW = 12
NS_FOLD = [(32, 4), (64, 2), (128, 1)]


def _config(ns: int, want_fold: int) -> TreeConfig:
    """A planned config whose ``leaf_cap + 1`` is not a multiple of the
    fold (where the fold is above 1), so the store carries pad leaves."""
    max_keys = 2048
    while True:
        cfg = TreeConfig.plan(max_keys=max_keys, key_width=KW, ns=ns)
        if want_fold == 1 or (cfg.leaf_cap + 1) % want_fold:
            break
        max_keys += 16
    assert cfg.leaf_fold == want_fold
    assert cfg.leaf_len % want_fold == 0
    assert cfg.leaf_len >= cfg.leaf_cap + 1
    return cfg


def _keys(rng, n, lo=0, hi=10**6):
    nums = rng.choice(np.arange(lo, hi), size=n, replace=False)
    return [b"k%07d" % int(x) for x in nums]


class Oracle:
    """Plain dict of values plus a sorted list of the live keys."""

    def __init__(self, keys, vals):
        self.d = dict(zip(keys, vals))
        self.sorted = sorted(self.d)

    def put(self, k, v):
        if k not in self.d:
            bisect.insort(self.sorted, k)
        self.d[k] = v

    def drop(self, k):
        if self.d.pop(k, None) is not None:
            self.sorted.pop(bisect.bisect_left(self.sorted, k))

    def scan(self, k, n):
        i = bisect.bisect_left(self.sorted, k)
        return self.sorted[i:i + n]


def _check_lookups(tree, oracle, probe_keys):
    ks = K.make_keyset(probe_keys, KW)
    vals, rep = B.lookup_batch(tree, ks.bytes, ks.lens)
    vals, found = np.asarray(vals), np.asarray(rep.found)
    for k, v, f in zip(probe_keys, vals, found):
        assert bool(f) == (k in oracle.d), k
        if f:
            assert int(v) == oracle.d[k], k


def _check_scan(tree, oracle, starts, max_items):
    ks = K.make_keyset(starts, KW)
    out_kid, out_val, emitted, _ = B.range_scan(tree, ks.bytes, ks.lens,
                                                max_items)
    kb = np.asarray(tree.arrays.key_bytes)
    kl = np.asarray(tree.arrays.key_lens)
    out_kid, out_val = np.asarray(out_kid), np.asarray(out_val)
    for i, s in enumerate(starts):
        want = oracle.scan(s, max_items)
        assert int(emitted[i]) == len(want), s
        got = [bytes(kb[k][:kl[k]]) for k in out_kid[i, :len(want)]]
        assert got == want, s
        assert out_val[i, :len(want)].tolist() == [oracle.d[k] for k in want]


def _fsck_ok(tree):
    rep = fsck.check_tree(tree)
    assert rep.ok, rep.violations


@pytest.mark.parametrize("ns,fold", NS_FOLD)
def test_folded_ops_match_oracle(ns, fold):
    rng = np.random.default_rng(ns)
    cfg = _config(ns, fold)
    keys = _keys(rng, 500)
    vals = np.arange(len(keys), dtype=np.int32)
    tree = bulk_build(cfg, K.make_keyset(keys, KW), vals)
    dev = bulk_build(cfg, K.make_keyset(keys, KW), vals, device=True)
    for x, y in zip(jax.tree_util.tree_leaves(tree.arrays),
                    jax.tree_util.tree_leaves(dev.arrays)):
        assert x.shape == y.shape and (np.asarray(x) == np.asarray(y)).all()
    a = tree.arrays
    assert a.leaf_val_f.shape == (cfg.leaf_len // fold, fold * ns)
    assert a.leaf_val.shape == (cfg.leaf_len, ns)
    oracle = Oracle(keys, vals.tolist())
    missing = [k for k in _keys(rng, 200) if k not in oracle.d][:100]
    _check_lookups(tree, oracle, keys + missing)

    # update: half present, half missing, with in-batch duplicates
    batch = list(rng.choice(keys, 150)) + missing[:50]
    uv = np.arange(len(batch), dtype=np.int32) + 5000
    ks = K.make_keyset(batch, KW)
    tree, _ = B.update_batch(tree, ks.bytes, ks.lens, uv)
    for k, v in zip(batch, uv.tolist()):
        if k in oracle.d:
            oracle.d[k] = v
    _fsck_ok(tree)
    _check_lookups(tree, oracle, keys + missing)

    # insert: a dense run between existing keys splits leaves, plus upserts
    lo = int(oracle.sorted[len(oracle.sorted) // 2][1:])
    run = [b"k%07d5" % (lo + i) for i in range(300)]
    batch = run + list(rng.choice(keys, 40))
    iv = np.arange(len(batch), dtype=np.int32) + 90000
    ks = K.make_keyset(batch, KW)
    tree, rep, _ = B.insert_batch(tree, ks.bytes, ks.lens, iv)
    assert int(rep.splits) > 0
    for k, v in zip(batch, iv.tolist()):
        oracle.put(k, v)
    _fsck_ok(tree)
    _check_lookups(tree, oracle, oracle.sorted + missing)

    # remove: present and missing keys
    gone = list(rng.choice(oracle.sorted, 200, replace=False)) + missing[:20]
    ks = K.make_keyset(gone, KW)
    tree, _ = B.remove_batch(tree, ks.bytes, ks.lens)
    for k in gone:
        oracle.drop(k)
    _fsck_ok(tree)
    _check_lookups(tree, oracle, keys + run + missing)
    starts = list(rng.choice(keys + run, 24)) + [b"k", b"k9999999"]
    _check_scan(tree, oracle, starts, 40)

    tree, brep = B.rebuild(tree)
    assert not bool(brep.error)
    assert int(brep.n_live) == len(oracle.d) == tree.n_keys_live
    _fsck_ok(tree)
    _check_lookups(tree, oracle, keys + run + missing)
    _check_scan(tree, oracle, starts, 40)


@pytest.mark.parametrize("ns,fold", NS_FOLD)
def test_masked_lanes_touch_only_the_scratch_leaf(ns, fold):
    """Masked lanes write the scratch leaf's own values back; the leaves
    sharing its stored row (pad leaves here) keep theirs."""
    rng = np.random.default_rng(ns + 1)
    cfg = _config(ns, fold)
    keys = _keys(rng, 300)
    tree = bulk_build(cfg, K.make_keyset(keys, KW),
                      np.arange(len(keys), dtype=np.int32))
    LC = cfg.leaf_len
    dump = LC - 1
    assert dump // fold == (LC // fold) - 1      # the last stored row
    peers = [dump - g for g in range(1, fold)]  # same row, other lanes
    lv = np.array(tree.arrays.leaf_val)
    lk = np.array(tree.arrays.leaf_keyid)
    lv[peers] = 777
    lk[peers] = 4242
    lv[dump] = np.arange(ns)
    tree = tree.replace(leaf_val=lv, leaf_keyid=lk)
    before = [np.asarray(x) for x in jax.tree_util.tree_leaves(tree.arrays)]

    ks = K.make_keyset(keys[:64], KW)
    mask = np.zeros(64, bool)
    t2, _ = B.update_batch(tree, ks.bytes, ks.lens,
                           np.full(64, -5, np.int32), mask=mask)
    t3, _ = B.remove_batch(tree, ks.bytes, ks.lens, mask=mask)
    for t in (t2, t3):
        after = [np.asarray(x) for x in jax.tree_util.tree_leaves(t.arrays)]
        for x, y in zip(before, after):
            assert (x == y).all()

    mask[::2] = True
    t4, _ = B.update_batch(tree, ks.bytes, ks.lens,
                           np.full(64, -5, np.int32), mask=mask)
    v4 = np.asarray(t4.arrays.leaf_val)
    assert (v4[peers] == 777).all()
    assert (v4[dump] == np.arange(ns)).all()
    vals, _ = B.lookup_batch(t4, ks.bytes, ks.lens)
    want = np.where(mask, -5, np.arange(64))
    assert (np.asarray(vals) == want).all()

    # the helper itself: a write to the scratch leaf stays in its lanes
    f = jnp.asarray(np.arange(LC * ns, dtype=np.int32).reshape(LC // fold, -1))
    ids = jnp.full((8,), dump, jnp.int32)
    out = np.asarray(leaf_set(f, ids, jnp.arange(8, dtype=jnp.int32),
                              jnp.full((8,), -1, jnp.int32), ns))
    logical = np.asarray(f).reshape(LC, ns).copy()
    logical[dump, :8] = -1
    assert (out.reshape(LC, ns) == logical).all()


@pytest.mark.parametrize("ns,fold", NS_FOLD)
@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.int32, jnp.float32,
                                   jnp.bool_])
def test_slot_helpers_match_logical_view(ns, fold, dtype):
    rng = np.random.default_rng(7)
    LC = _config(ns, fold).leaf_len
    logical = rng.integers(0, 100, (LC, ns)).astype(dtype)
    folded = jnp.asarray(logical.reshape(LC // fold, fold * ns))
    ids = jnp.asarray(rng.integers(0, LC, 97), jnp.int32)
    slot = jnp.asarray(rng.integers(0, ns, 97), jnp.int32)
    np.testing.assert_array_equal(leaf_rows(folded, ids, ns), logical[ids])
    np.testing.assert_array_equal(leaf_at(folded, ids, slot, ns),
                                  logical[ids, slot])
    np.testing.assert_array_equal(leaf_counts(folded != 0, ns),
                                  (logical != 0).sum(-1))

    uids = jnp.asarray(rng.choice(LC, 40, replace=False), jnp.int32)
    v = jnp.asarray(rng.integers(100, 200, 40).astype(dtype))
    want = jnp.asarray(logical).at[uids, slot[:40]].set(v)
    got = leaf_set(folded, uids, slot[:40], v, ns)
    assert got.shape == folded.shape and got.dtype == folded.dtype
    np.testing.assert_array_equal(np.asarray(got).reshape(LC, ns), want)

    rows = jnp.asarray(rng.integers(100, 200, (40, ns)).astype(dtype))
    want = jnp.asarray(logical).at[uids].set(rows)
    got = leaf_set(folded, uids, None, rows, ns)
    np.testing.assert_array_equal(np.asarray(got).reshape(LC, ns), want)


def test_replace_folds_a_logical_leaf_array():
    cfg = _config(64, 2)
    keys = [b"k%07d" % i for i in range(100)]
    tree = bulk_build(cfg, K.make_keyset(keys, KW),
                      np.arange(100, dtype=np.int32))
    kid = np.array(tree.arrays.leaf_keyid)
    kid[0, 0] = EMPTY
    t2 = tree.replace(leaf_keyid=kid)
    assert t2.arrays.leaf_keyid_f.shape == tree.arrays.leaf_keyid_f.shape
    assert (np.asarray(t2.leaf_keyid) == kid).all()
    assert tree.n_keys_live == 100
