"""Engine contract: every level backend is observationally identical —
same children at every level, same leaf ids, same machine-independent
BranchStats — on randomized trees drawn from the benchmark dataset
distributions, in both stats modes: ``collect_stats=False`` must return
bit-identical leaf ids/paths while compiling the counters away
(DESIGN.md §3)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core import batch_ops as B
from repro.core import keys as K
from repro.core import fsck
from repro.core.fbtree import TreeConfig, bulk_build
from repro.core.traverse import (DEFAULT_ENGINE, TraversalEngine,
                                 available_backends, get_backend)

from benchmarks.common import make_dataset

# the backends whose machine-independent counters follow the paper's
# algorithm (the binary-search baselines count their own)
BACKENDS = ("jnp", "pallas")
LEVEL_BACKENDS = ("jnp", "pallas", "binary", "binary+prefix")

STAT_FIELDS = ("feat_rounds", "suffix_bs", "key_compares", "sibling_hops")


def _build(ds_name, n_keys, seed, fs=4):
    keys, width = make_dataset(ds_name, n_keys, seed=seed)
    ks = K.make_keyset(keys, width)
    cfg = TreeConfig.plan(max_keys=2 * n_keys, key_width=width, fs=fs)
    tree = bulk_build(cfg, ks, np.arange(len(keys), dtype=np.int32))
    return tree, ks


@settings(deadline=None, max_examples=8,
          suppress_health_check=list(HealthCheck))
@given(st.sampled_from(("rand-int", "3-gram", "ycsb", "twitter", "url")),
       st.sampled_from((2, 4)), st.integers(0, 2**31 - 1))
def test_backend_layout_parity(ds_name, fs, seed):
    tree, ks = _build(ds_name, 600, seed % 1000, fs=fs)
    rng = np.random.default_rng(seed)
    # mix of present keys and perturbed (mostly-missing) keys
    idx = rng.integers(0, ks.n, size=192)
    qb = ks.bytes[idx].copy()
    ql = ks.lens[idx].copy()
    flip = rng.random(192) < 0.3
    qb[flip, -1] ^= 0xA5
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)

    results = {}
    for backend in BACKENDS:
        eng = TraversalEngine(backend=backend)
        leaf, path, stats = eng.traverse(tree, qb, ql)
        results[backend] = (np.asarray(leaf),
                            [np.asarray(p) for p in path], stats)
    ref_leaf, ref_path, ref_stats = results["jnp"]
    for combo, (leaf, path, stats) in results.items():
        assert (leaf == ref_leaf).all(), (combo, "leaf ids")
        for lvl, (p, rp) in enumerate(zip(path, ref_path)):
            assert (p == rp).all(), (combo, "children at level", lvl)
        for f in STAT_FIELDS:
            a = np.asarray(getattr(stats, f))
            b = np.asarray(getattr(ref_stats, f))
            assert (a == b).all(), (combo, f)


@settings(deadline=None, max_examples=6,
          suppress_health_check=list(HealthCheck))
@given(st.sampled_from(("ycsb", "url")), st.integers(0, 2**31 - 1))
def test_lookup_reports_identical_across_engines(ds_name, seed):
    tree, ks = _build(ds_name, 400, seed % 1000)
    qb = jnp.asarray(ks.bytes[:128])
    ql = jnp.asarray(ks.lens[:128])
    ref = None
    for backend in BACKENDS:
        vals, rep = B.lookup_batch(tree, qb, ql,
                                   engine=TraversalEngine(backend))
        sig = (np.asarray(vals), np.asarray(rep.found),
               np.asarray(rep.key_compares), np.asarray(rep.suffix_bs),
               np.asarray(rep.feat_rounds))
        if ref is None:
            ref = sig
            assert sig[1].all()   # all present keys found
        for a, b in zip(ref, sig):
            assert (a == b).all(), backend


def test_split_rounds_keep_engines_in_step():
    """Inserts that split leaves rewrite the inner levels in place: after
    them every key is found, the ``jnp`` and ``pallas`` engines return the
    same values, and the tree passes fsck."""
    KW = 12
    keys = [int(x) for x in range(0, 3000, 3)]
    ks0 = K.make_keyset(keys[:100], KW)
    cfg = TreeConfig.plan(max_keys=8192, key_width=KW)
    t = bulk_build(cfg, ks0, np.arange(100, dtype=np.int32))
    ks = K.make_keyset(keys[100:], KW)
    t, rep, _ = B.insert_batch(t, ks.bytes, ks.lens,
                               np.arange(100, 1000, dtype=np.int32))
    assert int(rep.splits) > 0
    allk = K.make_keyset(keys, KW)
    v_j, r_j = B.lookup_batch(t, allk.bytes, allk.lens,
                              engine=TraversalEngine("jnp"))
    v_p, r_p = B.lookup_batch(t, allk.bytes, allk.lens,
                              engine=TraversalEngine("pallas"))
    assert np.asarray(r_j.found).all() and np.asarray(r_p.found).all()
    assert (np.asarray(v_j) == np.arange(len(keys))).all()
    assert (np.asarray(v_p) == np.asarray(v_j)).all()
    report = fsck.check_tree(t)
    assert report.ok, report.violations


@pytest.mark.parametrize("backend", LEVEL_BACKENDS)
@settings(deadline=None, max_examples=6,
          suppress_health_check=list(HealthCheck))
@given(st.sampled_from(("rand-int", "3-gram", "ycsb", "twitter", "url")),
       st.integers(0, 2**31 - 1))
def test_device_built_tree_parity(backend, ds_name, seed):
    """A device-built tree is traversal-equivalent to the host-built tree
    under every level backend (DESIGN.md §5): same leaves, same per-level
    children and the same machine-independent counters; the
    stats-contract backends also reach the ``jnp`` engine's leaves."""
    keys, width = make_dataset(ds_name, 500, seed=seed % 1000)
    ks = K.make_keyset(keys, width)
    cfg = TreeConfig.plan(max_keys=2 * len(keys), key_width=width)
    vals = np.arange(len(keys), dtype=np.int32)
    th = bulk_build(cfg, ks, vals)
    td = bulk_build(cfg, ks, vals, device=True)

    rng = np.random.default_rng(seed)
    idx = rng.integers(0, ks.n, size=160)
    qb = ks.bytes[idx].copy()
    ql = ks.lens[idx].copy()
    flip = rng.random(160) < 0.3
    qb[flip, -1] ^= 0xA5
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)

    eng = TraversalEngine(backend)
    h_leaf, h_path, h_stats = eng.traverse(th, qb, ql)
    d_leaf, d_path, d_stats = eng.traverse(td, qb, ql)
    assert (np.asarray(d_leaf) == np.asarray(h_leaf)).all(), "leaf ids"
    for lvl, (p, rp) in enumerate(zip(d_path, h_path)):
        assert (np.asarray(p) == np.asarray(rp)).all(), \
            ("children at level", lvl)
    for f in STAT_FIELDS:
        assert (np.asarray(getattr(d_stats, f))
                == np.asarray(getattr(h_stats, f))).all(), f
    if backend in BACKENDS:
        ref_leaf, _, _ = DEFAULT_ENGINE.traverse(th, qb, ql)
        assert (np.asarray(d_leaf) == np.asarray(ref_leaf)).all()


def test_rebuild_preserves_engine_parity():
    """After churn + rebuild, every backend still agrees."""
    KW = 12
    keys = [int(x) for x in range(0, 3000, 3)]
    ks0 = K.make_keyset(keys[:100], KW)
    cfg = TreeConfig.plan(max_keys=8192, key_width=KW)
    t = bulk_build(cfg, ks0, np.arange(100, dtype=np.int32))
    ks = K.make_keyset(keys[100:], KW)
    t, rep, _ = B.insert_batch(t, ks.bytes, ks.lens,
                               np.arange(100, 1000, dtype=np.int32))
    assert int(rep.splits) > 0
    rmk = K.make_keyset(keys[::4], KW)
    t, _ = B.remove_batch(t, rmk.bytes, rmk.lens)
    t, brep = B.rebuild(t)
    assert not bool(brep.error)
    allk = K.make_keyset(keys, KW)
    ref = None
    for backend in BACKENDS:
        v, r = B.lookup_batch(t, allk.bytes, allk.lens,
                              engine=TraversalEngine(backend))
        sig = (np.asarray(v), np.asarray(r.found))
        if ref is None:
            ref = sig
            expect = np.array([i % 4 != 0 for i in range(len(keys))])
            assert (sig[1] == expect).all()
        assert (sig[0] == ref[0]).all() and (sig[1] == ref[1]).all(), backend


@settings(deadline=None, max_examples=6,
          suppress_health_check=list(HealthCheck))
@given(st.sampled_from(("rand-int", "ycsb", "url")),
       st.integers(0, 2**31 - 1))
def test_stats_free_path_bit_identical(ds_name, seed):
    """collect_stats=False is observationally identical on leaf ids and
    per-level paths for EVERY level backend, and returns all-zero counters
    (the stats machinery compiles away, DESIGN.md §3)."""
    tree, ks = _build(ds_name, 500, seed % 1000)
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, ks.n, size=160)
    qb = ks.bytes[idx].copy()
    ql = ks.lens[idx].copy()
    flip = rng.random(160) < 0.3
    qb[flip, -1] ^= 0xA5
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)

    for backend in LEVEL_BACKENDS:
        on = TraversalEngine(backend, collect_stats=True)
        off = TraversalEngine(backend, collect_stats=False)
        leaf_on, path_on, _ = on.traverse(tree, qb, ql)
        leaf_off, path_off, stats_off = off.traverse(tree, qb, ql)
        assert (np.asarray(leaf_off) == np.asarray(leaf_on)).all(), \
            (backend, "leaf ids")
        for lvl, (p, rp) in enumerate(zip(path_off, path_on)):
            assert (np.asarray(p) == np.asarray(rp)).all(), \
                (backend, "path at level", lvl)
        for f in stats_off._fields:
            assert (np.asarray(getattr(stats_off, f)) == 0).all(), \
                (backend, f)


def test_stats_free_lookup_matches():
    """The full op pipeline (descend + probe) returns the same
    values/found under a stats-free engine; counters are zero."""
    tree, ks = _build("ycsb", 500, 3)
    qb = jnp.asarray(ks.bytes[:128])
    ql = jnp.asarray(ks.lens[:128])
    v_ref, r_ref = B.lookup_batch(tree, qb, ql,
                                  engine=TraversalEngine("jnp"))
    for backend in BACKENDS:
        eng = TraversalEngine(backend, collect_stats=False)
        v, r = B.lookup_batch(tree, qb, ql, engine=eng)
        assert (np.asarray(v) == np.asarray(v_ref)).all(), backend
        assert (np.asarray(r.found) == np.asarray(r_ref.found)).all()
        for f in ("feat_rounds", "suffix_bs", "key_compares",
                  "lines_touched", "tag_candidates"):
            assert (np.asarray(getattr(r, f)) == 0).all(), (backend, f)


def test_backend_registry():
    """One registry: the four level backends, nothing else."""
    assert available_backends() == sorted(LEVEL_BACKENDS)
    for name in LEVEL_BACKENDS:
        assert callable(get_backend(name))
    with pytest.raises(KeyError):
        get_backend("no-such-backend")
    with pytest.raises(ValueError):
        TraversalEngine(backend="no-such-backend")
    assert DEFAULT_ENGINE.backend == "jnp"
    assert DEFAULT_ENGINE.collect_stats
