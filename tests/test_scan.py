"""Scan contract (DESIGN.md §6): the jnp chain walk under any level
backend and the always-sort baseline emit bit-identical ``(key_id, value)``
pairs, ascending, starting at the first key >= the query, on ordered and
dirty (lazily-rearranged) leaves alike; the early-exit walk drains chains
completely when ``max_items`` exceeds the live key count; ``rearranged``
counts exactly the dirty leaves visited and compiles away stats-free."""
import jax.numpy as jnp
import numpy as np
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core import batch_ops as B
from repro.core import keys as K
from repro.core.fbtree import EMPTY, TreeConfig, bulk_build
from repro.core.traverse import TraversalEngine

from benchmarks.common import make_dataset

# the scan's descent under the jnp engine and under the Pallas level kernel
SCAN_BACKENDS = ("jnp", "pallas")


def _build_churned(ds_name, n_keys, seed, dirty=True):
    """Tree + sorted live-key oracle; ``dirty=True`` in-place-inserts extra
    keys so a fraction of leaves have ``leaf_ordered`` cleared."""
    keys, width = make_dataset(ds_name, n_keys, seed=seed)
    ks = K.make_keyset(keys, width)
    cfg = TreeConfig.plan(max_keys=3 * n_keys, key_width=width)
    tree = bulk_build(cfg, ks, np.arange(len(keys), dtype=np.int32))
    if dirty:
        extra, _ = make_dataset(ds_name, n_keys // 4, seed=seed + 1)
        extra = [k for k in extra if k not in set(keys)]
        if extra:
            eks = K.make_keyset(extra, width)
            tree, _, _ = B.insert_batch(
                tree, eks.bytes, eks.lens,
                np.arange(len(extra), dtype=np.int32) + 10 * n_keys)
    return tree, width


def _oracle(tree):
    """(sorted live key ids, their padded bytes/lens, kid → value map)."""
    a = tree.arrays
    occ = np.asarray(a.leaf_occ)
    kid = np.asarray(a.leaf_keyid)[occ]
    val = np.asarray(a.leaf_val)[occ]
    kb = np.asarray(a.key_bytes)[kid]
    kl = np.asarray(a.key_lens)[kid]
    order = np.lexsort([kl] + [np.asarray(K.pack_words(kb))[:, i]
                               for i in range(K.pack_words(kb).shape[1] - 1,
                                              -1, -1)])
    return kid[order], kb[order], kl[order], dict(zip(kid.tolist(),
                                                      val.tolist()))


def _key_tuple(kb_row, kl):
    return (bytes(kb_row.tobytes()), int(kl))


def _expected(tree, qb_row, ql_row, max_items):
    kid, kb, kl, vmap = _oracle(tree)
    q = _key_tuple(qb_row, ql_row)
    sel = [i for i in range(len(kid)) if _key_tuple(kb[i], kl[i]) >= q]
    sel = sel[:max_items]
    return kid[sel], np.asarray([vmap[int(k)] for k in kid[sel]])


@settings(deadline=None, max_examples=6,
          suppress_health_check=list(HealthCheck))
@given(st.sampled_from(("rand-int", "ycsb", "url")), st.booleans(),
       st.integers(0, 2**31 - 1))
def test_scan_backend_parity(ds_name, dirty, seed):
    """jnp and pallas descents, ordered and dirty trees: identical pairs,
    ascending, starting at the first key >= query, EMPTY past ``emitted``;
    ``rearranged`` agrees across backends."""
    tree, width = _build_churned(ds_name, 400, seed % 1000, dirty=dirty)
    a = tree.arrays
    kid_s, kb_s, kl_s, _ = _oracle(tree)
    rng = np.random.default_rng(seed)
    picks = rng.integers(0, len(kid_s), size=24)
    qb = np.asarray(a.key_bytes)[kid_s[picks]].copy()
    ql = np.asarray(a.key_lens)[kid_s[picks]].copy()
    # perturb a third of the queries so scans also start between keys
    flip = rng.random(len(picks)) < 0.33
    qb[flip, -1] ^= 0xA5
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    M = 32

    ref = None
    for backend in SCAN_BACKENDS:
        eng = TraversalEngine(backend=backend)
        kid, val, em, rearr = B.range_scan(tree, qb, ql, max_items=M,
                                           engine=eng)
        sig = tuple(np.asarray(x) for x in (kid, val, em, rearr))
        if ref is None:
            ref = sig
            # semantic checks against the python oracle on the reference
            for i in range(qb.shape[0]):
                ek, ev = _expected(tree, np.asarray(qb)[i],
                                   int(np.asarray(ql)[i]), M)
                n = int(sig[2][i])
                assert n == len(ek), (backend, i, n, len(ek))
                assert (sig[0][i, :n] == ek).all(), (backend, i)
                assert (sig[1][i, :n] == ev).all(), (backend, i)
                assert (sig[0][i, n:] == EMPTY).all(), (backend, i)
        else:
            for got, want, nm in zip(sig, ref,
                                     ("kid", "val", "emitted", "rearranged")):
                assert (got == want).all(), (backend, nm)
        if not dirty:
            assert (sig[3] == 0).all(), (backend, "rearranged clean")


@settings(deadline=None, max_examples=6,
          suppress_health_check=list(HealthCheck))
@given(st.sampled_from(("rand-int", "ycsb")), st.integers(0, 2**31 - 1))
def test_scan_always_sort_bit_identical(ds_name, seed):
    """The lazy-rearrangement fast path changes nothing observable: the
    always-sort baseline (``force_sort=True``) emits bit-identical pairs."""
    tree, _ = _build_churned(ds_name, 300, seed % 1000, dirty=True)
    kid_s, kb_s, kl_s, _ = _oracle(tree)
    rng = np.random.default_rng(seed)
    picks = rng.integers(0, len(kid_s), size=16)
    qb = jnp.asarray(kb_s[picks])
    ql = jnp.asarray(kl_s[picks])
    eng = TraversalEngine("jnp")
    fast = B.range_scan(tree, qb, ql, max_items=24, engine=eng)
    slow = B._range_scan_jnp(tree, qb, ql, 24, eng, force_sort=True)
    for got, want, nm in zip(slow, fast, ("kid", "val", "emitted",
                                          "rearranged")):
        assert (np.asarray(got) == np.asarray(want)).all(), nm


def test_scan_drains_short_chains():
    """Regression for the old fixed hop bound
    (``ceil(max_items / (leaf_fill // 2)) + 1``): after tombstoning most
    keys, leaves hold far fewer live keys than the bound assumed and a
    ``max_items`` larger than the live set must still drain the WHOLE
    chain. The early-exit while_loop walks to chain end; the old unrolled
    loop under-filled here."""
    KW = 12
    rng = np.random.default_rng(7)
    ints = rng.choice(2**31, size=800, replace=False)
    keys = [int(x) for x in ints]
    ks = K.make_keyset(keys, KW)
    cfg = TreeConfig.plan(max_keys=4000, key_width=KW)
    t = bulk_build(cfg, ks, np.arange(800, dtype=np.int32))
    rm = K.make_keyset(keys[:700], KW)
    t, _ = B.remove_batch(t, rm.bytes, rm.lens)
    live = np.sort(ints[700:].astype(np.uint64))

    s0 = K.make_keyset([int(live[0])], KW)
    for eng in (TraversalEngine("jnp"), TraversalEngine("pallas")):
        kid, val, em, _ = B.range_scan(t, s0.bytes, s0.lens, max_items=256,
                                       engine=eng)
        assert int(em[0]) == len(live), (eng.backend, int(em[0]), len(live))
        got = K.decode_uint64(
            np.asarray(t.arrays.key_bytes)[np.asarray(kid[0][:len(live)])][:, :8])
        assert (got == live).all(), eng.backend


def test_scan_rearranged_accounting():
    """``rearranged`` counts the dirty leaves a lane actually visited —
    across ALL hops (the old code only billed hop 0) — is zero on a fresh
    bulk-built tree, zero under a stats-free engine, and identical under
    the jnp and pallas descents."""
    KW = 12
    keys = [int(x) for x in range(0, 4000, 4)]
    ks = K.make_keyset(keys, KW)
    cfg = TreeConfig.plan(max_keys=8192, key_width=KW)
    t = bulk_build(cfg, ks, np.arange(len(keys), dtype=np.int32))
    s = K.make_keyset([0], KW)

    _, _, em, rearr = B.range_scan(t, s.bytes, s.lens, max_items=200)
    assert int(em[0]) == 200
    assert (np.asarray(rearr) == 0).all()          # fresh build: all ordered

    # dirty a mid-chain leaf (in-place fit insert clears leaf_ordered) that
    # a 200-item scan from 0 must cross but the hop-0 leaf does not contain
    ins = K.make_keyset([401], KW)
    t2, _, _ = B.insert_batch(t, ins.bytes, ins.lens,
                              np.asarray([9999], np.int32))
    n_dirty = int((~np.asarray(t2.arrays.leaf_ordered)
                   [:int(t2.arrays.leaf_count)]).sum())
    assert n_dirty == 1
    _, _, _, r_jnp = B.range_scan(t2, s.bytes, s.lens, max_items=200)
    assert int(r_jnp[0]) == 1                      # billed on a later hop
    _, _, _, r_pallas = B.range_scan(t2, s.bytes, s.lens, max_items=200,
                                     engine=TraversalEngine("pallas"))
    assert (np.asarray(r_pallas) == np.asarray(r_jnp)).all()
    # a scan starting past the dirty leaf never visits it
    s2 = K.make_keyset([2000], KW)
    _, _, _, r_far = B.range_scan(t2, s2.bytes, s2.lens, max_items=64)
    assert int(r_far[0]) == 0
    # stats-free engines compile the counter away
    for backend in SCAN_BACKENDS:
        _, _, em_off, r_off = B.range_scan(
            t2, s.bytes, s.lens, max_items=200,
            engine=TraversalEngine(backend, collect_stats=False))
        assert int(em_off[0]) == 200, backend
        assert (np.asarray(r_off) == 0).all(), backend

