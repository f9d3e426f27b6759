"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import keys as K
from repro.core.branch import branch_level
from repro.core.fbtree import TreeConfig, bulk_build
from repro.core.leaf import probe
from repro.kernels.feature_branch.kernel import feature_branch_kernel
from repro.kernels.feature_branch.ops import branch_level_pallas, feature_branch
from repro.kernels.feature_branch.ref import feature_branch_ref
from repro.kernels.leaf_probe.ops import probe_pallas


def _mk_inputs(rng, B, fs, ns, skew=False):
    feats = rng.integers(0, 8 if skew else 256, size=(B, fs, ns),
                         dtype=np.uint8)
    feats.sort(axis=-1)
    qfeat = rng.integers(0, 8 if skew else 256, size=(B, fs), dtype=np.uint8)
    knum = rng.integers(1, ns + 1, size=(B, 1), dtype=np.int32)
    pcmp = rng.integers(-1, 2, size=(B, 1), dtype=np.int32)
    return (jnp.asarray(feats), jnp.asarray(qfeat), jnp.asarray(knum),
            jnp.asarray(pcmp))


@pytest.mark.parametrize("B,fs,ns", [(32, 4, 64), (64, 2, 64), (16, 4, 128),
                                     (128, 8, 64), (256, 1, 32)])
def test_feature_branch_kernel_matches_ref(rng, B, fs, ns):
    for skew in (False, True):
        args = _mk_inputs(rng, B, fs, ns, skew)
        ref = feature_branch_ref(*args)
        tile = min(B, 128)
        got = feature_branch_kernel(*args, tile_b=tile, interpret=True)
        for r, g, name in zip(ref, got,
                              ("idx", "resolved", "lo", "hi", "rounds")):
            # idx is only defined where the kernel resolved the branch
            if name == "idx":
                m = ref[1].astype(bool)
                assert (jnp.where(m, r, 0) == jnp.where(m, g, 0)).all()
            else:
                assert (r == g).all(), name


def test_feature_branch_pad_path(rng):
    args = _mk_inputs(rng, 37, 4, 64)        # B not multiple of tile
    ref = feature_branch_ref(*args)
    got = feature_branch(*args, use_pallas=True)
    m = ref[1].astype(bool)
    assert (jnp.where(m, ref[0], 0) == jnp.where(m, got[0], 0)).all()


@pytest.mark.parametrize("n,width", [(500, 8), (900, 16)])
def test_branch_level_pallas_full_tree(rng, n, width):
    ints = rng.choice(2**48, size=n, replace=False)
    ks = K.make_keyset([int(x) for x in ints], width)
    cfg = TreeConfig.plan(max_keys=2 * n, key_width=width)
    t = bulk_build(cfg, ks, np.arange(n, dtype=np.int32))
    a = t.arrays
    qb, ql = jnp.asarray(ks.bytes[:256]), jnp.asarray(ks.lens[:256])
    node = jnp.zeros((256,), jnp.int32)
    for lvl in a.levels:
        c1, s1 = branch_level(lvl, a.key_bytes, a.key_lens, node, qb, ql)
        c2, s2 = branch_level_pallas(lvl, a.key_bytes, a.key_lens, node,
                                     qb, ql)
        assert (c1 == c2).all()
        assert (s1.feat_rounds == s2.feat_rounds).all()
        node = c1


def test_leaf_probe_kernel(rng):
    n = 700
    ints = rng.choice(2**40, size=n, replace=False)
    ks = K.make_keyset([int(x) for x in ints], 8)
    cfg = TreeConfig.plan(max_keys=2 * n, key_width=8)
    t = bulk_build(cfg, ks, np.arange(n, dtype=np.int32))
    from repro.core.branch import traverse
    qb, ql = jnp.asarray(ks.bytes[:256]), jnp.asarray(ks.lens[:256])
    leaf, _ = traverse(t, qb, ql)
    f1, s1, v1, _ = probe(t, leaf, qb, ql)
    f2, s2, v2, _ = probe_pallas(t, leaf, qb, ql)
    assert (f1 == f2).all() and (s1 == s2).all() and (v1 == v2).all()


# ---------------------------------------------------------------- flash attn
def test_flash_attention_kernel_sweep(rng):
    import jax
    from repro.kernels.flash_attention.kernel import flash_attention_kernel
    from repro.kernels.flash_attention.ref import flash_attention_ref
    for BH, S, T, hd, hv, bq, bk in [(2, 128, 128, 32, 32, 128, 128),
                                     (4, 256, 384, 64, 32, 128, 128),
                                     (1, 512, 256, 16, 16, 256, 128)]:
        q = jnp.asarray(rng.standard_normal((BH, S, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((BH, T, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((BH, T, hv)), jnp.float32)
        for causal, window, pre in [(True, 0, 0), (False, 0, 0),
                                    (True, 48, 0), (True, 0, 33)]:
            got = flash_attention_kernel(
                q, k, v, scale=hd ** -0.5, kv_len=T, causal=causal,
                window=window, prefix_len=pre, block_q=bq, block_k=bk,
                interpret=True)
            ref = flash_attention_ref(
                q, k, v, scale=hd ** -0.5, kv_len=T, causal=causal,
                window=window, prefix_len=pre)
            assert float(jnp.abs(got - ref).max()) < 1e-4


def test_flash_sdpa_gqa_and_grads(rng):
    import jax
    from repro.kernels.flash_attention.ops import flash_sdpa
    from repro.models.attention import MaskSpec, _sdpa_small
    B, S, H, Hk, hd = 2, 200, 8, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hk, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hk, hd)), jnp.float32)
    spec = MaskSpec("causal")
    ref = _sdpa_small(q, k, v, spec, 4)
    got = flash_sdpa(q, k, v, spec, 4, hd ** -0.5)
    assert float(jnp.abs(got - ref).max()) < 1e-4
    g1 = jax.grad(lambda q_: (flash_sdpa(q_, k, v, spec, 4, hd ** -0.5)
                              ** 2).sum())(q)
    g2 = jax.grad(lambda q_: (_sdpa_small(q_, k, v, spec, 4) ** 2).sum())(q)
    assert float(jnp.abs(g1 - g2).max()) < 1e-3


# ------------------------------------------------------- backend selection
@pytest.mark.parametrize("backend,interprets", [("cpu", True), ("tpu", False),
                                                ("gpu", None)])
def test_interpret_mode_follows_the_backend(monkeypatch, backend,
                                            interprets):
    """Kernels interpret only on the CPU and compile on a TPU; any other
    backend raises instead of hiding the device behind the interpreter."""
    import jax
    from repro.core.traverse import TraversalEngine
    from repro.kernels import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interprets is None:
        with pytest.raises(RuntimeError, match="gpu"):
            interpret_mode()
    else:
        assert interpret_mode() is interprets
    TraversalEngine("pallas")
