"""Compile the index's main-path programs for a TPU v5e without a chip.

The TPU compiler is installed here and compiles for a described topology,
so what Mosaic or XLA would refuse on the chip — an unsupported vector op in
a Pallas kernel, a layout it cannot produce, more VMEM than a kernel may
use — fails here at no chip time. Nothing runs: these tests say nothing
about results or speed, though the compiled text shows where the compiler
copies or loops over a whole array.

This is the only test file that describes the topology, and it does so
inside a fixture, so that one test worker loads the TPU library.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import batch_ops as B
from repro.core.fbtree import (FBTree, TreeConfig, _device_build_jit,
                               _sort_raw, sort_pool)
from repro.core.traverse import TraversalEngine
from repro.kernels.feature_branch import ops as feature_branch_ops
from repro.kernels.feature_branch.kernel import auto_tile
from repro.kernels.feature_branch.kernel import \
    DEFAULT_TILE_B as BRANCH_TILE
from repro.kernels.feature_branch.kernel import feature_branch_kernel
from repro.kernels.leaf_probe.kernel import DEFAULT_TILE_B as PROBE_TILE
from repro.kernels.leaf_probe.kernel import leaf_probe_kernel

NS = 128            # the TPU-natural node width: one full lane row
KEY_W = 24          # YCSB keys
TREE_KEYS = 1 << 16  # compiles in seconds; shapes scale, code does not
# the one-chip YCSB deployment (ns 64): at this size the compiler places
# the leaf store in HBM and picks its layouts as it does on the chip
YCSB_KEYS = (1 << 25) + (1 << 18)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip can be written to the persistent cache
    # but never read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _tree_shapes(one_chip, cfg):
    R = cfg.key_cap + 1
    arrays, _ = jax.eval_shape(
        lambda kb, kl, kt, v, n: _device_build_jit(
            cfg=cfg, kb=kb, kl=kl, ktags=kt, vals=v, n=n),
        jax.ShapeDtypeStruct((R, KEY_W), jnp.uint8),
        jax.ShapeDtypeStruct((R,), jnp.int32),
        jax.ShapeDtypeStruct((R,), jnp.uint8),
        jax.ShapeDtypeStruct((R,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    return FBTree(cfg, jax.tree_util.tree_map(
        lambda s: _sds(one_chip, s.shape, s.dtype), arrays))


@pytest.fixture(scope="module")
def tree(one_chip):
    """Shapes (not arrays) of an ns=128 tree, placed on the described
    chip."""
    return _tree_shapes(one_chip, TreeConfig.plan(
        max_keys=TREE_KEYS, key_width=KEY_W, ns=NS))


@pytest.fixture(scope="module")
def tree64(one_chip):
    """Shapes of the ns=64 YCSB tree at its deployed size."""
    return _tree_shapes(one_chip, TreeConfig.plan(
        max_keys=YCSB_KEYS, key_width=KEY_W, ns=64))


def _queries(one_chip, b):
    return (_sds(one_chip, (b, KEY_W), jnp.uint8),
            _sds(one_chip, (b,), jnp.int32))


STATS_OFF = TraversalEngine(collect_stats=False)


@pytest.mark.parametrize("op", ["lookup", "lookup_pallas", "update",
                                "range_scan"])
def test_index_ops_compile_for_v5e(one_chip, tree, op, monkeypatch):
    # this process's backend is the CPU, which would pick interpret mode;
    # the compile is for the chip, so the kernel must go through Mosaic
    monkeypatch.setattr(feature_branch_ops, "interpret_mode", lambda: False)
    qb, ql = _queries(one_chip, 4096)
    vals = _sds(one_chip, (4096,), jnp.int32)
    if op == "lookup":
        low = B._lookup_batch_jit.lower(tree, qb, ql, True, STATS_OFF)
    elif op == "lookup_pallas":
        low = B._lookup_batch_jit.lower(
            tree, qb, ql, True, TraversalEngine("pallas",
                                                collect_stats=False))
    elif op == "update":
        low = B._update_batch_jit.lower(tree, qb, ql, vals, STATS_OFF)
    else:
        low = B._range_scan_jit.lower(tree, qb, ql, 100, STATS_OFF)
    text = low.compile().as_text()
    # the Pallas engine's levels are Mosaic kernels, not interpreted XLA
    assert ("tpu_custom_call" in text) == (op == "lookup_pallas")


LEAF_PARAM = re.compile(
    r'%([\w.\-]+) = \w+\[([\d,]+)\](\{[^}]*\}) parameter\(\d+\).*'
    r'op_name="tree\[0\]\.leaf_(?:tags|keyid|val|occ)')
SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


def _elements(dims: str) -> int:
    return math.prod(int(d) for d in dims.split(","))


@pytest.mark.parametrize("op", ["lookup", "update"])
def test_ns64_leaf_store_is_used_in_place_on_v5e(one_chip, tree64, op):
    """An ns=64 tree's leaf slot arrays reach the program in a layout its
    gathers and scatters use as they are: no ``copy`` in the entry
    computation takes a leaf-store parameter whole into another layout (a
    relayout of the whole store on every call), and no ``while`` carries an
    operand the size of the flattened store (a point scatter run as a
    serial loop over it). A copy in the parameter's own layout — the
    undonated update's output — is not a relayout."""
    qb, ql = _queries(one_chip, 4096)
    if op == "lookup":
        low = B._lookup_batch_jit.lower(tree64, qb, ql, True, STATS_OFF)
    else:
        low = B._update_batch_jit.lower(tree64, qb, ql,
                                        _sds(one_chip, (4096,), jnp.int32),
                                        STATS_OFF)
    text = low.compile().as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    params = {name: layout for name, _, layout in LEAF_PARAM.findall(entry)}
    assert len(params) == 4, params
    slots = {_elements(dims) for _, dims, _ in LEAF_PARAM.findall(entry)}
    assert len(slots) == 1, slots
    names = "|".join(map(re.escape, params))
    whole_copy = re.compile(r"= \w+\[[\d,]+\](\{[^}]*\})\S* "
                            rf"copy\(%({names})\)")
    relayouts = [ln.strip()[:120] for ln in entry.splitlines()
                 for m in [whole_copy.search(ln)]
                 if m and m.group(1).split(":")[0]
                 != params[m.group(2)].split(":")[0]]
    assert not relayouts, relayouts
    loops = [ln.strip()[:120] for ln in text.splitlines()
             if " while(" in ln and any(
                 _elements(dims) in slots
                 for dims in SHAPE.findall(ln.split(" while(")[0]))]
    assert not loops, loops


def test_build_and_rebuild_sort_compiles_for_v5e(one_chip, tree):
    """The device build's sort of the raw keys and ``rebuild``'s sort of
    the key pool."""
    n = TREE_KEYS
    _sort_raw.lower(tree.config, _sds(one_chip, (n, KEY_W), jnp.uint8),
                    _sds(one_chip, (n,), jnp.int32),
                    _sds(one_chip, (n,), jnp.int32)).compile()
    R = tree.config.key_cap + 1
    sort_pool.lower(_sds(one_chip, (R, KEY_W), jnp.uint8),
                    _sds(one_chip, (R,), jnp.int32),
                    _sds(one_chip, (R,), jnp.uint8),
                    _sds(one_chip, (R,), jnp.int32),
                    _sds(one_chip, (R,), jnp.bool_)).compile()


@pytest.mark.parametrize("b", [32, 4096])
def test_feature_branch_kernel_compiles_for_v5e(one_chip, b):
    fs = 4
    feats = _sds(one_chip, (b, fs, NS), jnp.uint8)
    qfeat = _sds(one_chip, (b, fs), jnp.uint8)
    col = _sds(one_chip, (b, 1), jnp.int32)
    tile = auto_tile(b, BRANCH_TILE)
    for stats in (False, True):
        feature_branch_kernel.lower(
            feats, qfeat, col, col, tile_b=tile, interpret=False,
            collect_stats=stats).compile()


@pytest.mark.parametrize("b", [32, 4096])
def test_leaf_probe_kernel_compiles_for_v5e(one_chip, b):
    tags = _sds(one_chip, (b, NS), jnp.uint8)
    qtag = _sds(one_chip, (b, 1), jnp.uint8)
    leaf_probe_kernel.lower(tags, tags, qtag,
                            tile_b=auto_tile(b, PROBE_TILE),
                            interpret=False).compile()
