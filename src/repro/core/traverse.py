"""Unified traversal engine with pluggable per-level branch backends.

Branch resolution — prefix compare + feature comparison + suffix binary
search (paper §3.2–3.4) — is one reusable primitive applied identically at
every inner level. This module is the single entry point for all
root-to-leaf descent. One registry holds the **level backends**
(DESIGN.md §3), each resolving ONE inner level for a batch:
``fn(level, key_bytes, key_lens, node_ids, qb, ql, collect_stats=...)
-> (child_ids, stats | None)``. Built-ins:

- ``"jnp"``            pure-XLA oracle (``core.branch.branch_level``)
- ``"pallas"``         Pallas feature-comparison kernel
                       (``kernels.feature_branch``; Mosaic-compiled on TPU,
                       interpreted on the CPU test backend)
- ``"binary"``         classic full-key binary search baseline
- ``"binary+prefix"``  baseline with prefix skip

The engine unrolls a Python loop over the per-level tuple
``TreeArrays.levels``, then takes the sibling hop. Range scans descend
through the same engine and walk the leaf chain in ``core.batch_ops``
(DESIGN.md §6).

``TraversalEngine`` is a frozen (hashable) dataclass so it can ride along
as a static jit argument; one engine value == one compiled specialization.
Its static ``collect_stats`` flag is threaded into every backend: with it
off, none of the ``BranchStats`` counter arithmetic is traced (the engine
returns zeros) while leaf ids and paths stay bit-identical — the
stats-free hot path serving and throughput benchmarks run on.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .branch import BranchStats, branch_level, to_sibling
from .fbtree import FBTree

__all__ = [
    "TraversalEngine", "DEFAULT_ENGINE", "register_backend", "get_backend",
    "available_backends", "resolve_engine",
]

# fn(level, key_bytes, key_lens, node_ids, qb, ql, collect_stats=...)
#   -> (child_ids, stats | None)
BackendFn = Callable[..., Tuple[jnp.ndarray, Optional[BranchStats]]]

_BACKENDS: Dict[str, BackendFn] = {}
_LAZY_BACKENDS: Dict[str, Callable[[], BackendFn]] = {}


def register_backend(name: str, fn: BackendFn = None, *,
                     loader: Callable[[], BackendFn] = None) -> None:
    """Register a per-level branch backend (eagerly, or via a deferred
    ``loader`` for backends whose import is heavy or optional)."""
    assert (fn is None) != (loader is None), "pass exactly one of fn/loader"
    if fn is not None:
        _BACKENDS[name] = fn
        _LAZY_BACKENDS.pop(name, None)
    else:
        _LAZY_BACKENDS[name] = loader


def get_backend(name: str) -> BackendFn:
    if name not in _BACKENDS:
        if name not in _LAZY_BACKENDS:
            raise KeyError(
                f"unknown level backend {name!r}; "
                f"available: {available_backends()}")
        _BACKENDS[name] = _LAZY_BACKENDS.pop(name)()
    return _BACKENDS[name]


def available_backends() -> List[str]:
    return sorted(set(_BACKENDS) | set(_LAZY_BACKENDS))


def _load_pallas_backend() -> BackendFn:
    from repro.kernels.feature_branch.ops import branch_level_pallas
    return branch_level_pallas


def _load_binary_backend(use_prefix: bool) -> BackendFn:
    from .baseline import branch_level_binary
    return functools.partial(branch_level_binary, use_prefix=use_prefix)


register_backend("jnp", branch_level)
register_backend("pallas", loader=_load_pallas_backend)
register_backend("binary", loader=functools.partial(_load_binary_backend, False))
register_backend("binary+prefix",
                 loader=functools.partial(_load_binary_backend, True))


@dataclasses.dataclass(frozen=True)
class TraversalEngine:
    """Root-to-leaf descent strategy: (backend, collect_stats).

    ``collect_stats=False`` compiles the stats machinery to nothing: the
    returned ``BranchStats`` are all-zero, leaf ids/paths bit-identical.
    """
    backend: str = "jnp"
    collect_stats: bool = True

    def __post_init__(self):
        # fail at construction, not deep inside the first jit trace
        if self.backend not in available_backends():
            raise ValueError(f"unknown traversal backend {self.backend!r}; "
                             f"available: {available_backends()}")

    def traverse(self, tree: FBTree, qb: jnp.ndarray, ql: jnp.ndarray,
                 sibling_check: bool = True,
                 ) -> Tuple[jnp.ndarray, List[jnp.ndarray], BranchStats]:
        """Descend all inner levels. Returns (leaf_ids, path, stats) where
        ``path[l]`` is each query's node id AT level ``l`` (root first) —
        the parent chain the split path propagates anchors through.

        The descent's ops carry the named scope ``descent``, and each inner
        level's ops ``descent/level_<l>``. Named scopes are metadata: the
        same ops and results, labelled for a trace."""
        with jax.named_scope("descent"):
            return self._traverse(tree, qb, ql, sibling_check)

    def _traverse(self, tree: FBTree, qb: jnp.ndarray, ql: jnp.ndarray,
                  sibling_check: bool):
        B = qb.shape[0]
        cs = self.collect_stats
        a = tree.arrays
        fn = get_backend(self.backend)
        node_ids = jnp.zeros((B,), jnp.int32)   # root = node 0 of level 0
        stats = BranchStats.zeros(B)

        path = []
        for l, level in enumerate(a.levels):
            path.append(node_ids)
            with jax.named_scope(f"level_{l}"):
                node_ids, s = fn(level, a.key_bytes, a.key_lens,
                                 node_ids, qb, ql, collect_stats=cs)
            if cs:
                stats = stats + s

        if sibling_check:
            node_ids, hops = to_sibling(tree, node_ids, qb, ql)
            if cs:
                stats = stats._replace(
                    sibling_hops=stats.sibling_hops + hops)
        return node_ids, path, stats


DEFAULT_ENGINE = TraversalEngine(backend="jnp")


def resolve_engine(engine: Optional[TraversalEngine]) -> TraversalEngine:
    return DEFAULT_ENGINE if engine is None else engine
