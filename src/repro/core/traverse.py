"""Unified traversal engine with pluggable branch *and* descent backends.

Branch resolution — prefix compare + feature comparison + suffix binary
search (paper §3.2–3.4) — is one reusable primitive applied identically at
every inner level. This module is the single entry point for all
root-to-leaf descent. Two backend kinds live in two registries
(DESIGN.md §3):

* **Level backends** resolve ONE inner level for a batch:
  ``fn(level, key_bytes, key_lens, node_ids, qb, ql, collect_stats=...)
  -> (child_ids, stats | None)``. Built-ins:
    - ``"jnp"``            pure-XLA oracle (``core.branch.branch_level``)
    - ``"pallas"``         Pallas feature-comparison kernel
                           (``kernels.feature_branch``; Mosaic-compiled
                           on TPU, interpreted on the CPU test backend)
    - ``"binary"``         classic full-key binary search baseline
    - ``"binary+prefix"``  baseline with prefix skip
  The engine loops them over levels in either layout: ``"tuple"`` unrolls a
  Python loop over the per-level tuple, ``"stacked"`` runs one ``lax.scan``
  over the padded ``[n_levels, C_max, ...]`` Level pytree.

* **Descent backends** resolve the WHOLE root→leaf descent in one call —
  they receive the tree (stacked levels + key pool + leaf arrays) and the
  query batch, and own the per-level loop themselves:
  ``fn(tree, qb, ql, sibling_check=..., collect_stats=...)
  -> (leaf_ids, path, stats | None)``. Built-in: ``"fused"``
  (``kernels.fused_descent`` — one pallas_call keeps the descent resident
  on-core instead of relaunching a kernel per level). A descent backend may
  also expose a fused traverse+probe entry (the hashtag leaf probe as the
  kernel epilogue); ``core.batch_ops`` uses it to collapse descend+probe
  into one launch. Descent backends always consume ``arrays.stacked``, so
  the engine's ``layout`` field is ignored for them.

A third registry holds **scan backends** (DESIGN.md §6): whole-range-scan
kernels ``fn(tree, qb, ql, max_items=..., collect_stats=...)
-> (out_kid, out_val, emitted, rearranged)`` that own descent, sibling hop,
and the leaf-chain walk in one launch. ``core.batch_ops.range_scan``
dispatches through :meth:`TraversalEngine.scan_path`: engines whose backend
registers a scan entry (built-in: ``"fused"`` → ``kernels.fused_scan``)
collapse the scan into that kernel; every other backend falls back to the
jnp chain-walk reference in ``batch_ops`` (which still descends through the
engine's own backend).

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
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .branch import BranchStats, branch_level, to_sibling
from .fbtree import FBTree, Level

__all__ = [
    "TraversalEngine", "DEFAULT_ENGINE", "DescentBackend", "ScanBackend",
    "register_backend", "get_backend", "register_descent_backend",
    "get_descent_backend", "register_scan_backend", "get_scan_backend",
    "available_backends", "backend_kind", "resolve_engine",
]

# fn(level, key_bytes, key_lens, node_ids, qb, ql, collect_stats=...)
#   -> (child_ids, stats | None)
BackendFn = Callable[..., Tuple[jnp.ndarray, Optional[BranchStats]]]

_BACKENDS: Dict[str, BackendFn] = {}
_LAZY_BACKENDS: Dict[str, Callable[[], BackendFn]] = {}


class DescentBackend(NamedTuple):
    """A whole-descent backend (DESIGN.md §3).

    ``traverse(tree, qb, ql, sibling_check=..., collect_stats=...)``
      -> (leaf_ids, path, stats | None) — ``path[l]`` is each query's node
      id at level ``l``, matching ``TraversalEngine.traverse``.
    ``traverse_probe`` (optional) additionally fuses the hashtag leaf probe
      as the epilogue: ``(tree, qb, ql, sibling_check=..., collect_stats=...)
      -> (leaf_ids, path, found, slot, val, bstats | None, lstats | None)``.
    """
    traverse: Callable
    traverse_probe: Optional[Callable] = None


_DESCENT: Dict[str, DescentBackend] = {}
_LAZY_DESCENT: Dict[str, Callable[[], DescentBackend]] = {}

# fn(tree, qb, ql, max_items=..., collect_stats=...)
#   -> (out_kid [B, max_items], out_val [B, max_items], emitted [B],
#       rearranged [B]) — the ``core.batch_ops.range_scan`` contract
# (DESIGN.md §6). ``rearranged`` must be all-zero (and untraced) when
# ``collect_stats`` is off.
ScanBackend = Callable[..., Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                  jnp.ndarray]]

_SCAN: Dict[str, ScanBackend] = {}
_LAZY_SCAN: Dict[str, Callable[[], ScanBackend]] = {}


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


def register_descent_backend(name: str, backend: DescentBackend = None, *,
                             loader: Callable[[], DescentBackend] = None,
                             ) -> None:
    """Register a whole-descent backend (same eager/lazy split as
    :func:`register_backend`)."""
    assert (backend is None) != (loader is None), \
        "pass exactly one of backend/loader"
    if backend is not None:
        _DESCENT[name] = backend
        _LAZY_DESCENT.pop(name, None)
    else:
        _LAZY_DESCENT[name] = loader


def register_scan_backend(name: str, fn: ScanBackend = None, *,
                          loader: Callable[[], ScanBackend] = None) -> None:
    """Register a whole-scan backend (same eager/lazy split as
    :func:`register_backend`). A scan backend rides under the same name as
    the level/descent backend it pairs with (e.g. ``"fused"`` registers
    both a descent and a scan entry); ``range_scan`` dispatches to it via
    :meth:`TraversalEngine.scan_path` (DESIGN.md §6)."""
    assert (fn is None) != (loader is None), "pass exactly one of fn/loader"
    if fn is not None:
        _SCAN[name] = fn
        _LAZY_SCAN.pop(name, None)
    else:
        _LAZY_SCAN[name] = loader


def get_backend(name: str) -> BackendFn:
    if name not in _BACKENDS:
        if name not in _LAZY_BACKENDS:
            raise KeyError(
                f"unknown level backend {name!r}; "
                f"available: {available_backends()}")
        _BACKENDS[name] = _LAZY_BACKENDS.pop(name)()
    return _BACKENDS[name]


def get_descent_backend(name: str) -> DescentBackend:
    if name not in _DESCENT:
        if name not in _LAZY_DESCENT:
            raise KeyError(
                f"unknown descent backend {name!r}; "
                f"available: {available_backends()}")
        _DESCENT[name] = _LAZY_DESCENT.pop(name)()
    return _DESCENT[name]


def get_scan_backend(name: str) -> ScanBackend:
    if name not in _SCAN:
        if name not in _LAZY_SCAN:
            raise KeyError(
                f"unknown scan backend {name!r}; "
                f"available: {available_backends()}")
        _SCAN[name] = _LAZY_SCAN.pop(name)()
    return _SCAN[name]


def available_backends() -> List[str]:
    return sorted(set(_BACKENDS) | set(_LAZY_BACKENDS)
                  | set(_DESCENT) | set(_LAZY_DESCENT)
                  | set(_SCAN) | set(_LAZY_SCAN))


def backend_kind(name: str) -> str:
    """``"level"``, ``"descent"``, or ``"scan"`` for a scan-only name
    (KeyError if unregistered). Names registered in several registries
    report the kind that drives point-op descent: descent > level."""
    if name in _DESCENT or name in _LAZY_DESCENT:
        return "descent"
    if name in _BACKENDS or name in _LAZY_BACKENDS:
        return "level"
    if name in _SCAN or name in _LAZY_SCAN:
        return "scan"
    raise KeyError(f"unknown traversal backend {name!r}; "
                   f"available: {available_backends()}")


def _load_pallas_backend() -> BackendFn:
    from repro.kernels.feature_branch.ops import branch_level_pallas
    return branch_level_pallas


def _load_binary_backend(use_prefix: bool) -> BackendFn:
    from .baseline import branch_level_binary
    return functools.partial(branch_level_binary, use_prefix=use_prefix)


def _load_fused_backend() -> DescentBackend:
    from repro.kernels.fused_descent.ops import (fused_traverse,
                                                 fused_traverse_probe)
    return DescentBackend(fused_traverse, fused_traverse_probe)


def _load_fused_scan_backend() -> ScanBackend:
    from repro.kernels.fused_scan.ops import fused_range_scan
    return fused_range_scan


register_backend("jnp", branch_level)
register_backend("pallas", loader=_load_pallas_backend)
register_backend("binary", loader=functools.partial(_load_binary_backend, False))
register_backend("binary+prefix",
                 loader=functools.partial(_load_binary_backend, True))
register_descent_backend("fused", loader=_load_fused_backend)
register_scan_backend("fused", loader=_load_fused_scan_backend)

LAYOUTS = ("tuple", "stacked")

# the engine refuses "fused" on a TPU rather than interpreting its kernels
# or substituting another backend
_FUSED_NOT_ON_TPU = (
    "the fused descent and scan kernels do not compile for a TPU: Mosaic "
    "refuses their per-lane row gathers, and their whole-array VMEM blocks "
    "cannot hold a deployment-sized tree (ROADMAP A5). Use the 'jnp' or "
    "'pallas' backend.")


@dataclasses.dataclass(frozen=True)
class TraversalEngine:
    """Root-to-leaf descent strategy: (backend, layout, collect_stats).

    ``layout=None`` defers to ``tree.config.stacked`` at trace time, so one
    engine value serves trees of either default layout (descent backends
    ignore layout — they always consume the stacked pytree).
    ``collect_stats=False`` compiles the stats machinery to nothing: the
    returned ``BranchStats`` are all-zero, leaf ids/paths bit-identical.
    """
    backend: str = "jnp"
    layout: Optional[str] = None
    collect_stats: bool = True

    def __post_init__(self):
        # fail at construction, not deep inside the first jit trace
        if self.backend not in available_backends():
            raise ValueError(f"unknown traversal backend {self.backend!r}; "
                             f"available: {available_backends()}")
        if self.layout not in (None,) + LAYOUTS:
            raise ValueError(f"unknown layout {self.layout!r}; "
                             f"expected one of {LAYOUTS} or None")
        if self.backend == "fused" and jax.default_backend() == "tpu":
            raise ValueError(f"traversal backend 'fused' on a TPU: "
                             f"{_FUSED_NOT_ON_TPU}")

    @property
    def kind(self) -> str:
        return backend_kind(self.backend)

    def resolve_layout(self, tree: FBTree) -> str:
        return self.layout or ("stacked" if tree.config.stacked else "tuple")

    def probe_path(self) -> Optional[Callable]:
        """Fused traverse+probe entry of a descent backend, or None — the
        hook ``core.batch_ops._traverse_probe`` collapses to one launch."""
        if self.kind != "descent":
            return None
        return get_descent_backend(self.backend).traverse_probe

    def scan_path(self) -> Optional[ScanBackend]:
        """Whole-scan kernel entry of this engine's backend, or None —
        ``core.batch_ops.range_scan`` collapses the scan to one launch when
        present, and otherwise runs the jnp chain-walk reference (which
        still descends through this engine's backend). DESIGN.md §6."""
        if self.backend in _SCAN or self.backend in _LAZY_SCAN:
            return get_scan_backend(self.backend)
        return None

    def traverse(self, tree: FBTree, qb: jnp.ndarray, ql: jnp.ndarray,
                 sibling_check: bool = True,
                 ) -> Tuple[jnp.ndarray, List[jnp.ndarray], BranchStats]:
        """Descend all inner levels. Returns (leaf_ids, path, stats) where
        ``path[l]`` is each query's node id AT level ``l`` (root first) —
        the parent chain the split path propagates anchors through.

        The descent's ops carry the named scope ``descent``, and under the
        ``tuple`` layout each inner level's ops ``descent/level_<l>``
        (a ``stacked`` scan has one body for every level). Named scopes
        are metadata: the same ops and results, labelled for a trace."""
        with jax.named_scope("descent"):
            return self._traverse(tree, qb, ql, sibling_check)

    def _traverse(self, tree: FBTree, qb: jnp.ndarray, ql: jnp.ndarray,
                  sibling_check: bool):
        B = qb.shape[0]
        cs = self.collect_stats

        if self.kind == "descent":
            d = get_descent_backend(self.backend)
            leaf_ids, path, stats = d.traverse(
                tree, qb, ql, sibling_check=sibling_check, collect_stats=cs)
            return leaf_ids, path, stats if cs else BranchStats.zeros(B)

        a = tree.arrays
        fn = get_backend(self.backend)
        node_ids = jnp.zeros((B,), jnp.int32)   # root = node 0 of level 0
        stats = BranchStats.zeros(B)

        if self.resolve_layout(tree) == "tuple":
            path = []
            for l, level in enumerate(a.levels):
                path.append(node_ids)
                with jax.named_scope(f"level_{l}"):
                    node_ids, s = fn(level, a.key_bytes, a.key_lens,
                                     node_ids, qb, ql, collect_stats=cs)
                if cs:
                    stats = stats + s
        elif cs:
            def step(carry, level: Level):
                ids, st = carry
                child, s = fn(level, a.key_bytes, a.key_lens, ids, qb, ql,
                              collect_stats=True)
                return (child, st + s), ids
            (node_ids, stats), path_arr = jax.lax.scan(
                step, (node_ids, stats), a.stacked)
            path = [path_arr[l] for l in range(len(a.levels))]
        else:
            # stats-free scan: the carry is just the node ids — the stats
            # pytree never enters the compiled loop at all
            def step(ids, level: Level):
                child, _ = fn(level, a.key_bytes, a.key_lens, ids, qb, ql,
                              collect_stats=False)
                return child, ids
            node_ids, path_arr = jax.lax.scan(step, node_ids, a.stacked)
            path = [path_arr[l] for l in range(len(a.levels))]

        if sibling_check:
            node_ids, hops = to_sibling(tree, node_ids, qb, ql)
            if cs:
                stats = stats._replace(
                    sibling_hops=stats.sibling_hops + hops)
        return node_ids, path, stats


DEFAULT_ENGINE = TraversalEngine(backend="jnp", layout=None)


def resolve_engine(engine: Optional[TraversalEngine]) -> TraversalEngine:
    return DEFAULT_ENGINE if engine is None else engine
