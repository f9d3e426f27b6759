"""FB+-tree core structure (structure-of-arrays, JAX pytree).

Layout mirrors the paper's node structures (Fig. 5) adapted to a pointer-free
structure-of-arrays device representation (DESIGN.md §1):

* inner level ``l`` (level 0 = root, fixed height — upper levels may be
  single-child chains so the compiled traversal is shape-static):
  - ``knum``      number of anchors (== number of children)
  - ``plen``      common-prefix length of the node's anchors
  - ``prefix``    embedded common prefix bytes (the ``tiny``/``huge`` fields)
  - ``features``  ``uint8[fs, ns]`` — byte ``plen+fid`` of every anchor,
    transposed so one row is one SIMD vector (paper §3.3)
  - ``children``  child ids (next level / leaf ids)
  - ``anchors``   key ids (pointers to high keys — the paper stores pointers,
    not key copies; here: indices into the key pool)
* leaves: unsorted kv slots + occupancy bitmap + 1-byte hashtags + high key +
  sibling link + version word (insert/remove bump it; updates do *not* — §4.2).
  The four slot arrays are stored lane-dense: ``leaf_fold`` leaves share one
  128-lane row (DESIGN.md §1); ``TreeArrays.leaf_tags`` etc. are the
  logical ``[LC, ns]`` views.

Anchor convention: ``anchors[i]`` is the minimum key of ``children[i]``'s
subtree; child ``i`` covers ``[anchors[i], anchors[i+1])`` and keys below
``anchors[0]`` descend to child 0.

Construction comes in two parity-locked flavors (DESIGN.md §5):
:func:`bulk_build` is the host numpy reference; ``bulk_build(device=True)``
runs the same algorithm as a jit-compatible jnp pipeline
(:func:`_device_build_from_sorted`) whose only Python loop is over the
O(log n) tree height. Both produce bit-identical ``TreeArrays``; the device
core is also what ``core.batch_ops.rebuild`` re-invokes in-graph to compact
a split-fragmented live tree.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import keys as K

__all__ = ["TreeConfig", "Level", "FBTree", "bulk_build", "tree_to_device",
           "leaf_fold", "stack_levels", "chunk_start", "chunk_of_pos",
           "recompute_inner_meta", "sharded_partition", "sort_pool"]

EMPTY = np.int32(-1)
BIG = jnp.int32(2**30)
LANES = 128  # one TPU vector row


def leaf_fold(ns: int) -> int:
    """Leaves stored per row of the leaf slot arrays: ``128 // ns`` when
    ``ns`` divides a 128-lane row, else 1 (DESIGN.md §1)."""
    return LANES // ns if ns < LANES and LANES % ns == 0 else 1


@dataclasses.dataclass(frozen=True)
class TreeConfig:
    """Static tree geometry (hashable: rides through ``jax.jit`` as aux data).

    Every array in :class:`TreeArrays` has a shape fully determined by this
    config, so one config == one compiled specialization of every batched op.

    Fields:

    * ``key_width``   fixed key-pool row width ``L`` in bytes; keys are
      zero-padded to it (order preserved via the length tie-break,
      ``core.keys``).
    * ``ns``          slots per leaf == anchors per inner node (paper
      default 64).
    * ``fs``          feature bytes per anchor (paper default 4).
    * ``leaf_fill`` / ``inner_fill``  bulk-load & repack target occupancy;
      builds chunk sorted runs into ``ceil(n / fill)`` balanced nodes.
    * ``n_levels``    fixed inner height including root chain. Trees smaller
      than the capacity plan keep the same height via single-child chain
      nodes at the top (free pass-throughs, never billed in stats).
    * ``leaf_cap`` / ``level_caps`` / ``key_cap``  allocation watermark caps;
      arrays are padded to ``cap + 1`` rows, the extra row being the scratch
      row masked scatters dump into (DESIGN.md §1). Per-leaf arrays hold
      :attr:`leaf_len` leaves: ``leaf_cap + 1`` rounded up to a multiple of
      :attr:`leaf_fold`, the scratch leaf last.
    * ``val_dtype``   leaf value dtype.
    * ``stacked``     default descent layout for the traversal engine:
      False = per-level tuple (Python loop), True = stacked
      ``[n_levels, C_max, ...]`` arrays driven by one ``lax.scan``. Both
      layouts are always materialized and kept coherent.
    """
    key_width: int
    ns: int = 64           # slots / anchors per node (paper default 64)
    fs: int = 4            # feature bytes per anchor (paper default 4)
    leaf_fill: int = 48    # bulk-load / repack target occupancy
    inner_fill: int = 48
    n_levels: int = 3      # inner levels incl. root chain
    leaf_cap: int = 1024
    level_caps: Tuple[int, ...] = (1, 16, 256)
    key_cap: int = 65536
    val_dtype: Any = jnp.int32
    # default descent layout for the traversal engine: False = per-level
    # tuple (Python loop), True = stacked [n_levels, C_max, ...] arrays
    # driven by one lax.scan. Both layouts are always materialized.
    stacked: bool = False

    def __post_init__(self):
        # fail at construction with an actionable message instead of a
        # shape explosion (or a silent mis-build) in the first jitted op
        def bad(msg: str):
            raise ValueError(f"TreeConfig: {msg}")
        if self.key_width < 1:
            bad(f"key_width must be >= 1, got {self.key_width} (bytes per "
                f"fixed-width key-pool row)")
        if self.ns < 2:
            bad(f"ns must be >= 2, got {self.ns} — a node needs at least "
                f"two slots to ever split")
        if self.fs < 1:
            bad(f"fs must be >= 1, got {self.fs} (feature bytes per "
                f"anchor)")
        if not (1 <= self.leaf_fill <= self.ns):
            bad(f"leaf_fill must be in [1, ns={self.ns}], got "
                f"{self.leaf_fill} — TreeConfig.plan clamps it for you")
        if not (1 <= self.inner_fill <= self.ns):
            bad(f"inner_fill must be in [1, ns={self.ns}], got "
                f"{self.inner_fill} — TreeConfig.plan clamps it for you")
        if self.n_levels < 1:
            bad(f"n_levels must be >= 1, got {self.n_levels}")
        if len(self.level_caps) != self.n_levels:
            bad(f"level_caps has {len(self.level_caps)} entries for "
                f"n_levels={self.n_levels} — one cap per inner level, "
                f"root first (TreeConfig.plan derives them)")
        if any(c < 1 for c in self.level_caps):
            bad(f"level_caps must all be >= 1, got {self.level_caps}")
        if self.leaf_cap < 1:
            bad(f"leaf_cap must be >= 1, got {self.leaf_cap}")
        if self.key_cap < 1:
            bad(f"key_cap must be >= 1, got {self.key_cap}")

    @property
    def leaf_fold(self) -> int:
        """Leaves per stored row of the leaf slot arrays (:func:`leaf_fold`)."""
        return leaf_fold(self.ns)

    @property
    def leaf_len(self) -> int:
        """Leaves every per-leaf array holds: ``leaf_cap`` plus the scratch
        leaf, rounded up to a multiple of ``leaf_fold``; the scratch leaf is
        the last one."""
        f = self.leaf_fold
        return -(-(self.leaf_cap + 1) // f) * f

    @staticmethod
    def plan(max_keys: int, key_width: int, ns: int = 64, fs: int = 4,
             leaf_fill: int = 48, inner_fill: int = 48,
             val_dtype: Any = jnp.int32, stacked: bool = False) -> "TreeConfig":
        """Capacity planning: fixed height with min-fanout-16 safety margin.

        Guarantees that any key set up to ``max_keys`` (and any tree holding
        at most that many live keys, e.g. after a ``rebuild``) fits the caps:
        ``leaf_cap = ceil(max_keys / max(8, leaf_fill // 3))`` and each inner
        level cap is ``ceil(child_cap / 16)`` up to a single-node root.
        """
        leaf_cap = max(2, -(-max_keys // max(8, leaf_fill // 3)))
        caps: List[int] = []
        c = leaf_cap
        while True:
            c = max(1, -(-c // 16))
            caps.append(c)
            if c == 1:
                break
        caps = caps[::-1]  # root first
        return TreeConfig(key_width=key_width, ns=ns, fs=fs,
                          leaf_fill=min(leaf_fill, ns), inner_fill=min(inner_fill, ns),
                          n_levels=len(caps), leaf_cap=leaf_cap,
                          level_caps=tuple(caps), key_cap=int(max_keys),
                          val_dtype=val_dtype, stacked=stacked)


class Level(NamedTuple):
    """One inner level, ``C = level_caps[l] + 1`` rows (last row = scratch).

    Rows past ``count`` are zeroed pads (``knum=0``,
    ``children=anchors=EMPTY``) that every backend treats as trivial nodes.
    In the stacked layout (:func:`stack_levels`) the same six arrays gain a
    leading ``n_levels`` axis and ``count`` becomes an ``int32[n_levels]``
    vector.
    """
    knum: jnp.ndarray      # int32 [C]
    plen: jnp.ndarray      # int32 [C]
    prefix: jnp.ndarray    # uint8 [C, L]
    features: jnp.ndarray  # uint8 [C, fs, ns]
    children: jnp.ndarray  # int32 [C, ns]
    anchors: jnp.ndarray   # int32 [C, ns]  (key ids)
    count: jnp.ndarray     # int32 scalar — allocation watermark


LEAF_SLOT_VIEWS = ("leaf_tags", "leaf_keyid", "leaf_val", "leaf_occ")


class TreeArrays(NamedTuple):
    """All tree state as a flat pytree of device arrays.

    Shapes below use ``KC = key_cap + 1``, ``LC = leaf_len`` (``leaf_cap``
    plus the scratch leaf, rounded up to a multiple of ``f = leaf_fold``;
    DESIGN.md §1), ``L = key_width``, ``ns`` slots.

    The leaf slot arrays are stored folded, ``[LC // f, f * ns]``: leaf
    ``i`` is row ``i // f``, lanes ``(i % f) * ns`` to ``+ ns`` — the
    logical ``[LC, ns]`` array reshaped, so a 64-slot leaf store fills
    128-lane rows. Hot paths read and write them through
    ``core.leaf.leaf_rows``/``leaf_at``/``leaf_set``; ``leaf_tags``,
    ``leaf_keyid``, ``leaf_val`` and ``leaf_occ`` are read-only logical
    views for cold paths (``FBTree.replace`` folds them back).

    Invariants the parity/property suites check
    (``tests/test_traverse_parity.py``, ``tests/test_tree_ops.py``):

    * key-pool rows ``[0, key_count)`` hold valid keys; rows at or above the
      watermark (and the scratch row) are zero.
    * ``levels`` and ``stacked`` describe the same tree: re-deriving
      ``stacked`` via :func:`stack_levels` is a no-op, and every
      backend × layout combination descends to identical leaves with
      identical machine-independent stats (DESIGN.md §3).
    * each live key id appears in exactly one occupied leaf slot;
      ``leaf_high``/``leaf_next`` order leaves ascending with the last
      leaf's high key ``EMPTY`` (= +inf).
    * ``leaf_version`` bumps on insert/remove but never on update
      (paper §4.2); a fresh build resets versions to zero (DESIGN.md §5).
    """
    key_bytes: jnp.ndarray   # uint8 [KC, L]
    key_lens: jnp.ndarray    # int32 [KC]
    key_tags: jnp.ndarray    # uint8 [KC] hash fingerprints (computed at append)
    key_count: jnp.ndarray   # int32 scalar
    levels: Tuple[Level, ...]
    stacked: Level           # same levels, stacked+padded to [n_levels, C_max, ...]
    leaf_tags_f: jnp.ndarray   # uint8 [LC // f, f * ns]
    leaf_keyid_f: jnp.ndarray  # int32 [LC // f, f * ns] (-1 empty)
    leaf_val_f: jnp.ndarray    # val_dtype [LC // f, f * ns]
    leaf_occ_f: jnp.ndarray    # bool [LC // f, f * ns]
    leaf_high: jnp.ndarray   # int32 [LC] key id, -1 = +inf
    leaf_next: jnp.ndarray   # int32 [LC]
    leaf_version: jnp.ndarray  # int32 [LC]
    leaf_ordered: jnp.ndarray  # bool [LC]
    leaf_count: jnp.ndarray    # int32 scalar

    def _unfold(self, folded):
        return folded.reshape(self.leaf_high.shape[0], -1)

    @property
    def leaf_tags(self):     # uint8 [LC, ns]
        return self._unfold(self.leaf_tags_f)

    @property
    def leaf_keyid(self):    # int32 [LC, ns]
        return self._unfold(self.leaf_keyid_f)

    @property
    def leaf_val(self):      # val_dtype [LC, ns]
        return self._unfold(self.leaf_val_f)

    @property
    def leaf_occ(self):      # bool [LC, ns]
        return self._unfold(self.leaf_occ_f)


@jax.tree_util.register_pytree_node_class
class FBTree:
    """Pytree wrapper: arrays are leaves, config is static aux data."""

    def __init__(self, config: TreeConfig, arrays: TreeArrays):
        self.config = config
        self.arrays = arrays

    def tree_flatten(self):
        return (self.arrays,), self.config

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux, children[0])

    # convenience accessors
    def __getattr__(self, name):
        if name in TreeArrays._fields or name in LEAF_SLOT_VIEWS:
            return getattr(self.arrays, name)
        raise AttributeError(name)

    def replace(self, **kw) -> "FBTree":
        """``arrays._replace``; a logical ``[LC, ns]`` leaf slot array given
        under its view name (``leaf_val=`` …) is folded into storage."""
        a = self.arrays
        for name in LEAF_SLOT_VIEWS:
            if name in kw:
                kw[name + "_f"] = jnp.reshape(
                    kw.pop(name), getattr(a, name + "_f").shape)
        return FBTree(self.config, a._replace(**kw))

    @property
    def n_keys_live(self) -> int:
        return int(jnp.sum(self.arrays.leaf_occ_f))


def stack_levels(levels: Tuple[Level, ...]) -> Level:
    """Stack per-level arrays into one padded [n_levels, C_max, ...] Level.

    Rows past a level's own cap are knum=0 / children=anchors=EMPTY, so a
    backend treats them as trivial nodes (well-formed descents never land on
    them). ``count`` becomes an int32 [n_levels] vector. Pure jnp: callable
    under jit, so mutating ops can refresh the stacked copy in-graph. This is
    the level-synchronous layout the ``lax.scan`` descent consumes
    (DESIGN.md §3); both builders materialize it alongside ``levels``.
    """
    C_max = max(l.knum.shape[0] for l in levels)

    def pad(a, fillv):
        short = C_max - a.shape[0]
        if short == 0:
            return a
        return jnp.concatenate(
            [a, jnp.full((short,) + a.shape[1:], fillv, a.dtype)], axis=0)

    return Level(
        knum=jnp.stack([pad(l.knum, 0) for l in levels]),
        plen=jnp.stack([pad(l.plen, 0) for l in levels]),
        prefix=jnp.stack([pad(l.prefix, 0) for l in levels]),
        features=jnp.stack([pad(l.features, 0) for l in levels]),
        children=jnp.stack([pad(l.children, EMPTY) for l in levels]),
        anchors=jnp.stack([pad(l.anchors, EMPTY) for l in levels]),
        count=jnp.stack([l.count for l in levels]),
    )


# --------------------------------------------------------------------------
# shared segmented-construction primitives (host build, device build, and the
# batch_ops split path all use these — DESIGN.md §5)
# --------------------------------------------------------------------------

def chunk_of_pos(p, base, rem):
    """Chunk index of position ``p`` under balanced chunking.

    ``n`` items over ``c`` chunks with ``base = n // c``, ``rem = n % c``:
    the first ``rem`` chunks hold ``base + 1`` items, the rest ``base``.
    """
    cut = (base + 1) * rem
    return jnp.where(p < cut, p // jnp.maximum(base + 1, 1),
                     rem + (p - cut) // jnp.maximum(base, 1)).astype(jnp.int32)


def chunk_start(c, base, rem):
    """First item position of chunk ``c`` (inverse of :func:`chunk_of_pos`)."""
    return jnp.where(c <= rem, c * (base + 1),
                     rem * (base + 1) + (c - rem) * base).astype(jnp.int32)


def recompute_inner_meta(kb_store, kl_store, anchors, knum, fs):
    """Segmented reduction deriving ``plen``/``prefix``/``features`` for a
    block of inner nodes from their anchor key ids. ``anchors`` is ``[R, ns]``
    with ``EMPTY`` pads; invalid lanes contribute the identity.

    The common-prefix length is the first byte column where some valid anchor
    differs from anchor 0, clipped by the shortest anchor length and the key
    width; feature row ``f`` is byte ``plen + f`` of every anchor (0 when past
    the key width). Shared verbatim by the device build and the insert split
    path so split-produced and built nodes agree byte-for-byte.
    """
    R, ns = anchors.shape
    L = kb_store.shape[-1]
    aid = jnp.maximum(anchors, 0)
    akb = kb_store[aid]                       # [R, ns, L]
    akl = kl_store[aid]
    lane = jnp.arange(ns, dtype=jnp.int32)[None, :]
    valid = lane < knum[:, None]
    first = akb[:, :1, :]
    same = (akb == first) | ~valid[:, :, None]
    allsame = same.all(axis=1)                # [R, L]
    plen = jnp.where(allsame.all(-1), L,
                     jnp.argmin(allsame.astype(jnp.int32), axis=-1))
    minlen = jnp.min(jnp.where(valid, akl, BIG), axis=-1)
    plen = jnp.minimum(plen, jnp.minimum(minlen, L)).astype(jnp.int32)
    prefix = akb[:, 0, :]
    feats = []
    for f in range(fs):
        pos = jnp.clip(plen + f, 0, L - 1)        # [R]
        byte = jnp.take_along_axis(
            akb, jnp.broadcast_to(pos[:, None, None], (R, ns, 1)), axis=-1)[..., 0]
        byte = jnp.where(((plen + f)[:, None] < L) & valid, byte, 0)
        feats.append(byte.astype(jnp.uint8))
    features = jnp.stack(feats, axis=1)       # [R, fs, ns]
    return plen, prefix, features


# --------------------------------------------------------------------------
# host (numpy) build — the parity reference
# --------------------------------------------------------------------------

def _common_prefix_len(kb: np.ndarray, kl: np.ndarray) -> Tuple[int, np.ndarray]:
    """plen + prefix bytes over rows of a [k, L] anchor byte block."""
    L = kb.shape[1]
    if kb.shape[0] == 1:
        pl = int(min(kl[0], L))
        return pl, kb[0]
    eq = (kb == kb[:1]).all(axis=0)           # [L]
    neq = np.nonzero(~eq)[0]
    pl = int(neq[0]) if neq.size else L
    pl = int(min(pl, kl.min()))
    return pl, kb[0]


def _build_inner_level_np(cfg: TreeConfig, child_min_keyid: np.ndarray,
                          key_bytes: np.ndarray, key_lens: np.ndarray,
                          fill: int) -> Tuple[dict, np.ndarray]:
    """Group children into inner nodes; return level arrays + per-node min key id."""
    ns, fs, L = cfg.ns, cfg.fs, cfg.key_width
    n_child = child_min_keyid.shape[0]
    n_nodes = max(1, -(-n_child // fill))
    # balanced grouping
    base = n_child // n_nodes
    rem = n_child % n_nodes
    sizes = np.full(n_nodes, base, dtype=np.int64)
    sizes[:rem] += 1
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])

    knum = np.zeros(n_nodes, dtype=np.int32)
    plen = np.zeros(n_nodes, dtype=np.int32)
    prefix = np.zeros((n_nodes, L), dtype=np.uint8)
    features = np.zeros((n_nodes, fs, ns), dtype=np.uint8)
    children = np.full((n_nodes, ns), EMPTY, dtype=np.int32)
    anchors = np.full((n_nodes, ns), EMPTY, dtype=np.int32)
    node_min = np.zeros(n_nodes, dtype=np.int32)

    for i in range(n_nodes):
        s, k = int(starts[i]), int(sizes[i])
        ids = child_min_keyid[s:s + k]
        kb = key_bytes[ids]
        kl = key_lens[ids]
        pl, pfx = _common_prefix_len(kb, kl)
        knum[i] = k
        plen[i] = pl
        prefix[i] = pfx
        for f in range(fs):
            pos = pl + f
            if pos < L:
                features[i, f, :k] = kb[:, pos]
        children[i, :k] = np.arange(s, s + k, dtype=np.int32)
        anchors[i, :k] = ids
        node_min[i] = ids[0]
    return dict(knum=knum, plen=plen, prefix=prefix, features=features,
                children=children, anchors=anchors, count=np.int32(n_nodes)), node_min


def _check_capacity(cfg: TreeConfig, n: int) -> None:
    """Host-side mirror of the device build's capacity checks."""
    assert n <= cfg.key_cap, "key_cap exceeded"
    assert cfg.leaf_fill <= cfg.ns and cfg.inner_fill <= cfg.ns, \
        "fill targets cannot exceed ns slots (TreeConfig.plan clamps them)"
    c = max(1, -(-n // cfg.leaf_fill))
    assert c <= cfg.leaf_cap, "leaf_cap exceeded"
    for lvl in range(cfg.n_levels - 1, -1, -1):
        c = max(1, -(-c // cfg.inner_fill))
        assert c <= cfg.level_caps[lvl], f"level {lvl}: {c} > cap"
    assert c == 1, "tree too shallow for n_levels — use TreeConfig.plan"


def bulk_build(cfg: TreeConfig, ks: K.KeySet, vals: np.ndarray,
               device: bool = False) -> FBTree:
    """Bulk-load a tree from (possibly unsorted) unique keys.

    ``device=False`` (default) runs the numpy host reference: sort on host,
    chunk the sorted run into balanced leaves, then group bottom-up into
    inner levels, padding to the fixed height with single-child chain nodes.

    ``device=True`` runs the jit-compatible device pipeline (DESIGN.md §5):
    sort via packed-word argsorts (:func:`_sort_raw`), build leaves and
    every inner level with segmented jnp reductions
    (:func:`recompute_inner_meta`), the only Python loop being over the
    O(log n) height. Both paths produce
    bit-identical :class:`TreeArrays` (including the stacked layout) — the
    equivalence tests in ``tests/test_tree_ops.py`` pin this contract.

    Shapes: ``ks.bytes`` is ``uint8 [n, key_width]``, ``ks.lens`` ``int32
    [n]``, ``vals`` ``[n]`` (cast to ``cfg.val_dtype``). Raises on capacity
    overflow (``key_cap`` / ``leaf_cap`` / ``level_caps``).
    """
    ns, fs, L = cfg.ns, cfg.fs, cfg.key_width
    n = ks.n
    _check_capacity(cfg, n)
    if device:
        return _bulk_build_device(cfg, ks, vals)
    order = K.lex_sort_indices(ks)
    # every array gets one trailing scratch row (index cap) so masked scatters
    # have a conflict-free dump target; the watermarks never reach it.
    kb = np.zeros((cfg.key_cap + 1, L), dtype=np.uint8)
    kl = np.zeros((cfg.key_cap + 1,), dtype=np.int32)
    kb[:n] = ks.bytes[order]
    kl[:n] = ks.lens[order]
    vv = np.asarray(vals)[order]

    # ---- leaves ----
    fill = cfg.leaf_fill
    n_leaves = max(1, -(-n // fill))
    assert n_leaves <= cfg.leaf_cap, "leaf_cap exceeded"
    LC = cfg.leaf_len  # + scratch row, a multiple of leaf_fold
    leaf_tags = np.zeros((LC, ns), dtype=np.uint8)
    leaf_keyid = np.full((LC, ns), EMPTY, dtype=np.int32)
    leaf_val = np.zeros((LC, ns), dtype=np.asarray(vals).dtype)
    leaf_occ = np.zeros((LC, ns), dtype=bool)
    leaf_high = np.full((LC,), EMPTY, dtype=np.int32)
    leaf_next = np.full((LC,), EMPTY, dtype=np.int32)

    tags_all = K.fnv1a_tags(kb[:n], kl[:n])
    base = n // n_leaves
    rem = n % n_leaves
    sizes = np.full(n_leaves, base, dtype=np.int64)
    sizes[:rem] += 1
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    leaf_min = np.zeros(n_leaves, dtype=np.int32)
    for i in range(n_leaves):
        s, k = int(starts[i]), int(sizes[i])
        leaf_keyid[i, :k] = np.arange(s, s + k, dtype=np.int32)
        leaf_val[i, :k] = vv[s:s + k]
        leaf_tags[i, :k] = tags_all[s:s + k]
        leaf_occ[i, :k] = True
        leaf_min[i] = s
        leaf_next[i] = i + 1 if i + 1 < n_leaves else EMPTY
        leaf_high[i] = s + k if i + 1 < n_leaves else EMPTY

    # ---- inner levels bottom-up ----
    levels_np: List[dict] = []
    child_min = leaf_min
    lvl_arrays, node_min = _build_inner_level_np(cfg, child_min, kb, kl, cfg.inner_fill)
    levels_np.append(lvl_arrays)
    while levels_np[-1]["knum"].shape[0] > 1:
        prev_n = levels_np[-1]["knum"].shape[0]
        lvl_arrays, node_min = _build_inner_level_np(cfg, node_min, kb, kl, cfg.inner_fill)
        levels_np.append(lvl_arrays)
        assert lvl_arrays["knum"].shape[0] < prev_n
    # pad to fixed height with single-child chain roots
    while len(levels_np) < cfg.n_levels:
        ids = node_min[:1]
        pl, pfx = _common_prefix_len(kb[ids], kl[ids])
        feat = np.zeros((1, fs, ns), dtype=np.uint8)
        for f in range(fs):
            if pl + f < L:
                feat[0, f, 0] = kb[ids[0], pl + f]
        levels_np.append(dict(
            knum=np.array([1], np.int32), plen=np.array([pl], np.int32),
            prefix=pfx[None].copy(), features=feat,
            children=np.full((1, ns), EMPTY, np.int32),
            anchors=np.full((1, ns), EMPTY, np.int32),
            count=np.int32(1)))
        levels_np[-1]["children"][0, 0] = 0
        levels_np[-1]["anchors"][0, 0] = ids[0]
    levels_np = levels_np[::-1]  # root first
    assert len(levels_np) == cfg.n_levels, (len(levels_np), cfg.n_levels)

    # pad each level to its cap (+1 scratch row)
    levels: List[Level] = []
    for li, lv in enumerate(levels_np):
        cap = cfg.level_caps[li]
        cur = lv["knum"].shape[0]
        assert cur <= cap, f"level {li}: {cur} > cap {cap}"

        def pad(a, fillv=0):
            out_shape = (cap + 1,) + a.shape[1:]
            out = np.full(out_shape, fillv, dtype=a.dtype)
            out[:cur] = a
            return out

        levels.append(Level(
            knum=jnp.asarray(pad(lv["knum"])),
            plen=jnp.asarray(pad(lv["plen"])),
            prefix=jnp.asarray(pad(lv["prefix"])),
            features=jnp.asarray(pad(lv["features"])),
            children=jnp.asarray(pad(lv["children"], EMPTY)),
            anchors=jnp.asarray(pad(lv["anchors"], EMPTY)),
            count=jnp.asarray(lv["count"]),
        ))

    ktags = np.zeros((cfg.key_cap + 1,), dtype=np.uint8)
    ktags[:n] = tags_all
    fold = lambda x: jnp.asarray(x.reshape(LC // cfg.leaf_fold, -1))
    arrays = TreeArrays(
        key_bytes=jnp.asarray(kb), key_lens=jnp.asarray(kl),
        key_tags=jnp.asarray(ktags),
        key_count=jnp.asarray(np.int32(n)),
        levels=tuple(levels),
        stacked=stack_levels(tuple(levels)),
        leaf_tags_f=fold(leaf_tags), leaf_keyid_f=fold(leaf_keyid),
        leaf_val_f=fold(leaf_val).astype(cfg.val_dtype),
        leaf_occ_f=fold(leaf_occ),
        leaf_high=jnp.asarray(leaf_high), leaf_next=jnp.asarray(leaf_next),
        leaf_version=jnp.zeros((LC,), jnp.int32),
        leaf_ordered=jnp.asarray(np.arange(LC) < n_leaves),
        leaf_count=jnp.asarray(np.int32(n_leaves)),
    )
    return FBTree(cfg, arrays)


# --------------------------------------------------------------------------
# device (jnp) build — jit-compatible, traced key count (DESIGN.md §5)
# --------------------------------------------------------------------------

def _device_build_from_sorted(cfg: TreeConfig, kb, kl, ktags, vals, n):
    """Construct :class:`TreeArrays` from a sorted, compacted key pool.

    Inputs are pool-shaped (``[key_cap + 1, ...]``) with rows ``[0, n)``
    holding the keys in ascending order and zeros everywhere else; ``n`` may
    be a *traced* int32 (the caller under jit — e.g.
    ``core.batch_ops.rebuild`` — does not know the live count at trace
    time). Returns ``(arrays, error)`` where ``error`` flags a capacity
    overflow (arrays are then shape-valid garbage; callers must discard).

    The pipeline (DESIGN.md §5): balanced chunking of the sorted run into
    leaves via a pure gather grid (no scatter conflicts), then one bottom-up
    pass per inner level — uniform grouping plus
    :func:`recompute_inner_meta` segmented reductions. Grouping a
    single-child run yields exactly the host build's chain-node padding, so
    no special casing is needed for under-full trees and the result is
    bit-identical to the host path.
    """
    ns, fs, L = cfg.ns, cfg.fs, cfg.key_width
    KC = cfg.key_cap
    LC, f = cfg.leaf_len, cfg.leaf_fold
    n = jnp.asarray(n, jnp.int32)
    lane = jnp.arange(ns, dtype=jnp.int32)

    # ---- leaves: balanced chunking of the sorted key run ----
    n_leaves = jnp.maximum(1, -(-n // jnp.int32(cfg.leaf_fill)))
    base, rem = n // n_leaves, n % n_leaves
    start = lambda l: chunk_start(l, base, rem)
    size = lambda l: base + (l < rem).astype(jnp.int32)
    li = jnp.arange(LC, dtype=jnp.int32)
    lstart, lsize, lexists = start(li), size(li), li < n_leaves   # [LC]
    # the slot grid in folded shape [LC // f, f * ns] (DESIGN.md §1): each
    # lane's leaf and slot come from iotas, so nothing [LC, ns] is built
    if f == 1:
        gstart, gsize, gexists = lstart[:, None], lsize[:, None], lexists[:, None]
        slot = lane[None, :]
    else:
        g = jnp.arange(f * ns, dtype=jnp.int32)
        gl = jnp.arange(LC // f, dtype=jnp.int32)[:, None] * f + g // ns
        gstart, gsize, gexists, slot = start(gl), size(gl), gl < n_leaves, g % ns
    pos = gstart + slot                            # key id at (leaf, slot)
    lvalid = gexists & (slot < gsize) & (pos < n)
    pos_safe = jnp.clip(pos, 0, KC)
    leaf_keyid = jnp.where(lvalid, pos, EMPTY)
    leaf_val = jnp.where(lvalid, vals[pos_safe], 0).astype(cfg.val_dtype)
    leaf_tags = jnp.where(lvalid, ktags[pos_safe], 0).astype(jnp.uint8)
    nxt_ok = lexists & (li + 1 < n_leaves)
    leaf_high = jnp.where(nxt_ok, chunk_start(li + 1, base, rem), EMPTY)
    leaf_next = jnp.where(nxt_ok, li + 1, EMPTY)
    # a chunk wider than ns would silently truncate at the lane mask — flag
    # it (host path crashes loudly on the same fill > ns misconfiguration)
    err = (n_leaves > cfg.leaf_cap) | (jnp.where(lexists, lsize, 0) > ns).any()

    # ---- inner levels bottom-up (Python loop over the static height only);
    # grouping a 1-child run reproduces the host chain padding exactly ----
    child_min = jnp.where(lexists, lstart, 0)      # min key id per child
    n_child = n_leaves
    child_cap = LC
    levels_rev: List[Level] = []
    for lvl in range(cfg.n_levels - 1, -1, -1):
        Cn = cfg.level_caps[lvl] + 1
        n_nodes = jnp.maximum(1, -(-n_child // jnp.int32(cfg.inner_fill)))
        nb, nr = n_child // n_nodes, n_child % n_nodes
        ni = jnp.arange(Cn, dtype=jnp.int32)
        nstart = chunk_start(ni, nb, nr)
        nsize = nb + (ni < nr).astype(jnp.int32)
        nexists = ni < n_nodes
        cpos = nstart[:, None] + lane[None, :]     # child id at (node, slot)
        nvalid = (nexists[:, None] & (lane[None, :] < nsize[:, None])
                  & (cpos < n_child))
        cpos_safe = jnp.clip(cpos, 0, child_cap - 1)
        children = jnp.where(nvalid, cpos, EMPTY)
        anchors = jnp.where(nvalid, child_min[cpos_safe], EMPTY)
        knum = jnp.where(nexists, nsize, 0).astype(jnp.int32)
        pl, pf, ft = recompute_inner_meta(kb, kl, anchors, knum, fs)
        levels_rev.append(Level(
            knum=knum,
            plen=jnp.where(nexists, pl, 0).astype(jnp.int32),
            prefix=jnp.where(nexists[:, None], pf, 0).astype(jnp.uint8),
            features=jnp.where(nexists[:, None, None], ft, 0
                               ).astype(jnp.uint8),
            children=children, anchors=anchors,
            count=n_nodes.astype(jnp.int32)))
        err = err | (n_nodes > cfg.level_caps[lvl]) \
            | (jnp.where(nexists, nsize, 0) > ns).any()
        child_min = jnp.where(
            nexists, child_min[jnp.clip(nstart, 0, child_cap - 1)], 0)
        n_child = n_nodes
        child_cap = Cn
    err = err | (n_child != 1)                     # root must be one node
    levels = tuple(levels_rev[::-1])

    arrays = TreeArrays(
        key_bytes=kb, key_lens=kl, key_tags=ktags,
        key_count=n,
        levels=levels,
        stacked=stack_levels(levels),
        leaf_tags_f=leaf_tags, leaf_keyid_f=leaf_keyid, leaf_val_f=leaf_val,
        leaf_occ_f=lvalid,
        leaf_high=leaf_high, leaf_next=leaf_next,
        leaf_version=jnp.zeros((LC,), jnp.int32),
        leaf_ordered=lexists,
        leaf_count=n_leaves.astype(jnp.int32),
    )
    return arrays, err


_device_build_jit = functools.partial(
    jax.jit, static_argnames=("cfg",))(_device_build_from_sorted)


@jax.jit
def sort_pool(kb, kl, ktags, vals, invalid):
    """Sort and compact a pool-shaped key set (DESIGN.md §5, step 1).

    Inputs are ``[key_cap + 1, ...]`` rows; rows flagged by the bool mask
    ``invalid`` are dropped. Returns ``(kb, kl, ktags, vals, n)`` with rows
    ``[0, n)`` holding the valid keys ascending and zeros everywhere else —
    the input contract of :func:`_device_build_from_sorted`.
    ``batch_ops.rebuild`` sorts the tree's key pool through it.
    """
    order = K.lex_sort_indices_j(kb, jnp.where(invalid, 0, kl),
                                 invalid=invalid)   # valid rows first
    n = (~invalid).sum().astype(jnp.int32)
    valid = jnp.arange(kb.shape[0], dtype=jnp.int32) < n
    keep = lambda x: jnp.where(valid.reshape((-1,) + (1,) * (x.ndim - 1)),
                               x[order], 0).astype(x.dtype)
    return keep(kb), keep(kl), keep(ktags), keep(vals), n


@functools.partial(jax.jit, static_argnames=("cfg",))
def _sort_raw(cfg: TreeConfig, qb, ql, vals):
    """Sort a raw batch of ``n`` keys and pad it to pool shape:
    ``(kb, kl, ktags, vals)`` with rows ``[0, n)`` ascending and zeros past
    them (DESIGN.md §5, step 1). The sort runs over the ``n`` keys, not
    over the pool's ``key_cap + 1`` rows."""
    n, R = qb.shape[0], cfg.key_cap + 1
    ql = ql.astype(jnp.int32)
    order = K.lex_sort_indices_j(qb, ql)
    pad = lambda x, dt: jnp.zeros((R,) + x.shape[1:], dt).at[:n].set(
        x.astype(dt)[order])
    return (pad(qb, jnp.uint8), pad(ql, jnp.int32),
            pad(K.fnv1a_tags(qb, ql), jnp.uint8), pad(vals, cfg.val_dtype))


def _launch_device_build(cfg: TreeConfig, ks: K.KeySet, vals):
    """Dispatch the device sort and the jitted build core on the default
    device without waiting for them: ``(arrays, err)``, both unfetched.
    ``repro.shard.sharded_build`` launches every shard's build through it
    before the first host sync."""
    kb, kl, ktags, vv = _sort_raw(cfg, jnp.asarray(ks.bytes),
                                  jnp.asarray(ks.lens), jnp.asarray(vals))
    return _device_build_jit(cfg=cfg, kb=kb, kl=kl, ktags=ktags, vals=vv,
                             n=jnp.int32(ks.n))


def _bulk_build_device(cfg: TreeConfig, ks: K.KeySet, vals) -> FBTree:
    """``bulk_build(device=True)`` body: device sort + jitted build core."""
    arrays, err = _launch_device_build(cfg, ks, vals)
    # _check_capacity already vetted n host-side; err re-validates on device
    if bool(err):  # pragma: no cover - unreachable after _check_capacity
        raise RuntimeError("bulk_build(device=True): capacity exceeded")
    return FBTree(cfg, arrays)


def tree_to_device(tree: FBTree) -> FBTree:
    return jax.tree_util.tree_map(jnp.asarray, tree)


# --------------------------------------------------------------------------
# shard-aware build entry (DESIGN.md §7)
# --------------------------------------------------------------------------

def sharded_partition(ks: K.KeySet, vals, n_shards: int,
                      presorted: bool = False):
    """Range-partition a key set for a sharded build: the §5 pipeline's
    step 1 (the global sort) going distributed, with steps 2–3 unchanged
    per shard.

    One global lexicographic sort (``keys.lex_sort_indices`` — the same
    order every build path uses), then a balanced contiguous split into
    ``n_shards`` runs. ``presorted=True`` skips the sort for inputs already
    in that exact order (e.g. ``repro.shard.rebalance``'s concatenation of
    per-shard sorted snapshots — every skew-recovery barrier would
    otherwise pay a redundant O(n log n) host sort). Returns
    ``(parts, split_keys)``:

    * ``parts[s]``      ``(KeySet, vals)`` — shard ``s``'s sorted slice,
      ready for an independent :func:`bulk_build` (host or device);
    * ``split_keys[s]`` ``(bytes_row uint8[L], len)`` — the run's minimum
      key. The shard router replicates these: shard ``s`` owns
      ``[split_keys[s], split_keys[s+1])`` and shard 0 additionally owns
      everything below ``split_keys[0]``.

    Requires ``n >= n_shards`` (an empty shard has no min key to route
    by); shard sizes differ by at most one.
    """
    n = ks.n
    if n_shards < 1:
        raise ValueError(f"sharded_partition: n_shards must be >= 1, "
                         f"got {n_shards}")
    if n < n_shards:
        raise ValueError(
            f"sharded_partition needs at least one key per shard "
            f"(n={n} < n_shards={n_shards}): an empty shard has no "
            f"minimum key for the router — lower n_shards or seed "
            f"sentinel keys")
    if presorted:
        sb, sl, sv = ks.bytes, ks.lens, np.asarray(vals)
    else:
        order = K.lex_sort_indices(ks)
        sb = ks.bytes[order]
        sl = ks.lens[order]
        sv = np.asarray(vals)[order]
    base, rem = divmod(n, n_shards)
    parts = []
    split_keys = []
    start = 0
    for s in range(n_shards):
        k = base + (1 if s < rem else 0)
        parts.append((K.KeySet(sb[start:start + k].copy(),
                               sl[start:start + k].copy()),
                      sv[start:start + k].copy()))
        split_keys.append((sb[start].copy(), int(sl[start])))
        start += k
    return parts, split_keys
