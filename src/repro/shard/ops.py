"""Routed batch ops over a ShardedTree (DESIGN.md §7).

Dispatch model: the router buckets the query batch by owning shard on the
host's CPU device, where the router lives, over the host copy of the keys
(no accelerator involved; a device batch is fetched first). A host loop
then launches ONE jitted shard-local op per shard that owns work — the
same ``core.batch_ops`` entry points every unsharded call site uses,
through the same ``TraversalEngine`` (any level backend). Launches are
asynchronous per device, so with a multi-device mesh the shards
genuinely overlap. A lookup's results are copied device to device to
shard 0's device and selected by owner there, in one small jitted
program, and returned as device arrays whose host copies are already
under way: for a host batch
the call never waits on a device, so the caller's next work overlaps the
shard lookups. Mutations and scans combine their reports on the host.

Shapes stay static by running each shard over the *full* batch with the
routed-op ``mask`` hook (``core.batch_ops``): masked-out lanes read
harmlessly and never write, so a shard-local op on a full batch commits
exactly its owned lanes. Shards owning no lanes are skipped outright.

Cross-shard ``range_scan``: each query starts in its owner shard; lanes
that exhaust the owner's leaf chain before ``max_items`` spill to the next
shard (range partition ⇒ the next shard's first key is the chain's
successor) and the per-shard emissions — each ascending, each riding the
§6 lazy-rearrangement fast path — concatenate in shard order into the
globally ascending result. Filled lanes are parked on an all-0xFF start
key so later shards do one trivial descent for them, and the host loop
stops as soon as no lane is active.

Fault tolerance (DESIGN.md §8): every launch goes through
:func:`_dispatch`, which retries injected :class:`ShardDropped` faults
with capped exponential backoff and marks a shard unhealthy
(``ShardedTree.health``) when retries are exhausted. Unhealthy shards
degrade instead of erroring: lookups serve their lanes from the
last-barrier ``snapshots`` replica (``degraded`` mask — possibly stale),
mutations and scans report those lanes ``failed`` (never silently
dropped or truncated), and :func:`rebalance` is the recovery barrier that
re-admits the shard with fresh health and snapshots.

Telemetry (DESIGN.md §9): the layer's three kinds of host work each run
under a span, none inside another — ``shard.route`` (the route on the
CPU device, after a device batch's fetch; in a scan, also each shard's
parked lanes),
``shard.dispatch`` (one shard's launch, retries included; the shard op's
own ``op.<name>`` span nests in it) and ``shard.combine`` (a lookup: the
results' device-to-device copies, the select's launch and the start of
the host copies, with no wait; mutations and scans: per-shard results
pulled to the host and merged by owner). While ``repro.obs`` is enabled,
counters measure the full-width dispatch: ``shard.lanes{op, shard}``
(lanes the router gave each shard, counted from the owners the route
left on the host), ``shard.launches{op}`` and
``shard.launch_lanes{op}`` (lanes the launched shard programs compute:
the batch width per launch). ``launch_lanes`` over the lanes served is
the masked overcompute.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import batch_ops as B
from repro.core import keys as K
from repro.core.faults import FaultPlan, RetryPolicy, ShardDropped
from repro.core.fbtree import EMPTY
from repro.core.traverse import TraversalEngine

from .build import sharded_build
from .router import route
from .tree import ShardedTree

__all__ = ["ShardOpReport", "RebalanceReport", "lookup_batch",
           "update_batch", "insert_batch", "remove_batch", "range_scan",
           "rebalance", "DEFAULT_RETRY"]

DEFAULT_RETRY = RetryPolicy()


class ShardOpReport(NamedTuple):
    """Cross-shard op outcome. For lookups ``found`` is a device array on
    shard 0's device, like the returned values (``np.asarray`` fetches
    it); every other field, and every field of a mutation's report, is
    exact host numpy.

    A shard ends a routed op in exactly one of three states, and the
    report keeps them apart (healthy skips must never read as
    degradation — the telemetry counters and recovery heuristics key off
    this): **hit** (owned lanes, served normally), **skipped** (owned no
    lanes this batch — healthy, no launch attempted), or **dropped**
    (owned lanes but was unreachable: its lanes appear in ``degraded``
    for lookups or ``failed`` for mutations/scans).
    """
    found: np.ndarray       # bool [B] — owner shard's found (lookups: a
    #                         jax.Array on shard 0's device)
    conflicts: np.ndarray   # int32 — in-batch dedupe losers (global, once)
    splits: np.ndarray      # int32 — leaf splits summed over shards
    error: np.ndarray       # bool — any shard hit a capacity error
    owner: np.ndarray       # int32 [B] — routed shard per query
    shards_hit: int         # shards that owned lanes AND served normally
    failed: np.ndarray = np.zeros(0, bool)    # bool [B] — lane not served
    #                         (owner shard down; mutations: NOT committed)
    degraded: np.ndarray = np.zeros(0, bool)  # bool [B] — lane served from
    #                         the last-barrier snapshot (may be stale)
    shards_skipped: int = 0  # healthy shards that owned no lanes
    shards_dropped: Tuple[int, ...] = ()      # shard ids unreachable this
    #                         op (their lanes are degraded/failed above)


class RebalanceReport(NamedTuple):
    """Outcome of a cross-shard rebalance (a bulk-synchronous barrier)."""
    n_live: int             # keys carried into the new partition
    reclaimed: int          # key-pool rows freed across shards
    counts_before: Tuple[int, ...]   # live keys per shard pre-barrier
    counts_after: Tuple[int, ...]    # live keys per shard post-barrier


def _put(x, dev):
    return x if dev is None else jax.device_put(x, dev)


def _owner_masks(st: ShardedTree, qb, ql, opname: str):
    """Route once (span ``shard.route``); returns the owners as host
    numpy.

    The jitted :func:`route` runs on the host's CPU device, where the
    router lives, over host copies of the keys: a host batch launches
    nothing on an accelerator and waits for none (a device batch's fetch
    is the one sync). Counts ``shard.lanes`` per shard while telemetry is
    on."""
    with obs.span("shard.route"):
        hb, hl = np.asarray(qb), np.asarray(ql)
        if hb.ndim != 2 or hb.shape[-1] != st.config.key_width:
            got = "x".join(map(str, hb.shape))
            raise ValueError(
                f"query batch shape [{got}] does not match the tree's key "
                f"width {st.config.key_width}: routing compares packed "
                f"words, so keys must be zero-padded to exactly key_width "
                f"bytes — build them with repro.core.keys.make_keyset("
                f"keys, max_key_len={st.config.key_width})")
        owner = np.asarray(route(st.router, hb, hl))
    if obs.enabled():
        per_shard = np.bincount(owner, minlength=st.n_shards)
        for s, n in enumerate(per_shard):
            obs.counter("shard.lanes", op=opname, shard=s).inc(int(n))
    return owner


def _dispatch(st: ShardedTree, s: int, opname: str, call,
              faults: Optional[FaultPlan], retry: Optional[RetryPolicy],
              lanes: int):
    """Launch one shard-local op of ``lanes`` lanes through the fault
    layer.

    Returns the op result, or None when the shard cannot be reached: the
    site ``shard.dispatch.<opname>`` fires per attempt; ShardDropped is
    retried with capped exponential backoff (transient flakes are
    absorbed); exhausting retries marks the shard down in
    ``st.health`` so later ops skip the launch outright. Any other
    exception (capacity overflow etc.) propagates unchanged — faults
    model reachability, not data errors.
    """
    if st.health is not None and not st.health.is_ok(s):
        obs.counter("shard.skipped_down", op=opname).inc()
        return None
    pol = retry if retry is not None else DEFAULT_RETRY
    delays = list(pol.delays()) + [None]        # None = no sleep after last
    with obs.span("shard.dispatch", op=opname, shard=s):
        for attempt, delay in enumerate(delays):
            try:
                if faults is not None:
                    faults.fire(f"shard.dispatch.{opname}", shard=s,
                                attempt=attempt)
                res = call()
                obs.counter("shard.launches", op=opname).inc()
                obs.counter("shard.launch_lanes", op=opname).inc(lanes)
                return res
            except ShardDropped:
                obs.counter("shard.retries", op=opname).inc()
                obs.event("shard.retry", op=opname, shard=s,
                          attempt=attempt)
                if delay is not None:
                    pol.sleep(delay)
    if st.health is not None:
        st.health.mark_down(
            s, f"{opname}: unreachable after {len(delays)} attempts")
    obs.counter("shard.down", op=opname).inc()
    obs.event("shard.down", op=opname, shard=s, attempts=len(delays))
    return None


@jax.jit
def _select_by_owner(slot, vals, founds):
    """Lane ``i`` takes ``vals[slot[i]]`` / ``founds[slot[i]]``: the
    combine of a routed lookup, one small program on shard 0's
    device."""
    v, f = vals[0], founds[0]
    for k in range(1, len(vals)):
        v = jnp.where(slot == k, vals[k], v)
        f = jnp.where(slot == k, founds[k], f)
    return v, f


def lookup_batch(st: ShardedTree, qb, ql,
                 engine: Optional[TraversalEngine] = None,
                 faults: Optional[FaultPlan] = None,
                 retry: Optional[RetryPolicy] = None):
    """Batched point lookup across shards. Returns ``(vals [B], report)``;
    ``vals`` and ``report.found`` are device arrays on shard 0's device,
    their host copies already started, and bit-identical to
    ``core.batch_ops.lookup_batch`` on one unsharded tree over the same
    keys. For a host batch the call never waits on a device.

    Degradation: lanes owned by an unreachable shard are served from that
    shard's last-barrier snapshot (``report.degraded`` — correct as of the
    barrier, possibly stale) rather than failed; reads prefer staleness
    over unavailability. ``report.failed`` stays all-False for lookups.
    """
    owner = _owner_masks(st, qb, ql, "lookup")
    Bn = owner.shape[0]
    degraded = np.zeros((Bn,), dtype=bool)
    slot = np.zeros((st.n_shards,), dtype=np.int32)  # shard -> its result
    results = []
    hit = 0
    skipped = 0
    dropped = []
    for s, t in enumerate(st.shards):
        sel = owner == s
        if not sel.any():
            skipped += 1                        # healthy skip, not a drop
            continue
        dev = st.devices[s]
        res = _dispatch(
            st, s, "lookup",
            lambda: B.lookup_batch(t, _put(qb, dev), _put(ql, dev),
                                   engine=engine),
            faults, retry, Bn)
        if res is None:
            # degrade: the snapshot replica is reachable by construction
            # (it lives with the router, not behind the downed dispatch)
            snap = st.snapshots[s]
            v, rep = B.lookup_batch(snap, qb, ql, engine=engine)
            degraded |= sel
            dropped.append(s)
            obs.counter("shard.degraded_lanes", op="lookup").inc(
                int(sel.sum()))
            obs.event("shard.degraded", op="lookup", shard=s,
                      lanes=int(sel.sum()))
        else:
            v, rep = res
            hit += 1
        slot[s] = len(results)
        results.append((v, rep.found))         # async: combine later
    with obs.span("shard.combine"):
        home = st.devices[0]                    # None: the default device
        if results:
            vals, found = _select_by_owner(
                _put(slot[owner], home),
                tuple(_put(v, home) for v, _ in results),
                tuple(_put(f, home) for _, f in results))
        else:                                   # an empty batch
            vals = jnp.zeros((0,), jax.dtypes.canonicalize_dtype(
                st.config.val_dtype), device=home)
            found = jnp.zeros((0,), bool, device=home)
        vals.copy_to_host_async()
        found.copy_to_host_async()
    rep = ShardOpReport(found=found, conflicts=np.int32(0),
                        splits=np.int32(0), error=np.bool_(False),
                        owner=owner, shards_hit=hit,
                        failed=np.zeros((Bn,), bool), degraded=degraded,
                        shards_skipped=skipped,
                        shards_dropped=tuple(dropped))
    return vals, rep


def _routed_mutation(st: ShardedTree, owner, opname, run_one, faults,
                     retry):
    """Shared mutation loop: run ``run_one(shard_tree, mask, dev)`` on every
    reachable shard owning lanes; returns (new shards, outcomes, failed,
    skipped, dropped).

    Lanes of an unreachable shard are reported ``failed`` — the shard tree
    is left untouched (the mutation is NOT committed there), so a caller
    can re-apply exactly the failed lanes after recovery.
    """
    shards = list(st.shards)
    outcomes = []
    failed = np.zeros(owner.shape, dtype=bool)
    skipped = 0
    dropped = []
    for s, t in enumerate(st.shards):
        sel = owner == s
        if not sel.any():
            skipped += 1                        # healthy skip, not a drop
            continue
        dev = st.devices[s]

        def call(t=t, sel=sel, dev=dev):
            mask = _put(jnp.asarray(sel), dev)
            return run_one(t, mask, dev)
        res = _dispatch(st, s, opname, call, faults, retry, owner.shape[0])
        if res is None:
            failed |= sel
            dropped.append(s)
            obs.counter("shard.failed_lanes", op=opname).inc(int(sel.sum()))
            obs.event("shard.failed", op=opname, shard=s,
                      lanes=int(sel.sum()))
            continue
        t2, out = res
        shards[s] = t2
        outcomes.append((sel, out))
    return tuple(shards), outcomes, failed, skipped, tuple(dropped)


def update_batch(st: ShardedTree, qb, ql, vals,
                 engine: Optional[TraversalEngine] = None,
                 faults: Optional[FaultPlan] = None,
                 retry: Optional[RetryPolicy] = None):
    """Routed blind update. Returns ``(ShardedTree', report)``."""
    owner = _owner_masks(st, qb, ql, "update")
    vals = jnp.asarray(vals)

    def run_one(t, mask, dev):
        t2, rep = B.update_batch(t, _put(qb, dev), _put(ql, dev),
                                 _put(vals, dev), engine=engine, mask=mask)
        return t2, rep
    shards, outcomes, failed, skipped, dropped = _routed_mutation(
        st, owner, "update", run_one, faults, retry)
    return (st.replace(shards=shards),
            _combine(outcomes, owner, failed, skipped, dropped))


def remove_batch(st: ShardedTree, qb, ql,
                 engine: Optional[TraversalEngine] = None,
                 faults: Optional[FaultPlan] = None,
                 retry: Optional[RetryPolicy] = None):
    """Routed tombstone removal. Returns ``(ShardedTree', report)``."""
    owner = _owner_masks(st, qb, ql, "remove")

    def run_one(t, mask, dev):
        t2, rep = B.remove_batch(t, _put(qb, dev), _put(ql, dev),
                                 engine=engine, mask=mask)
        return t2, rep
    shards, outcomes, failed, skipped, dropped = _routed_mutation(
        st, owner, "remove", run_one, faults, retry)
    return (st.replace(shards=shards),
            _combine(outcomes, owner, failed, skipped, dropped))


def insert_batch(st: ShardedTree, qb, ql, vals,
                 engine: Optional[TraversalEngine] = None,
                 faults: Optional[FaultPlan] = None,
                 retry: Optional[RetryPolicy] = None, **kw):
    """Routed upsert. Returns ``(ShardedTree', report, rounds)`` —
    ``rounds`` is the max split rounds any shard needed. New keys land in
    their owner shard only (range partition preserved); a per-shard
    capacity overflow raises exactly as the unsharded op does —
    ``rebalance`` is the recovery for skew-driven overflow."""
    owner = _owner_masks(st, qb, ql, "insert")
    vals = jnp.asarray(vals)
    rounds_max = 0

    def run_one(t, mask, dev):
        nonlocal rounds_max
        t2, rep, rounds = B.insert_batch(t, _put(qb, dev), _put(ql, dev),
                                         _put(vals, dev), engine=engine,
                                         mask=mask, **kw)
        rounds_max = max(rounds_max, rounds)
        return t2, rep
    shards, outcomes, failed, skipped, dropped = _routed_mutation(
        st, owner, "insert", run_one, faults, retry)
    return (st.replace(shards=shards),
            _combine(outcomes, owner, failed, skipped, dropped),
            rounds_max)


def _combine(outcomes, owner, failed=None, skipped=0,
             dropped=()) -> ShardOpReport:
    found = np.zeros(owner.shape, dtype=bool)
    splits = 0
    error = False
    conflicts = 0
    with obs.span("shard.combine"):     # each shard's outcome to the host
        for i, (sel, rep) in enumerate(outcomes):
            found[sel] = np.asarray(rep.found)[sel]
            splits += int(rep.splits)
            error = error or bool(rep.error)
            if i == 0:
                # per-shard ops dedupe the FULL batch before the mask ANDs
                # in, so any one report already carries the global
                # conflict count
                conflicts = int(rep.conflicts)
    if failed is None:
        failed = np.zeros(owner.shape, dtype=bool)
    return ShardOpReport(found=found, conflicts=np.int32(conflicts),
                         splits=np.int32(splits), error=np.bool_(error),
                         owner=owner, shards_hit=len(outcomes),
                         failed=failed,
                         degraded=np.zeros(owner.shape, dtype=bool),
                         shards_skipped=skipped,
                         shards_dropped=tuple(dropped))


# --------------------------------------------------------------------------
# cross-shard range scan
# --------------------------------------------------------------------------

def range_scan(st: ShardedTree, qb, ql, max_items: int = 64,
               engine: Optional[TraversalEngine] = None,
               faults: Optional[FaultPlan] = None,
               retry: Optional[RetryPolicy] = None):
    """Cross-shard range scan with spill-to-next-shard continuation.

    Returns ``(gkid int64 [B, max_items], val [B, max_items], emitted [B],
    rearranged [B], failed bool [B])`` — ascending per lane, starting at
    the first key >= the query; ``gkid`` is the global key id
    (``ShardedTree.key_rows`` resolves it), EMPTY past ``emitted``.
    Values, emitted counts, and the resolved key bytes are bit-identical
    to the unsharded §6 scan; ``rearranged`` sums the dirty leaves visited
    across shards (leaf chunking differs per partition, so it is *not*
    parity-comparable).

    Each per-shard scan goes through the §6 jnp chain walk and keeps its
    lazy-rearrangement ordering guarantee; the merge is pure concatenation
    because the partition is by key range.

    Degradation: a lane whose next needed shard is unreachable is marked
    ``failed`` and stops there — its emissions so far are a correct
    ascending *prefix* of the full result, and the flag says it may be
    truncated. A result is never silently shortened: ``failed[i] is
    False`` guarantees lane ``i`` is complete. Failed lanes take no items
    from later shards (a contiguity gap would corrupt the ascending
    merge); snapshots are not substituted here for the same reason.
    """
    if max_items < 1:
        raise ValueError(
            f"range_scan: max_items must be >= 1, got {max_items} — each "
            f"lane emits up to max_items (key, value) pairs")
    owner = _owner_masks(st, qb, ql, "range_scan")
    Bn = owner.shape[0]
    L = st.config.key_width
    stride = st.kid_stride
    vdt = jax.dtypes.canonicalize_dtype(st.config.val_dtype)
    out_kid = np.full((Bn, max_items), EMPTY, dtype=np.int64)
    out_val = np.zeros((Bn, max_items), dtype=vdt)
    emitted = np.zeros((Bn,), dtype=np.int32)
    rearranged = np.zeros((Bn,), dtype=np.int32)
    failed = np.zeros((Bn,), dtype=bool)
    park_b = np.full((L,), 0xFF, dtype=np.uint8)   # parked lanes descend to
    park_l = np.int32(L)                           # the last leaf, emit ~0
    qb_np = np.asarray(qb)
    ql_np = np.asarray(ql)
    cols = np.arange(max_items, dtype=np.int32)[None, :]
    rows = np.broadcast_to(np.arange(Bn, dtype=np.int32)[:, None],
                           (Bn, max_items))

    for s, t in enumerate(st.shards):
        active = (owner <= s) & (emitted < max_items) & ~failed
        if not active.any():
            # stop only when NO lane can still gain: lanes owned by later
            # shards haven't started yet (owners are clustered, e.g. {0, 3})
            if not (owner > s).any():
                break
            continue
        with obs.span("shard.route"):       # this shard's lanes, parked
            sqb = np.where(active[:, None], qb_np, park_b[None, :])
            sql = np.where(active, ql_np, park_l).astype(np.int32)
        dev = st.devices[s]
        res = _dispatch(
            st, s, "range_scan",
            lambda: B.range_scan(t, _put(jnp.asarray(sqb), dev),
                                 _put(jnp.asarray(sql), dev),
                                 max_items=max_items, engine=engine),
            faults, retry, Bn)
        if res is None:
            failed |= active      # partial prefix, flagged — never silent
            obs.counter("shard.failed_lanes", op="range_scan").inc(
                int(active.sum()))
            obs.event("shard.failed", op="range_scan", shard=s,
                      lanes=int(active.sum()))
            continue
        with obs.span("shard.combine"):
            kid_s, val_s, em_s, re_s = (np.asarray(x) for x in res)
            take = np.where(active,
                            np.minimum(em_s, max_items - emitted), 0)
            ok = cols < take[:, None]      # emitted slots only: kid_s >= 0
            dst = emitted[:, None] + cols
            out_kid[rows[ok], dst[ok]] = (kid_s[ok].astype(np.int64)
                                          + s * stride)
            out_val[rows[ok], dst[ok]] = val_s[ok]
            emitted += take.astype(np.int32)
            rearranged += np.where(active, re_s, 0).astype(np.int32)
    return out_kid, out_val, emitted, rearranged, failed


# --------------------------------------------------------------------------
# rebalance — the skew-recovery barrier
# --------------------------------------------------------------------------

def rebalance(st: ShardedTree, device: bool = True,
              faults: Optional[FaultPlan] = None
              ) -> Tuple[ShardedTree, RebalanceReport]:
    """Re-partition the live key set evenly across shards.

    Built on the rebuild primitive (DESIGN.md §5/§7):
    ``core.batch_ops.gather_live_sorted`` snapshots each shard — sorted,
    tombstones dropped, pool compacted, the exact front half of
    ``rebuild`` — and because shards are range-partitioned, concatenating
    the snapshots in shard order IS the globally sorted live set. That set
    re-enters :func:`repro.shard.build.sharded_build` with the *same*
    shared ``TreeConfig`` (no recompiles) and the same mesh placement:
    step 1's sort re-distributed, steps 2–3 (the §5 device build) per
    shard, and a fresh router from the new balanced boundaries.

    Same barrier semantics as ``rebuild``: key ids (global ones included)
    are not stable across it, versions reset, values carry over. With
    ``n_shards == 1`` this degenerates to exactly ``rebuild``.

    This is also the **recovery barrier** (DESIGN.md §8): the snapshots
    are gathered from the authoritative per-shard arrays — which survive a
    dispatch outage intact — so every committed op is carried over, and
    the fresh ShardedTree starts with all-healthy ``health`` and new
    barrier ``snapshots``, re-admitting any shard that was marked down.
    Run it inside ``core.lifecycle.TreeVersionManager.publish`` (or use
    ``manager.rebalance()``) to make it abortable: a fault below — the
    sites ``lifecycle.rebalance.gather``/``.build`` fire per step — then
    leaves the old partition serving.
    """
    counts_before = tuple(int(t.n_keys_live) for t in st.shards)
    with obs.span("shard.rebalance", n_shards=st.n_shards):
        kbs, kls, vvs = [], [], []
        reclaimed = 0
        for s, t in enumerate(st.shards):
            if faults is not None:
                faults.fire("lifecycle.rebalance.gather", shard=s)
            kb, kl, _, vv, n_live = B.gather_live_sorted(t)
            n = int(n_live)
            reclaimed += int(t.arrays.key_count) - n
            kbs.append(np.asarray(kb)[:n])
            kls.append(np.asarray(kl)[:n])
            vvs.append(np.asarray(vv)[:n])
        ks = K.KeySet(np.concatenate(kbs, axis=0),
                      np.concatenate(kls, axis=0))
        vals = np.concatenate(vvs, axis=0)
        if faults is not None:
            faults.fire("lifecycle.rebalance.build")
        # the concatenation is already globally sorted (invariant above) —
        # presorted skips re-running step 1's lexsort at every barrier
        st2 = sharded_build(ks, vals, st.n_shards, cfg=st.config,
                            device=device, mesh=st.mesh, presorted=True)
    rep = RebalanceReport(
        n_live=ks.n, reclaimed=reclaimed, counts_before=counts_before,
        counts_after=tuple(int(t.n_keys_live) for t in st2.shards))
    obs.event("rebalance", n_live=rep.n_live, reclaimed=rep.reclaimed)
    return st2, rep
