"""Telemetry subsystem (DESIGN.md §9): zero-overhead-when-off contract,
device-counter export parity, event schema, and the shard
skipped-vs-dropped report split.

The zero-cost contract is structural, not just fast: instrumentation
lives only at host call sites around jitted launches, so the traced
programs — and therefore compiled HLO and op outputs — are bit-identical
with telemetry on or off.
"""
import numpy as np
import pytest

from repro import obs
from repro import shard as SH
from repro.core import batch_ops as B
from repro.core import keys as K
from repro.core.faults import FaultPlan, FaultSpec, RetryPolicy
from repro.core.fbtree import TreeConfig, bulk_build
from repro.core.traverse import TraversalEngine

W = 8
FAST = RetryPolicy(max_attempts=2, sleep=lambda s: None)


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Every test starts and ends with telemetry off and empty."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _keyset(ints):
    return K.make_keyset([int(x).to_bytes(W, "big") for x in ints], W)


def _tree(n=200, seed=7):
    rng = np.random.default_rng(seed)
    base = np.sort(rng.choice(1 << 40, n, replace=False))
    cfg = TreeConfig.plan(max_keys=1024, key_width=W)
    return (bulk_build(cfg, _keyset(base), np.arange(n, dtype=np.int32)),
            base)


# ------------------------------------------------- zero-overhead contract

def test_disabled_is_bit_identical_and_registers_nothing():
    tree, base = _tree()
    q = _keyset([int(x) for x in base[:64]])
    v0, rep0 = B.lookup_batch(tree, q.bytes, q.lens)
    assert obs.all_metrics() == [] and obs.events() == []

    obs.enable()
    v1, rep1 = B.lookup_batch(tree, q.bytes, q.lens)
    assert np.array_equal(np.asarray(v0), np.asarray(v1))
    assert np.array_equal(np.asarray(rep0.found), np.asarray(rep1.found))
    for f in rep0._fields:
        assert np.array_equal(np.asarray(getattr(rep0, f)),
                              np.asarray(getattr(rep1, f))), f
    assert obs.all_metrics(), "enabled run should register metrics"


def test_jitted_program_is_identical_and_callback_free():
    """The traced lookup program must not change with the obs flag, and
    must contain no host callbacks either way — instrumentation never
    enters jit."""
    tree, base = _tree(n=120)
    q = _keyset([int(x) for x in base[:32]])
    import jax.numpy as jnp
    qb, ql = jnp.asarray(q.bytes), jnp.asarray(q.lens)

    def lowered_text():
        return B._lookup_batch_jit.lower(
            tree, qb, ql, sibling_check=True, engine=None).as_text()

    off = lowered_text()
    obs.enable()
    on = lowered_text()
    assert on == off, "obs flag changed the traced program"
    for marker in ("callback", "CustomCall", "outfeed"):
        assert marker not in off, f"host {marker} in jitted lookup"


def test_null_metrics_while_disabled():
    c = obs.counter("x")
    g = obs.gauge("y")
    h = obs.histogram("z")
    c.inc(), g.set(3.0), h.observe(0.5)
    assert obs.all_metrics() == []
    assert obs.get_metric("x") is None
    assert obs.event("rebalance", n_live=1, reclaimed=0) is None
    assert obs.events() == []


# -------------------------------------------------- device-counter export

def test_drained_counters_match_branchstats_totals():
    """The bridge's registry totals equal the per-lane BranchStats sums
    the parity suite asserts on directly — one device_get, no drift."""
    tree, base = _tree()
    q = _keyset([int(x) for x in base[:96]])
    eng = TraversalEngine("jnp", collect_stats=True)
    _, rep = B.lookup_batch(tree, q.bytes, q.lens, engine=eng)

    obs.enable()
    obs.reset()
    _, rep2 = B.lookup_batch(tree, q.bytes, q.lens, engine=eng)
    for f in ("feat_rounds", "suffix_bs", "key_compares", "lines_touched",
              "tag_candidates"):
        want = int(np.asarray(getattr(rep, f)).sum())
        m = obs.get_metric(f"tree.{f}", op="lookup")
        assert m is not None and m.value == want, (f, m and m.value, want)
    m = obs.get_metric("op.found", op="lookup")
    assert m.value == int(np.asarray(rep.found).sum())
    assert obs.get_metric("op.lanes", op="lookup").value == 96


def test_histogram_quantiles_and_prometheus_export():
    obs.enable()
    h = obs.histogram("lat", op="x")
    for v in (1e-5, 1e-4, 1e-3, 1e-2, 0.1, 1.0):
        h.observe(v)
    assert h.count == 6 and h.p50 <= h.p90 <= h.p99
    # geometric-midpoint estimate lands within its log2 bucket (factor 2)
    assert 0.5e-3 <= h.p50 <= 2e-3
    assert 0.5 <= h.p99 <= 2.0
    text = obs.prometheus_text()
    assert '# TYPE lat histogram' in text
    assert 'lat_count{op="x"} 6' in text
    assert 'lat_bucket{op="x",le="+Inf"} 6' in text
    obs.counter("hits", op="x").inc(3)
    assert 'hits{op="x"} 3' in obs.prometheus_text()


def test_spans_nest_and_record_duration():
    obs.enable()
    with obs.span("outer"):
        assert obs.current_path() == "outer"
        with obs.span("inner", shard=1):
            assert obs.current_path() == "outer.inner"
    assert obs.current_path() == ""
    m = obs.get_metric("span.outer.inner", shard=1)
    assert m is not None and m.count == 1 and m.sum > 0
    assert obs.get_metric("span.outer").count == 1


# ------------------------------------------------------------ event log

def test_event_schema_enforced_at_emit():
    obs.enable()
    with pytest.raises(ValueError, match="unknown telemetry event type"):
        obs.event("not-a-type", x=1)
    with pytest.raises(ValueError, match="missing required fields"):
        obs.event("publish", label="x")
    e = obs.event("publish", label="x", version=1, ok=True, reason="",
                  duration_s=0.5)
    assert e["seq"] == 0 and obs.validate_event(e) == []
    assert obs.validate_event({"type": "nope"}) != []
    assert obs.validate_event({"type": "fault", "seq": 1, "ts": 2.0}) != []
    assert obs.event_summary() == {"publish": 1}


def test_publish_event_from_compact_barrier(rng):
    """PrefixCache.compact routes through the lifecycle manager, so one
    compact emits one complete publish event labeled 'compact'."""
    from repro.serving import PrefixCache
    obs.enable()
    pc = PrefixCache(n_pages=64, block_tokens=8, max_keys=2048)
    for _ in range(4):
        toks = rng.integers(0, 500, size=24).astype(np.int32)
        hb, _ = pc.match([toks])
        pc.publish(toks, hb[0])
    rep = pc.compact()
    assert rep.ok
    pubs = [e for e in obs.events() if e["type"] == "publish"]
    assert len(pubs) == 1
    e = pubs[0]
    assert e["label"] == "compact" and e["ok"] and e["version"] == 1
    assert e["duration_s"] > 0 and obs.validate_event(e) == []


def test_fault_events_carry_replay_context():
    obs.enable()
    tree, base = _tree(n=80)
    plan = FaultPlan((FaultSpec("lifecycle.begin", "abort"),), seed=99)
    from repro.core.lifecycle import TreeVersionManager
    mgr = TreeVersionManager(tree, faults=plan)
    rep = mgr.rebuild()
    assert not rep.ok
    faults = [e for e in obs.events() if e["type"] == "fault"]
    assert faults and faults[0]["seed"] == 99
    assert faults[0]["site"] == "lifecycle.begin"
    pubs = [e for e in obs.events() if e["type"] == "publish"]
    assert pubs and not pubs[0]["ok"]
    assert pubs[0]["reason"].startswith("fault:")


# ------------------------------------- shard report: skipped vs dropped

def _sharded(n=120, n_shards=3, seed=5):
    rng = np.random.default_rng(seed)
    base = np.sort(rng.choice(1 << 40, n, replace=False))
    st = SH.sharded_build(_keyset(base), np.arange(n, dtype=np.int32),
                          n_shards, max_keys=1024)
    return st, base


def test_report_separates_healthy_skip_from_drop():
    """A shard that owns no lanes is 'skipped' (healthy); a shard that
    owned lanes but was unreachable is 'dropped'. The two must never be
    conflated — recovery heuristics and counters key off the split."""
    st, base = _sharded()
    # shard 0's keys only: shards 1-2 own no lanes -> healthy skips
    q = _keyset([int(x) for x in base[:32]])
    _, rep = SH.lookup_batch(st, q.bytes, q.lens)
    assert rep.shards_hit == 1
    assert rep.shards_skipped == 2
    assert rep.shards_dropped == ()
    assert not rep.degraded.any() and not rep.failed.any()

    # same query under a sticky drop of shard 0: now it is dropped, and
    # the other two are still just skipped
    st2, _ = _sharded()
    plan = FaultPlan((FaultSpec("shard.dispatch.lookup", "drop_shard",
                                shard=0),))
    _, rep2 = SH.lookup_batch(st2, q.bytes, q.lens, faults=plan,
                              retry=FAST)
    assert rep2.shards_hit == 0
    assert rep2.shards_skipped == 2
    assert rep2.shards_dropped == (0,)
    assert rep2.degraded.all()      # lookups degrade to the snapshot


def test_mutation_report_skipped_vs_dropped_and_counters():
    st, base = _sharded()
    obs.enable()
    q = _keyset([int(x) for x in base[:32]])     # shard 0 only
    vals = np.arange(32, dtype=np.int32)
    plan = FaultPlan((FaultSpec("shard.dispatch.update", "drop_shard",
                                shard=0),))
    _, rep = SH.update_batch(st, q.bytes, q.lens, vals, faults=plan,
                             retry=FAST)
    assert rep.shards_hit == 0
    assert rep.shards_skipped == 2
    assert rep.shards_dropped == (0,)
    assert rep.failed.all()
    assert obs.get_metric("shard.failed_lanes", op="update").value == 32
    assert obs.get_metric("shard.retries", op="update").value > 0
    evs = obs.event_summary()
    assert evs.get("shard.failed") == 1
    assert evs.get("shard.down") == 1
    # healthy skips registered no degradation signal anywhere
    assert obs.get_metric("shard.degraded_lanes", op="update") is None


def test_shard_retry_and_degraded_events():
    st, base = _sharded()
    obs.enable()
    q = _keyset([int(x) for x in base[:32]])
    # one transient drop: absorbed by retry, no degradation
    plan = FaultPlan((FaultSpec("shard.dispatch.lookup", "drop_shard",
                                shard=0, nth=0, count=1),))
    _, rep = SH.lookup_batch(st, q.bytes, q.lens, faults=plan, retry=FAST)
    assert rep.shards_hit == 1 and rep.shards_dropped == ()
    assert not rep.degraded.any()
    retries = [e for e in obs.events() if e["type"] == "shard.retry"]
    assert len(retries) == 1 and retries[0]["shard"] == 0
    assert obs.get_metric("shard.retries", op="lookup").value == 1
    assert obs.event_summary().get("shard.degraded") is None


# ------------------------------------------ shard dispatch counters

def _spread_batch(base):
    """25 lanes over four shards of 30 keys: 10 to shard 0, 5 to shard 1,
    none to shard 2, 10 to shard 3."""
    picks = np.concatenate([base[0:10], base[30:35], base[90:100]])
    return _keyset([int(x) for x in picks]), {0: 10, 1: 5, 2: 0, 3: 10}


def test_shard_dispatch_counters_while_enabled():
    """``shard.lanes`` counts what the router gave each shard and sums to
    the batch per op; every launch computes the full batch, so
    ``shard.launch_lanes`` is the batch times the shards launched."""
    st, base = _sharded(n=120, n_shards=4)
    q, per_shard = _spread_batch(base)
    obs.enable()
    SH.lookup_batch(st, q.bytes, q.lens)
    SH.update_batch(st, q.bytes, q.lens, np.arange(25, dtype=np.int32))
    for op in ("lookup", "update"):
        lanes = {s: obs.get_metric("shard.lanes", op=op, shard=s).value
                 for s in range(4)}
        assert lanes == per_shard, op
        assert sum(lanes.values()) == q.n
        assert obs.get_metric("shard.launches", op=op).value == 3
        assert obs.get_metric("shard.launch_lanes", op=op).value == 3 * q.n


def test_shard_dispatch_counters_off_record_nothing():
    st, base = _sharded(n=120, n_shards=4)
    q, _ = _spread_batch(base)
    v_off, rep_off = SH.lookup_batch(st, q.bytes, q.lens)
    assert obs.all_metrics() == []
    obs.enable()
    v_on, rep_on = SH.lookup_batch(st, q.bytes, q.lens)
    assert np.array_equal(v_off, v_on)
    assert np.array_equal(rep_off.found, rep_on.found)
    assert rep_off.found.all()


def test_shard_lanes_count_device_batches_like_host_ones():
    """A device batch is routed on the host too, from its fetched copy:
    ``shard.lanes`` counts what the router gave each shard exactly as for
    the same keys sent from the host, per op. Nothing while telemetry is
    off."""
    import jax.numpy as jnp
    st, base = _sharded(n=120, n_shards=4)
    q, per_shard = _spread_batch(base)
    dq = (jnp.asarray(q.bytes), jnp.asarray(q.lens))
    vals = np.arange(25, dtype=np.int32)

    def ops():
        SH.lookup_batch(st, q.bytes, q.lens)
        SH.lookup_batch(st, *dq)
        SH.update_batch(st, *dq, vals)
    ops()
    assert obs.all_metrics() == []
    obs.enable()
    ops()
    for op, n in (("lookup", 2), ("update", 1)):
        lanes = {s: obs.get_metric("shard.lanes", op=op, shard=s).value
                 for s in range(4)}
        assert lanes == {s: n * k for s, k in per_shard.items()}, op


# ------------------------------------- spans and scopes, telemetry off

class _RecordedAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; records names."""
    names = []

    def __init__(self, name, **kw):
        self.names.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("on", [False, True], ids=["off", "on"])
def test_span_annotates_its_own_name_either_way(monkeypatch, on):
    """A span always enters a profiler annotation named exactly its name
    (no labels, no dotted parent path); only with telemetry enabled does
    it also record the ``span.<path>`` histogram."""
    from repro.obs import trace as obs_trace
    monkeypatch.setattr(obs_trace._profiler, "TraceAnnotation",
                        _RecordedAnnotation)
    monkeypatch.setattr(_RecordedAnnotation, "names", [])
    if on:
        obs.enable()
    with obs.span("shard.dispatch", op="lookup", shard=1):
        with obs.span("op.lookup"):
            pass
    assert _RecordedAnnotation.names == ["shard.dispatch", "op.lookup"]
    m = obs.get_metric("span.shard.dispatch.op.lookup")
    if on:
        assert m is not None and m.count == 1
    else:
        assert m is None and obs.all_metrics() == []


def _no_sync(*a, **k):
    raise AssertionError("host sync with telemetry off")


def test_ops_add_no_host_sync_with_telemetry_off(monkeypatch):
    """With telemetry off no op drains its report: the bridge's
    ``device_get`` and ``jax.device_get`` are never called, though every
    op still runs under its span."""
    import jax
    from repro.obs import bridge
    tree, base = _tree()
    st, sbase = _sharded()
    q = _keyset([int(x) for x in base[:32]])
    sq = _keyset([int(x) for x in sbase[:32]])
    vals = np.arange(32, dtype=np.int32)
    monkeypatch.setattr(bridge, "_host", _no_sync)
    monkeypatch.setattr(jax, "device_get", _no_sync)
    B.lookup_batch(tree, q.bytes, q.lens)
    t2, _ = B.update_batch(tree, q.bytes, q.lens, vals)
    B.remove_batch(t2, q.bytes, q.lens)
    B.range_scan(tree, q.bytes, q.lens, max_items=4)
    B.rebuild(tree)
    SH.lookup_batch(st, sq.bytes, sq.lens)
    SH.update_batch(st, sq.bytes, sq.lens, vals)
    SH.range_scan(st, sq.bytes, sq.lens, max_items=4)
    obs.enable()            # the same op drains once telemetry is on
    with pytest.raises(AssertionError, match="host sync"):
        B.lookup_batch(tree, q.bytes, q.lens)


def test_programs_carry_named_scopes_and_match_across_flag():
    """The lookup and update programs carry the ``descent`` and
    ``leaf_probe`` scopes in their ``op_name`` metadata (per inner level
    ``level_<l>``), and their lowered text, debug locations included, does
    not change with the obs flag."""
    import re

    import jax.numpy as jnp
    tree, base = _tree(n=120)
    q = _keyset([int(x) for x in base[:32]])
    qb, ql = jnp.asarray(q.bytes), jnp.asarray(q.lens)
    vals = jnp.arange(32, dtype=jnp.int32)
    eng = TraversalEngine("jnp", collect_stats=False)
    progs = {"_lookup_batch_jit": lambda: B._lookup_batch_jit.lower(
                 tree, qb, ql, sibling_check=True, engine=eng),
             "_update_batch_jit": lambda: B._update_batch_jit.lower(
                 tree, qb, ql, vals, engine=eng)}
    for name, lower in progs.items():
        off = lower().as_text(debug_info=True)
        obs.enable()
        on = lower().as_text(debug_info=True)
        obs.disable()
        assert on == off, f"obs flag changed {name}"
        scopes = set(re.findall(r'op_name="jit\(' + name + r'\)/([^"]*)"',
                                lower().compile().as_text()))
        assert any(s.startswith("descent/") for s in scopes), name
        assert any(s.startswith("leaf_probe/") for s in scopes), name
        assert any(s.startswith("descent/level_0/") for s in scopes), name
