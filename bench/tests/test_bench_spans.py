"""The index's own spans in a profiler trace, with telemetry off: the
``op.*`` spans of the batch ops and the ``shard.*`` spans of the shard
dispatch layer reach the trace that ``bench.trace.load_xspace_dir``
reads, and the reduction puts idle gaps down to them."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000  # ns


def _no_sync(*a, **k):
    raise AssertionError("host sync with telemetry off")


def test_op_spans_reach_the_trace_with_telemetry_off(monkeypatch, tmp_path):
    import jax
    import jax.profiler

    from repro import obs
    from repro.core import batch_ops as B
    from repro.core import keys as K
    from repro.core.fbtree import TreeConfig, bulk_build
    from repro.obs import bridge
    obs.disable()
    keys = [int(x).to_bytes(8, "big") for x in range(1000, 1200)]
    ks = K.make_keyset(keys, 8)
    tree = bulk_build(TreeConfig.plan(max_keys=1024, key_width=8), ks,
                      np.arange(200, dtype=np.int32))
    qb, ql = ks.bytes[:32], ks.lens[:32]
    vals = np.arange(32, dtype=np.int32)
    B.update_batch(tree, qb, ql, vals)            # compile outside the trace
    monkeypatch.setattr(bridge, "_host", _no_sync)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("client.window"):
            v, rep = B.lookup_batch(tree, qb, ql)
            tree2, _ = B.update_batch(tree, qb, ql, vals)
            np.asarray(v), np.asarray(tree2.arrays.leaf_val)
    finally:
        jax.profiler.stop_trace()
    names = [e[0] for e in trace.load_xspace_dir(str(tmp_path))["host"]]
    assert names.count("op.lookup") == 1 and names.count("op.update") == 1
    assert "client.window" in names
    assert obs.all_metrics() == []


def test_idle_gap_inside_an_index_span_is_named_by_it():
    """The device idles while the host is inside ``op.lookup`` (nested in
    the harness's ``client.call.read``): the gap goes to the index span,
    the innermost one open at the gap's middle."""
    ev = {"devices": {"/device:TPU:0": {
              "ops": [["fusion.1", 0, 2 * MS], ["fusion.2", 5 * MS, 5 * MS]],
              "modules": [["jit__lookup_batch_jit(1)", 5 * MS, 5 * MS]]}},
          "host": [["client.window", 0, 10 * MS],
                   ["client.call.read", 2 * MS, 3 * MS],
                   ["op.lookup", 2 * MS + 1000, 2 * MS]]}
    gaps = dict(trace.reduce(ev, n_devices=1)["idle_gaps"])
    assert gaps == pytest.approx({"op.lookup": 0.003})


FOUR = r"""
import json, shutil, sys, tempfile
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax, jax.profiler
import numpy as np
from bench import trace
from repro import shard as SH
from repro.core import keys as K
keys = [int(x).to_bytes(8, "big") for x in range(5000, 5400)]
ks = K.make_keyset(keys, 8)
st = SH.sharded_build(ks, np.arange(400, dtype=np.int32), 4, max_keys=1024)
qb, ql = ks.bytes[::5][:64], ks.lens[::5][:64]
vals = np.arange(64, dtype=np.int32)
def ops(st):
    v, rep = SH.lookup_batch(st, qb, ql)
    st, urep = SH.update_batch(st, qb, ql, vals)
    SH.range_scan(st, qb, ql, max_items=4)
    return rep
ops(st)                                             # compile
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
rep = ops(st)
jax.profiler.stop_trace()
host = trace.load_xspace_dir(d)["host"]
shutil.rmtree(d)
print(json.dumps({"devices": len(st.devices), "hit": rep.shards_hit,
                  "host": host}))
"""


def test_shard_spans_on_four_cpu_devices():
    """A routed lookup, update and scan over four shards on four forced CPU
    devices: ``shard.route``, ``shard.dispatch`` and ``shard.combine`` in
    the trace, none inside another, each shard op's ``op.*`` span inside a
    ``shard.dispatch``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR, ROOT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4 and res["hit"] == 4
    by = {}
    for name, s, d in res["host"]:
        by.setdefault(name, []).append((s, s + d))
    # three routes (lookup, update, scan) and a combine per routed op, a
    # dispatch per shard that owns lanes
    assert len(by["shard.route"]) >= 3
    assert len(by["shard.combine"]) >= 3
    assert len(by["shard.dispatch"]) >= 4 * 2
    assert len(by["op.lookup"]) == 4 and len(by["op.update"]) == 4
    shard = sorted(iv for k in ("shard.route", "shard.dispatch",
                                "shard.combine") for iv in by[k])
    assert all(a[1] <= b[0] for a, b in zip(shard, shard[1:])), \
        "shard spans overlap"
    for s, e in by["op.lookup"] + by["op.update"] + by["op.scan"]:
        assert any(ds <= s and e <= de for ds, de in by["shard.dispatch"])


HERE = os.path.dirname(os.path.abspath(__file__))


def _fixture(name):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def test_existing_recorded_trace_reduces_as_before():
    """Every key ``reduce`` returns on the first recorded v5e trace reads
    what it read when the trace was recorded."""
    got = trace.reduce(_fixture("v5e_ycsb_c_2steps.json"), n_devices=1)
    want = _fixture("v5e_ycsb_c_2steps.reduced.json")
    assert set(got) >= set(want)
    for k in ("window_s", "busy_s"):
        assert got[k] == pytest.approx(want[k], rel=1e-12)
    assert got["program_s"] == pytest.approx(want["program_s"], rel=1e-12)
    for k in ("top_ops", "idle_gaps"):
        assert [n for n, _ in got[k]] == [n for n, _ in want[k]]
        assert [v for _, v in got[k]] == pytest.approx(
            [v for _, v in want[k]], rel=1e-12)


def _scope_s(ops, t0, t1, part):
    """Device seconds in which some op under scope ``part`` runs: the
    union of those ops' intervals (a ``while`` op spans its body's ops)."""
    iv = trace._clip([e for e in ops if f"/{part}/" in e[3]], t0, t1)
    u = trace._union(iv[iv[:, 1] > iv[:, 0]])
    return float((u[:, 1] - u[:, 0]).sum()) * 1e-9 if u.size else 0.0


def test_recorded_trace_with_the_index_spans_and_scopes():
    """Two steps of ycsb-c traced on one TPU v5e with the index's spans
    (``bench/record_trace.py``): each step's ``op.lookup`` lies inside the
    harness's ``client.call.read``; the reduction reads the lookup
    program as before; the ops under ``descent`` and ``leaf_probe`` lie
    inside the program and take less than its time, each inner level and
    the probe a share of it."""
    ev = _fixture("v5e_ycsb_c_2steps_spans.json")
    r = trace.reduce(ev, n_devices=1)
    assert set(r["program_s"]) == {"_lookup_batch_jit"}
    host = {}
    for name, s, d in ev["host"]:
        host.setdefault(name, []).append((s, s + d))
    assert len(host["op.lookup"]) == 2
    for s, e in host["op.lookup"]:
        assert any(cs <= s and e <= ce for cs, ce in host["client.call.read"])
    (t0, t1), = host["client.window"]
    ops = ev["devices"]["/device:TPU:0"]["ops"]
    prog = r["program_s"]["_lookup_batch_jit"]
    descent = _scope_s(ops, t0, t1, "descent")
    probe = _scope_s(ops, t0, t1, "leaf_probe")
    levels = [_scope_s(ops, t0, t1, f"descent/level_{i}") for i in range(6)]
    assert 0 < probe < descent and descent + probe <= prog
    assert all(0 < lv for lv in levels) and sum(levels) <= descent
    mods = ev["devices"]["/device:TPU:0"]["modules"]
    for e in ops:
        if "/descent/" in e[3] or "/leaf_probe/" in e[3]:
            assert e[3].startswith("jit(_lookup_batch_jit)/")
            assert any(s <= e[1] <= s + d for _, s, d in mods)
