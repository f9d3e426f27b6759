"""The trace reduction and the per-layer readers, on events made by hand
and on a trace recorded on a TPU v5e."""
import json
import os
import types

import pytest

from bench import layers, trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns


def events():
    """A 10 ms window on one device: two programs whose ops overlap, a
    gap while the host draws keys and one while it uploads."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 1 * MS, 2 * MS],
                    ["gather.2", 2 * MS, 2 * MS],       # overlaps fusion.1
                    ["fusion.1", 6 * MS, 1 * MS],
                    ["scatter.3", 7 * MS, 2 * MS]],
            "modules": [["jit__lookup_batch_jit(3)", 1 * MS, 3 * MS],
                        ["jit__update_batch_jit(4)", 6 * MS, 3 * MS]]}},
        "host": [["client.window", 0, 10 * MS],
                 ["client.generate", 4 * MS, 2 * MS],
                 ["client.upload", 9 * MS, 2 * MS],     # runs past the window
                 ["other.span", 0, 10 * MS]],
    }


def test_busy_union_programs_and_gaps():
    r = trace.reduce(events(), n_devices=1)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.006)          # [1,4] and [6,9]
    assert r["program_s"] == pytest.approx(
        {"_lookup_batch_jit": 0.003, "_update_batch_jit": 0.003})
    gaps = dict(r["idle_gaps"])
    # [0,1] lies under the outer span alone, [4,6] under client.generate
    assert gaps == pytest.approx({"client.generate": 0.002,
                                  "client.upload": 0.001,
                                  "other.span": 0.001})
    assert dict(r["top_ops"])["fusion.1"] == pytest.approx(0.003)


def test_busy_is_the_mean_over_devices():
    ev = events()
    ev["devices"]["/device:TPU:1"] = {"ops": [["fusion.9", 0, 10 * MS]],
                                      "modules": []}
    r = trace.reduce(ev, n_devices=2)
    assert r["busy_s"] == pytest.approx(0.008)
    assert trace.reduce(ev, n_devices=1)["busy_s"] == pytest.approx(0.006)


def test_no_window_gives_nothing():
    ev = events()
    ev["host"] = [e for e in ev["host"] if e[0] != "client.window"]
    assert trace.reduce(ev, n_devices=1) is None


def ctx(reduced, mix=None):
    cfg = types.SimpleNamespace(key_width=24, ns=64, fs=4, n_levels=6)
    return layers.Context(
        reduced=reduced, steps=2,
        mix=mix or {"ops": [{"op": "read", "lanes": 4096}]}, config=cfg,
        spans={"client.generate": 0.002, "client.book": 0.001,
               "client.wait": 0.5},
        device_kind="TPU v5 lite")


ENTRIES = [{"name": n, "unit": u} for n, u in (
    ("device_idle_pct", "%"), ("lookup_device_ms", "ms"),
    ("update_device_ms", "ms"), ("descent_roofline", "%"),
    ("client_host_ms", "ms"))]


def test_readers_on_hand_made_events():
    got = layers.read_all(ENTRIES, ctx(trace.reduce(events(), 1)))
    v = {k: m["value"] for k, m in got.items()}
    assert v["device_idle_pct"] == pytest.approx(40.0)
    assert v["lookup_device_ms"] == pytest.approx(1.5)
    assert v["update_device_ms"] == pytest.approx(1.5)
    assert v["client_host_ms"] == pytest.approx(1.5)
    lane = 28 + 6 * 292 + 164 + 5
    want = 100 * (4096 * lane / 819e9) / 1.5e-3
    assert v["descent_roofline"] == pytest.approx(want)


def test_a_renamed_or_absent_program_reads_nothing():
    ev = events()
    ev["devices"]["/device:TPU:0"]["modules"] = [
        ["jit__lookup_v2(3)", 1 * MS, 3 * MS]]
    got = layers.read_all(ENTRIES, ctx(trace.reduce(ev, 1)))
    assert "lookup_device_ms" not in got and "update_device_ms" not in got
    assert "descent_roofline" not in got
    assert "device_idle_pct" in got
    assert layers.read_all(ENTRIES, ctx(None)).keys() == {"client_host_ms"}


def recorded():
    with open(os.path.join(HERE, "fixtures", "v5e_ycsb_c_2steps.json")) as f:
        return json.load(f)


def test_recorded_v5e_trace():
    """Two steps of ycsb-c traced on one TPU v5e (2^25 keys, 4096 lanes):
    two lookup programs of ~12 ms each, nothing else on the device, the
    idle gaps while the host waits for results and uploads keys."""
    r = trace.reduce(recorded(), n_devices=1)
    assert r["window_s"] == pytest.approx(0.029013, abs=1e-6)
    assert set(r["program_s"]) == {"_lookup_batch_jit"}
    assert r["program_s"]["_lookup_batch_jit"] == pytest.approx(0.023993,
                                                                abs=1e-6)
    # busy is the union of op events: here it equals the programs' time
    assert r["busy_s"] == pytest.approx(r["program_s"]["_lookup_batch_jit"],
                                        rel=1e-3)
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"client.wait", "client.upload"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert len(r["top_ops"]) == trace.TOP


def test_recorded_trace_with_the_program_renamed():
    ev = recorded()
    for dev in ev["devices"].values():
        dev["modules"] = [["jit__lookup_renamed(1)", s, d]
                          for _, s, d in dev["modules"]]
    got = layers.read_all(ENTRIES, ctx(trace.reduce(ev, 1)))
    assert "lookup_device_ms" not in got and "descent_roofline" not in got
    assert got["device_idle_pct"]["value"] > 0
