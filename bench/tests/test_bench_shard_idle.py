"""The ``shard_idle_ms`` reader: device-idle time under the shard-dispatch
layer's host spans, per traced step."""
import types

import pytest

from bench import layers, trace

MS = 1_000_000  # ns
ENTRY = [{"name": "shard_idle_ms", "unit": "ms"}]


def ctx(reduced, steps=2):
    return layers.Context(
        reduced=reduced, steps=steps,
        mix={"ops": [{"op": "read", "lanes": 4096}]},
        config=types.SimpleNamespace(key_width=24, ns=64, fs=4, n_levels=6),
        spans={}, device_kind="TPU v5 lite")


def reduced(gaps):
    return {"window_s": 0.05, "busy_s": 0.03, "program_s": {},
            "top_ops": [], "idle_gaps": gaps}


def read(c):
    return layers.read_all(ENTRY, c).get("shard_idle_ms", {}).get("value")


def test_shard_gaps_summed_per_step():
    r = reduced([["client.generate", 0.010], ["shard.combine", 0.004],
                 ["shard.route", 0.002], ["shard.dispatch", 0.0002],
                 ["client.wait", 0.001]])
    assert read(ctx(r)) == pytest.approx(1e3 * 0.0062 / 2)
    assert read(ctx(r, steps=4)) == pytest.approx(1e3 * 0.0062 / 4)


def test_no_shard_label_or_no_trace_reads_nothing():
    assert read(ctx(reduced([["client.wait", 0.004],
                             ["op.lookup", 0.001]]))) is None
    assert read(ctx(reduced([]))) is None
    assert read(ctx(None)) is None
    assert read(ctx(reduced([["shard.route", 0.002]]), steps=0)) is None


def test_gaps_are_the_mean_over_chips():
    """Two chips over a 10 ms window: chip 0 idles 2 ms under
    ``shard.route`` and 1 ms under ``shard.combine``, chip 1 idles 4 ms
    under ``shard.route``; one step, so 3.5 ms a step."""
    ev = {"devices": {
        "/device:TPU:0": {"ops": [["fusion.1", 0, 4 * MS],
                                  ["fusion.2", 6 * MS, 3 * MS]],
                          "modules": []},
        "/device:TPU:1": {"ops": [["fusion.1", 0, 4 * MS],
                                  ["fusion.2", 8 * MS, 2 * MS]],
                          "modules": []}},
        "host": [["client.window", 0, 10 * MS],
                 ["shard.route", 4 * MS, 4 * MS],
                 ["shard.combine", 9 * MS, 1 * MS]]}
    r = trace.reduce(ev, n_devices=2)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"shard.route": 0.003, "shard.combine": 0.0005})
    assert read(ctx(r, steps=1)) == pytest.approx(3.5)
