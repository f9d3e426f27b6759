"""``chip_smoke.py``: its CPU rehearsal passes every exact check, and
without the rehearsal option it refuses a machine that has no TPU."""
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_cpu_without_the_rehearsal_option(chip_smoke, capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_phases_pass_on_the_cpu(chip_smoke, capsys):
    """Every one-chip phase at a tiny size, in this (CPU) process: the
    checks raise on any mismatch with the host reference."""
    chip_smoke.run_one_chip(n=1 << 12, seed=3, batch=128)
    phases = [json.loads(line)["phase"]
              for line in capsys.readouterr().out.splitlines()]
    assert phases == ["data", "build", "lookup", "update", "insert",
                      "remove", "range_scan", "rebuild", "lookup_pallas",
                      "prefix_cache"]


def test_reference_scan_and_upsert(chip_smoke):
    """The plain reference itself: upserts keep the key matrix sorted and
    a scan returns the slice from the first key >= the query."""
    import numpy as np
    nums = np.array([5, 1, 9], np.uint64)
    kb = chip_smoke.ycsb_keys(nums)
    ref = chip_smoke.Reference(kb[np.argsort(nums)], np.array([10, 50, 90]))
    ref.upsert(chip_smoke.ycsb_keys(np.array([7, 5], np.uint64)),
               np.array([70, 55], np.int32))
    assert ref.vals.tolist() == [10, 55, 70, 90]
    em, skb, sv = ref.scan(chip_smoke.ycsb_keys(np.array([6], np.uint64)), 3)
    assert em.tolist() == [2] and sv.tolist() == [[70, 90, 0]]
    assert (skb[0, :2] == chip_smoke.ycsb_keys(
        np.array([7, 9], np.uint64))).all()
    ref.remove(chip_smoke.ycsb_keys(np.array([1, 4], np.uint64)))
    assert ref.n == 3
