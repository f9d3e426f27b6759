"""Key generator, reference, work model and peak table of the benchmark."""
import types

import numpy as np
import pytest

from bench import peaks, reference, work, ycsb


def test_key_bytes_are_ycsb_keys():
    nums = np.array([0, 7, 10**18 + 3, 2**63 - 1], np.uint64)
    rows = ycsb.key_bytes(nums)
    assert rows.shape == (4, ycsb.KEY_WIDTH)
    want = [f"user{int(x):019d}".encode() + b"\0" for x in nums]
    assert [bytes(r) for r in rows] == want
    assert (ycsb.key_numbers(rows) == nums).all()


def test_device_key_bytes_match_host():
    import jax.numpy as jnp
    nums = ycsb.stratified_numbers(np.arange(300, dtype=np.uint64), 300,
                                   ycsb.seed_word(2**40 + 9, 1), 0)
    dev = np.asarray(ycsb.device_key_bytes(jnp.asarray(
        ycsb.number_parts(nums))))
    assert (dev == ycsb.key_bytes(nums)).all()


def test_loaded_keys_sorted_distinct_and_apart_from_fresh():
    w = ycsb.seed_word(2**35 + 1, 1)
    n = 1 << 12
    nums = ycsb.stratified_numbers(np.arange(n, dtype=np.uint64), n, w, 0)
    assert (nums[1:] > nums[:-1]).all() and (nums % 2 == 0).all()
    fresh = ycsb.stratified_numbers(np.arange(n, dtype=np.uint64), n, w, 1)
    assert (fresh % 2 == 1).all()
    assert nums.max() < ycsb.SPACE


@pytest.mark.parametrize("n", [1, 5, 4096, 5000])
def test_scramble_is_a_bijection(n):
    y = ycsb.scramble(np.arange(n), n, ycsb.seed_word(3, 3))
    assert sorted(y.tolist()) == list(range(n))


def test_zipfian_is_skewed_and_seeded():
    ch = ycsb.Chooser({"kind": "zipfian", "theta": 0.99}, 1 << 16,
                      ycsb.seed_word(1, 3))
    a = ch.loaded(np.random.default_rng(4), 20000)
    b = ch.loaded(np.random.default_rng(4), 20000)
    assert (a == b).all()
    _, counts = np.unique(a, return_counts=True)
    assert counts.max() > 200 * 20000 / (1 << 16)     # a hot record


def test_reference_semantics():
    ref = reference.Reference(np.array([10, 20, 30, 40], np.uint64),
                              np.array([1, 2, 3, 4], np.int32))
    f, v = ref.lookup(np.array([20, 25], np.uint64))
    assert f.tolist() == [True, False] and v.tolist() == [2, 0]
    # the last lane of a key wins; absent keys are not written
    f = ref.update(np.array([20, 20, 25], np.uint64),
                   np.array([7, 8, 9], np.int32))
    assert f.tolist() == [True, True, False]
    assert ref.lookup(np.array([20, 25], np.uint64))[1].tolist() == [8, 0]
    f = ref.insert(np.array([25, 30, 25], np.uint64),
                   np.array([5, 6, 7], np.int32))
    assert f.tolist() == [False, True, False]
    assert ref.lookup(np.array([25, 30], np.uint64))[1].tolist() == [7, 6]
    assert ref.remove(np.array([30, 35], np.uint64)).tolist() == [True, False]
    cnt, nums, vals = ref.scan(np.array([15, 26, 41], np.uint64),
                               np.array([3, 2, 1]))
    assert cnt.tolist() == [3, 1, 0]
    assert nums[0].tolist() == [20, 25, 40] and vals[0].tolist() == [8, 7, 4]
    assert nums[1, :1].tolist() == [40]


def test_lookup_lane_bytes_by_hand():
    cfg = types.SimpleNamespace(key_width=8, ns=4, fs=2, n_levels=2)
    # query 8+4; per level 4+4+8+2*4+4 = 28; leaf 4+4+4+8+4+4 = 28; out 5
    assert work.lookup_lane_bytes(cfg) == 12 + 2 * 28 + 28 + 5
    mix = {"ops": [{"op": "read", "lanes": 3}, {"op": "update", "lanes": 5},
                   {"op": "rmw", "lanes": 2}]}
    assert work.lookup_lanes_per_step(mix) == 5


def test_peaks_known_and_unknown_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
