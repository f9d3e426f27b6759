"""One generator for every traffic mix: a mix is a data file, read here.

A mix file (``bench/traffic/<name>.json``) holds:

* ``ops``: the step's sub-batches in issue order, each ``{"op": <type>,
  "lanes": <k>}``; the proportions are the lanes' shares. Types: ``read``,
  ``update``, ``insert``, ``scan``, ``rmw`` (a read, then an update of the
  same keys with the value read plus one), ``remove``.
* ``request_distribution``: ``{"kind": "zipfian"|"latest", "theta": t}``
  or ``{"kind": "uniform"}``; it picks the keys of every op but inserts.
* ``scan_length``: ``{"min": a, "max": b}``, uniform, and ``max_items``,
  the program's per-lane scan bound (``>= b``); needed where ``scan`` is.
* ``insert_pool``: how many fresh keys inserts may draw; needed where
  ``insert`` is.
"""
from __future__ import annotations

import json
from typing import List, NamedTuple, Optional

import numpy as np

from . import ycsb

OP_TYPES = ("read", "update", "insert", "scan", "rmw", "remove")
KEYED = {"read", "update", "scan", "rmw", "remove"}


class SubBatch(NamedTuple):
    op: str
    nums: np.ndarray            # uint64 [k] key numbers
    kb: np.ndarray              # uint8 [k, KEY_WIDTH]
    kl: np.ndarray              # int32 [k]
    vals: Optional[np.ndarray]  # int32 [k] for update / insert
    lengths: Optional[np.ndarray]  # int32 [k] for scan


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    check_mix(mix)
    return mix


def check_mix(mix: dict) -> None:
    ops = mix.get("ops") or []
    if not ops:
        raise ValueError("a mix needs at least one op")
    for sb in ops:
        if sb.get("op") not in OP_TYPES:
            raise ValueError(f"unknown op {sb.get('op')!r}; one of {OP_TYPES}")
        if int(sb.get("lanes", 0)) < 1:
            raise ValueError(f"op {sb['op']!r} needs lanes >= 1")
    kinds = {sb["op"] for sb in ops}
    if "scan" in kinds:
        sl = mix["scan_length"]
        if not 1 <= int(sl["min"]) <= int(sl["max"]) <= int(mix["max_items"]):
            raise ValueError("scan_length must lie in [1, max_items]")
    if "insert" in kinds and int(mix.get("insert_pool", 0)) < 1:
        raise ValueError("a mix with inserts needs insert_pool")
    if kinds & KEYED:
        ycsb.Chooser(mix["request_distribution"], 2, np.uint64(0))


def step_lanes(mix: dict) -> int:
    """Ops per step: an rmw lane is one op."""
    return sum(int(sb["lanes"]) for sb in mix["ops"])


class Traffic:
    """The op stream of one run: steps drawn from ``seed`` over ``n``
    loaded records whose key numbers are ``number_of(position)``."""

    def __init__(self, mix: dict, n: int, seed: int, data_word):
        self.mix = mix
        self.n = int(n)
        self.data_word = data_word
        self.rng = np.random.default_rng([int(seed) & (2**63 - 1),
                                          int(seed) >> 63, 2])
        self.inserted = 0
        self.pool = int(mix.get("insert_pool", 0))
        if {sb["op"] for sb in mix["ops"]} & KEYED:
            self.chooser = ycsb.Chooser(mix["request_distribution"], self.n,
                                        ycsb.seed_word(seed, 3))
        self.load_word = ycsb.seed_word(seed, 4)
        self.fresh_word = ycsb.seed_word(seed, 5)

    def loaded_numbers(self, pos) -> np.ndarray:
        return ycsb.stratified_numbers(pos, self.n, self.data_word, 0)

    def fresh_numbers(self, j) -> np.ndarray:
        """Key numbers of the ``j``-th inserted records."""
        if np.max(j, initial=-1) >= self.pool:
            raise RuntimeError(f"insert pool of {self.pool} keys exhausted")
        p = ycsb.scramble(j, self.pool, self.fresh_word)
        return ycsb.stratified_numbers(p, self.pool, self.fresh_word, 1)

    def _keys(self, k: int) -> np.ndarray:
        ch = self.chooser
        if ch.kind != "latest":
            return self.loaded_numbers(ch.loaded(self.rng, k))
        total = self.n + self.inserted
        rec = total - 1 - np.minimum(ch.ranks(self.rng, k), total - 1)
        fresh = rec >= self.n
        out = np.empty(k, np.uint64)
        out[fresh] = self.fresh_numbers(rec[fresh] - self.n)
        load_pos = ycsb.scramble(rec[~fresh], self.n, self.load_word)
        out[~fresh] = self.loaded_numbers(load_pos)
        return out

    def _values(self, k: int) -> np.ndarray:
        return self.rng.integers(0, 2**31 - 1, size=k, dtype=np.int32)

    def step(self) -> List[SubBatch]:
        out = []
        for sb in self.mix["ops"]:
            op, k = sb["op"], int(sb["lanes"])
            vals = lengths = None
            if op == "insert":
                nums = self.fresh_numbers(
                    self.inserted + np.arange(k, dtype=np.int64))
                self.inserted += k
            else:
                nums = self._keys(k)
            if op in ("update", "insert"):
                vals = self._values(k)
            if op == "scan":
                sl = self.mix["scan_length"]
                lengths = self.rng.integers(int(sl["min"]), int(sl["max"]) + 1,
                                            size=k).astype(np.int32)
            kb = ycsb.key_bytes(nums)
            kl = np.full(k, ycsb.KEY_LEN, np.int32)
            out.append(SubBatch(op, nums, kb, kl, vals, lengths))
        return out
