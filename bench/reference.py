"""The plain reference: the live key set as sorted arrays of key numbers.

It imports nothing of the index. Keys are YCSB key numbers (``ycsb.py``):
the fixed-width 19-digit encoding preserves order, so comparing numbers is
comparing key bytes. The loaded keys sit in one sorted array with their
values and a live mask; keys inserted later sit in a second, small sorted
array. Every operation is answered by ``np.searchsorted``.

Semantics, as the index states them:

* ``lookup``: found and value of each live key; absent keys read (False, 0).
* ``update``: writes only keys that are live; within one batch the last
  lane of a key wins. ``found`` is per lane, as of before the batch.
* ``insert``: upsert; last lane wins; ``found`` says the key was live.
* ``remove``: clears live keys; ``found`` as of before the batch.
* ``scan``: the first ``length`` live keys at or above the start key, in
  ascending order, with their values.
"""
from __future__ import annotations

import numpy as np


def last_wins(q: np.ndarray) -> np.ndarray:
    """Lane indices that carry the last occurrence of each key in ``q``."""
    rev = q[::-1]
    _, first = np.unique(rev, return_index=True)
    return np.sort(q.shape[0] - 1 - first)


class Reference:

    def __init__(self, nums: np.ndarray, vals: np.ndarray):
        self.base = np.asarray(nums, np.uint64)
        if self.base.size > 1 and not (self.base[1:] > self.base[:-1]).all():
            raise ValueError("reference keys must be strictly ascending")
        self.bval = np.asarray(vals, np.int32).copy()
        self.blive = np.ones(self.base.shape, bool)
        self.dnum = np.zeros(0, np.uint64)
        self.dval = np.zeros(0, np.int32)
        self.dlive = np.zeros(0, bool)

    @staticmethod
    def _at(arr, q):
        i = np.searchsorted(arr, q)
        ic = np.minimum(i, max(arr.size - 1, 0))
        hit = (i < arr.size) & (arr[ic] == q) if arr.size else \
            np.zeros(q.shape, bool)
        return hit, ic

    def _find(self, q):
        q = np.asarray(q, np.uint64)
        hb, ib = self._at(self.base, q)
        hd, idd = self._at(self.dnum, q)
        live_b = hb & self.blive[ib] if self.base.size else hb
        live_d = hd & self.dlive[idd] if self.dnum.size else hd
        return q, hb, ib, hd, idd, live_b, live_d

    def lookup(self, q):
        q, hb, ib, hd, idd, lb, ld = self._find(q)
        val = np.zeros(q.shape, np.int32)
        if self.base.size:
            val = np.where(lb, self.bval[ib], val)
        if self.dnum.size:
            val = np.where(ld, self.dval[idd], val)
        return lb | ld, val.astype(np.int32)

    def _write(self, q, vals, only_live: bool):
        q, hb, ib, hd, idd, lb, ld = self._find(q)
        found = lb | ld
        vals = np.asarray(vals, np.int32)
        w = last_wins(q)
        wb = w[hb[w] & (lb[w] if only_live else True)]
        self.bval[ib[wb]] = vals[wb]
        self.blive[ib[wb]] = True
        wd = w[hd[w] & (ld[w] if only_live else True)]
        self.dval[idd[wd]] = vals[wd]
        self.dlive[idd[wd]] = True
        return q, hb, hd, w, vals, found

    def update(self, q, vals):
        return self._write(q, vals, only_live=True)[-1]

    def insert(self, q, vals):
        q, hb, hd, w, vals, found = self._write(q, vals, only_live=False)
        new = w[~hb[w] & ~hd[w]]
        if new.size:
            order = np.argsort(q[new])
            nq, nv = q[new][order], vals[new][order]
            pos = np.searchsorted(self.dnum, nq)
            self.dnum = np.insert(self.dnum, pos, nq)
            self.dval = np.insert(self.dval, pos, nv)
            self.dlive = np.insert(self.dlive, pos, True)
        return found

    def remove(self, q):
        q, hb, ib, hd, idd, lb, ld = self._find(q)
        self.blive[ib[lb]] = False
        self.dlive[idd[ld]] = False
        return lb | ld

    def scan(self, q, lengths):
        """``(count [B], nums [B, max(lengths)], vals [B, ...])``, zero past
        each lane's count."""
        q = np.asarray(q, np.uint64)
        lengths = np.asarray(lengths, np.int64)
        width = int(lengths.max()) if lengths.size else 0
        out_n = np.zeros((q.size, width), np.uint64)
        out_v = np.zeros((q.size, width), np.int32)
        count = np.zeros(q.size, np.int32)
        ib = np.searchsorted(self.base, q)
        idd = np.searchsorted(self.dnum, q)
        for lane in range(q.size):
            need = int(lengths[lane])
            nums, vals = self._live_from(int(ib[lane]), int(idd[lane]), need)
            count[lane] = nums.size
            out_n[lane, :nums.size] = nums
            out_v[lane, :nums.size] = vals
        return count, out_n, out_v

    def _live_from(self, ib: int, idd: int, need: int):
        """The first ``need`` live keys from base row ``ib`` and delta row
        ``idd`` on, merged in key order."""
        got_n, got_v = [], []
        take = need
        while True:
            bn = self.base[ib:ib + take]
            bl = self.blive[ib:ib + take]
            dn = self.dnum[idd:idd + take]
            dl = self.dlive[idd:idd + take]
            nums = np.concatenate([bn[bl], dn[dl]])
            vals = np.concatenate([self.bval[ib:ib + take][bl],
                                   self.dval[idd:idd + take][dl]])
            # a merged prefix is final only up to the smaller of the two
            # windows' last keys: past it the other side may hold keys
            end_b = ib + take >= self.base.size
            end_d = idd + take >= self.dnum.size
            bound = min(bn[-1] if bn.size and not end_b else np.uint64(2**64 - 1),
                        dn[-1] if dn.size and not end_d else np.uint64(2**64 - 1))
            order = np.argsort(nums, kind="stable")
            nums, vals = nums[order], vals[order]
            sure = nums <= bound
            if sure.sum() >= need or (end_b and end_d):
                got_n, got_v = nums[sure][:need], vals[sure][:need]
                return got_n, got_v
            take *= 2
