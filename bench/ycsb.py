"""YCSB record keys and request distributions, made from a seed.

Keys follow YCSB's core workload with ``zeropadding=19``: ``user`` plus
the record's key number as 19 decimal digits (23 bytes, zero-padded to a
24-byte row). YCSB hashes the insert counter into a non-negative Java
long, so key numbers are spread evenly over ``[0, 2^63)``. Here the
``i``-th loaded key number is drawn inside the ``i``-th of ``n`` equal
strata of that space, which makes the loaded set sorted and distinct by
construction (no host sort of ``n`` keys) while keeping YCSB's spread.
Loaded numbers are even and fresh (insertable) numbers odd, so the two
pools never meet.

Request distributions:

* ``zipfian`` -- YCSB's scrambled zipfian: rank drawn from a zipf law with
  exponent ``theta`` over the loaded records by inverse CDF, then the rank
  scrambled over the records by a seeded bijection, so hot records are
  spread over the key space (the inverse-CDF method is
  ``benchmarks/common.py::zipf_indices``, split here into a CDF made once
  and draws per step).
* ``uniform`` -- every loaded record alike.
* ``latest`` -- YCSB's skewed-latest: zipf rank counted back from the
  most recently inserted record.
"""
from __future__ import annotations

import numpy as np

KEY_PREFIX = b"user"
DIGITS = 19
KEY_LEN = len(KEY_PREFIX) + DIGITS      # 23 bytes
KEY_WIDTH = 24                          # one zero pad byte
SPACE = 1 << 63

_U64 = np.uint64


def mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser: a bijection of uint64 (wrapping arithmetic)."""
    x = np.asarray(x, _U64)
    x = (x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)
    return x ^ (x >> _U64(31))


def seed_word(seed: int, salt: int) -> np.uint64:
    """One 64-bit word from ``(seed, salt)``; any Python int seed."""
    w = np.random.SeedSequence([int(seed) & (2**64 - 1), int(seed) >> 64,
                                salt]).generate_state(1, np.uint64)[0]
    return _U64(w)


def stratified_numbers(idx, count: int, word: np.uint64,
                       parity: int) -> np.ndarray:
    """Key numbers of positions ``idx`` among ``count`` strata of
    ``[0, 2^63)``: ``idx * w + 2 * r + parity`` with ``r`` a seeded hash
    of ``idx`` below ``w / 2``. Ascending in ``idx``; distinct."""
    w = SPACE // int(count)
    if w < 2:
        raise ValueError(f"{count} keys do not fit the 2^63 key space")
    idx = np.asarray(idx, _U64)
    half = _U64(w // 2)
    r = mix64(idx ^ word) % half
    return idx * _U64(w) + _U64(2) * r + _U64(parity)


def key_bytes(nums: np.ndarray) -> np.ndarray:
    """``uint8 [n, KEY_WIDTH]`` rows ``user`` + 19 zero-padded digits."""
    nums = np.asarray(nums, _U64)
    rows = np.empty((KEY_WIDTH, nums.shape[0]), np.uint8)
    rows[:len(KEY_PREFIX)] = np.frombuffer(KEY_PREFIX, np.uint8)[:, None]
    rows[KEY_LEN:] = 0
    x = nums.copy()
    for pos in range(KEY_LEN - 1, len(KEY_PREFIX) - 1, -1):
        q = x // _U64(10)
        rows[pos] = x - q * _U64(10)
        x = q
    rows[len(KEY_PREFIX):KEY_LEN] += ord("0")
    return np.ascontiguousarray(rows.T)


def key_numbers(kb: np.ndarray) -> np.ndarray:
    """Inverse of :func:`key_bytes` (rows that are not such keys give
    garbage; callers compare whole rows first)."""
    d = np.asarray(kb)[:, len(KEY_PREFIX):KEY_LEN].astype(_U64) - _U64(48)
    x = np.zeros(d.shape[0], _U64)
    for j in range(DIGITS):
        x = x * _U64(10) + d[:, j]
    return x


def number_parts(nums: np.ndarray) -> np.ndarray:
    """``int32 [n, 3]``: the 19 digits split 9 + 5 + 5, each part small
    enough for 32-bit device arithmetic."""
    nums = np.asarray(nums, _U64)
    out = np.empty((nums.shape[0], 3), np.int32)
    out[:, 0] = nums // _U64(10**10)
    out[:, 1] = (nums // _U64(10**5)) % _U64(10**5)
    out[:, 2] = nums % _U64(10**5)
    return out


def device_key_bytes(parts):
    """Device twin of :func:`key_bytes` from :func:`number_parts` rows."""
    import jax.numpy as jnp
    cols = [jnp.full(parts.shape[:1], c, jnp.uint8) for c in KEY_PREFIX]
    for j, width in enumerate((9, 5, 5)):
        p = parts[:, j]
        for e in range(width - 1, -1, -1):
            cols.append(((p // 10**e) % 10 + ord("0")).astype(jnp.uint8))
    cols.append(jnp.zeros(parts.shape[:1], jnp.uint8))
    return jnp.stack(cols, axis=1)


def scramble(x, n: int, word: np.uint64) -> np.ndarray:
    """A seeded bijection of ``[0, n)`` applied to ``x``: three rounds of
    multiply, xorshift and add modulo the next power of two, cycle-walked
    back into range."""
    bits = max(2, (int(n) - 1).bit_length())
    mask = _U64((1 << bits) - 1)
    keys = mix64(np.arange(1, 4, dtype=_U64) ^ word)
    shift = _U64(bits // 2 + 1)

    def rounds(v):
        for k in keys:
            v = (v * (k | _U64(1))) & mask
            v = v ^ (v >> shift)
            v = (v + k) & mask
        return v

    y = rounds(np.asarray(x, _U64))
    out = y >= _U64(n)
    while out.any():
        y[out] = rounds(y[out])
        out = y >= _U64(n)
    return y.astype(np.int64)


class Chooser:
    """Draws record positions for one request distribution."""

    def __init__(self, spec: dict, n: int, word: np.uint64):
        self.kind = spec["kind"]
        self.n = int(n)
        self.word = word
        if self.kind in ("zipfian", "latest"):
            theta = float(spec["theta"])
            w = np.arange(1, self.n + 1, dtype=np.float64) ** (-theta)
            self.cdf = np.cumsum(w)
            self.cdf /= self.cdf[-1]
        elif self.kind != "uniform":
            raise ValueError(f"unknown request distribution {self.kind!r}")

    def ranks(self, rng, k: int) -> np.ndarray:
        idx = np.searchsorted(self.cdf, rng.random(k))
        return np.minimum(idx, self.n - 1)

    def loaded(self, rng, k: int) -> np.ndarray:
        """Positions (in key order) of ``k`` loaded records."""
        if self.kind == "uniform":
            return rng.integers(0, self.n, size=k)
        return scramble(self.ranks(rng, k), self.n, self.word)
