"""Shared benchmark machinery: datasets (paper Table 2 analogues,
synthesized offline with fixed seeds), tree builders, timed batched runs.

Wall-clock numbers are CPU-backend *relative* measurements (this container
has no TPU); machine-independent counters (key compares, modeled cache
lines, suffix-fallback rates, conflict groups) carry the paper-comparable
claims — see EXPERIMENTS.md.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batch_ops as B
from repro.core import keys as K
from repro.core.fbtree import FBTree, TreeConfig, bulk_build
from repro.core.traverse import TraversalEngine, available_backends  # noqa: F401

SYLL = ["an", "ber", "co", "del", "er", "fo", "gra", "hu", "in", "jo",
        "ka", "lo", "mi", "nor", "ol", "pe", "qua", "ro", "sa", "tu"]


def _word(rng, lo=2, hi=4):
    return "".join(rng.choice(SYLL) for _ in range(rng.integers(lo, hi + 1)))


def make_dataset(name: str, n: int, seed: int = 7) -> Tuple[List, int]:
    """-> (keys, key_width). Distributions mirror paper Table 2."""
    rng = np.random.default_rng(seed)
    if name == "rand-int":
        ks = set()
        while len(ks) < n:
            ks.update(rng.integers(0, 2**63, size=n).tolist())
        return [int(x) for x in list(ks)[:n]], 8
    out = set()
    if name == "3-gram":          # ~16B: three short words
        while len(out) < n:
            out.add(f"{_word(rng)} {_word(rng)} {_word(rng)}".encode()[:38])
        width = 40
    elif name == "ycsb":          # ~23B: user<zero-padded counter hash>
        while len(out) < n:
            out.add(f"user{rng.integers(0, 10**18):019d}".encode())
        width = 24
    elif name == "twitter":       # ~52B: cluster-prefixed anonymized ids
        clusters = [f"c{c:02d}:ns{rng.integers(0,99):02d}:" for c in range(24)]
        while len(out) < n:
            pre = clusters[int(rng.zipf(1.3)) % len(clusters)]
            body = bytes(rng.integers(97, 123, size=40, dtype=np.uint8))
            out.add(pre.encode() + body)
        width = 52
    elif name == "url":           # ~70B: heavy shared prefixes
        hosts = ["http://dbpedia.org/resource/", "http://example.com/a/b/",
                 "https://api.service.io/v2/items/",
                 "http://news.site.net/2024/"]
        while len(out) < n:
            h = hosts[int(rng.zipf(1.2)) % len(hosts)]
            tail = f"{_word(rng)}/{_word(rng)}_{rng.integers(0, 10**9)}"
            out.add((h + tail).encode()[:72])
        width = 72
    else:
        raise KeyError(name)
    return sorted(out)[:n] if len(out) >= n else list(out), width


DATASETS = ("rand-int", "3-gram", "ycsb", "twitter", "url")


def build_tree(keys, width, fs: int = 4,
               ns: int = 64) -> Tuple[FBTree, K.KeySet]:
    ks = K.make_keyset(keys, width)
    cfg = TreeConfig.plan(max_keys=int(len(keys) * 2.5), key_width=width,
                          fs=fs, ns=ns)
    vals = np.arange(len(keys), dtype=np.int32)
    return bulk_build(cfg, ks, vals), ks


def make_engine(backend: str = "jnp") -> TraversalEngine:
    """CLI-facing engine selector: turns constructor validation errors into
    a clean SystemExit before a long benchmark run starts."""
    try:
        return TraversalEngine(backend=backend)
    except ValueError as e:
        raise SystemExit(f"bad --backend: {e}")


def zipf_indices(rng, n_keys: int, n_ops: int, theta: float) -> np.ndarray:
    """Zipfian (skew=theta) request indices over n_keys (YCSB default .99)."""
    if theta <= 0.01:
        return rng.integers(0, n_keys, size=n_ops)
    # standard YCSB zipf via rejection-free inverse CDF approximation
    ranks = np.arange(1, n_keys + 1, dtype=np.float64)
    w = ranks ** (-theta)
    cdf = np.cumsum(w) / w.sum()
    u = rng.random(n_ops)
    idx = np.searchsorted(cdf, u)
    perm = rng.permutation(n_keys)    # decorrelate rank from key order
    return perm[np.clip(idx, 0, n_keys - 1)]


def timed(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time of a jitted batched call (block_until_ready)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def fmt_table(rows: List[Dict], cols: Sequence[str]) -> str:
    widths = {c: max(len(c), *(len(str(r.get(c, ""))) for r in rows))
              for c in cols}
    line = "  ".join(c.ljust(widths[c]) for c in cols)
    out = [line, "  ".join("-" * widths[c] for c in cols)]
    for r in rows:
        out.append("  ".join(str(r.get(c, "")).ljust(widths[c])
                             for c in cols))
    return "\n".join(out)
