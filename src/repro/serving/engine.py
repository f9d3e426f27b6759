"""Batched serving engine: continuous batching + FB+-tree prefix cache.

Requests are admitted in waves; each wave's prompts are matched against the
prefix cache (one batched tree lookup), prefilled from the first miss block
(KV for hit blocks is gathered from the page store), then decoded step-wise
in a fixed-size continuous batch. Finished slots are refilled immediately.

The page store keeps per-block KV on host (numpy) — the CPU-scale analogue
of a paged-attention block pool; at fleet scale the same bookkeeping drives
device-resident pages (serve_step lowers independently in the dry-run).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import lm
from repro.models.config import ModelConfig

from repro.core.traverse import TraversalEngine

from .prefix_cache import PrefixCache


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    s_max: int = 256
    block_tokens: int = 32
    n_pages: int = 1024
    max_new_tokens: int = 32
    # traversal engine for the prefix-cache tree (None -> core default)
    tree_backend: Optional[str] = None
    tree_layout: Optional[str] = None
    # prefix-cache tree shards (>1 routes through repro.shard, DESIGN.md §7)
    tree_shards: int = 1
    # fault-injection plan for the cache's lifecycle + shard dispatch
    # (core.faults.FaultPlan; None = fault-free serving) — chaos harness
    faults: Optional[object] = None


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    cached_blocks: int = 0
    t0: float = 0.0       # arrival stamp (perf_counter) — request-latency
    #                       histogram observes done-time minus this


class Engine:
    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig):
        assert cfg.family not in ("ssm", "hybrid", "encdec", "vlm"), \
            "engine demo covers decoder-only KV families"
        self.cfg, self.params, self.scfg = cfg, params, scfg
        self.cache = lm.init_cache(cfg, scfg.max_batch, scfg.s_max)
        self.pos = np.zeros(scfg.max_batch, np.int32)
        self.live: List[Optional[Request]] = [None] * scfg.max_batch
        # serving is throughput-only: run the tree stats-free (DESIGN.md §3)
        tree_engine = (TraversalEngine(scfg.tree_backend or "jnp",
                                       scfg.tree_layout, collect_stats=False)
                       if (scfg.tree_backend or scfg.tree_layout) else None)
        self.prefix = PrefixCache(scfg.n_pages, scfg.block_tokens,
                                  engine=tree_engine,
                                  n_shards=scfg.tree_shards,
                                  faults=scfg.faults)
        # host page store: [n_pages, L, 2, block, kv, hd]
        L = cfg.n_layers
        self.page_kv = np.zeros(
            (scfg.n_pages, L, 2, scfg.block_tokens, cfg.n_kv_heads, cfg.hd),
            np.float32)
        self._decode = jax.jit(
            lambda p, t, pos, c: lm.decode_step(p, cfg, t, pos, c))
        self._prefill = jax.jit(
            lambda p, toks: lm.prefill(p, cfg, {"tokens": toks}, scfg.s_max))
        self.steps = 0
        self._blocks_hit = 0     # prefix-cache blocks served from the store
        self._blocks_seen = 0    # prompt blocks offered to the cache

    # ------------------------------------------------------------- plumbing
    def _store_blocks(self, cache_np, slot: int, page_ids: np.ndarray,
                      first_block: int):
        bt = self.scfg.block_tokens
        k, v = cache_np        # [L, B, S, kv, hd] each
        for j, pid in enumerate(page_ids):
            b0 = (first_block + j) * bt
            self.page_kv[pid, :, 0] = k[:, slot, b0:b0 + bt]
            self.page_kv[pid, :, 1] = v[:, slot, b0:b0 + bt]

    def _load_blocks(self, slot: int, page_ids: Sequence[int]):
        bt = self.scfg.block_tokens
        k = np.array(self.cache.k)
        v = np.array(self.cache.v)
        for j, pid in enumerate(page_ids):
            k[:, slot, j * bt:(j + 1) * bt] = self.page_kv[pid, :, 0]
            v[:, slot, j * bt:(j + 1) * bt] = self.page_kv[pid, :, 1]
        import repro.models.attention as A
        self.cache = A.KVCache(jnp.asarray(k), jnp.asarray(v))

    # --------------------------------------------------------------- admit
    def admit(self, reqs: List[Request]):
        """Fill free slots; batched prefix match across the whole wave."""
        waves = [r for r in reqs][: self.live.count(None)]
        if not waves:
            return
        with obs.span("serve.admit", wave=len(waves)):
            self._admit_wave(waves)

    def _admit_wave(self, waves: List[Request]):
        with obs.span("serve.cache_lookup"):
            hit_blocks, pages = self.prefix.match([r.prompt for r in waves])
        if obs.enabled():
            bt = self.scfg.block_tokens
            self._blocks_hit += int(sum(hit_blocks))
            self._blocks_seen += int(sum(
                r.prompt.shape[0] // bt for r in waves))
            if self._blocks_seen:
                obs.gauge("serve.hit_rate").set(
                    self._blocks_hit / self._blocks_seen)
            obs.counter("serve.admitted").inc(len(waves))
        for r, hb, pg in zip(waves, hit_blocks, pages):
            slot = self.live.index(None)
            r.cached_blocks = hb
            # prefill the whole prompt for the engine cache (single call),
            # but only *new* blocks are published to the page store
            toks = jnp.asarray(r.prompt, jnp.int32)[None]
            with obs.span("serve.prefill"):
                logits, c = self._prefill(self.params, toks)
            k = np.array(self.cache.k)
            v = np.array(self.cache.v)
            k[:, slot] = 0
            v[:, slot] = 0
            k[:, slot, :r.prompt.shape[0]] = np.asarray(c.k)[:, 0, :r.prompt.shape[0]]
            v[:, slot, :r.prompt.shape[0]] = np.asarray(c.v)[:, 0, :r.prompt.shape[0]]
            import repro.models.attention as A
            self.cache = A.KVCache(jnp.asarray(k), jnp.asarray(v))
            if pg:   # demonstrate reuse: overwrite hit blocks from the store
                self._load_blocks(slot, pg)
            new_ids = self.prefix.publish(r.prompt, hb)
            if new_ids is not None and new_ids.size:
                self._store_blocks((np.asarray(c.k), np.asarray(c.v)),
                                   0, new_ids, hb)
            self.pos[slot] = r.prompt.shape[0]
            nxt = int(np.argmax(np.asarray(logits)[0]))
            r.out.append(nxt)
            self.live[slot] = r

    # ---------------------------------------------------------------- step
    def step(self):
        """One decode step for every live slot (continuous batch)."""
        toks = np.zeros(self.scfg.max_batch, np.int32)
        for i, r in enumerate(self.live):
            if r is not None:
                toks[i] = r.out[-1]
        with obs.span("serve.decode"):
            logits, self.cache = self._decode(
                self.params, jnp.asarray(toks), jnp.asarray(self.pos),
                self.cache)
            nxt = np.asarray(jnp.argmax(logits, -1))
        for i, r in enumerate(self.live):
            if r is None:
                continue
            self.pos[i] += 1
            r.out.append(int(nxt[i]))
            if (len(r.out) >= self.scfg.max_new_tokens
                    or self.pos[i] + 1 >= self.scfg.s_max):
                r.done = True
                self.live[i] = None
                if obs.enabled():
                    obs.counter("serve.completed").inc()
                    if r.t0:
                        obs.histogram("serve.request_latency_s").observe(
                            time.perf_counter() - r.t0)
        self.steps += 1

    def run(self, requests: List[np.ndarray], max_steps: int = 10_000
            ) -> List[Request]:
        t0 = time.perf_counter()
        queue = [Request(i, np.asarray(p, np.int32), t0=t0) for i, p in
                 enumerate(requests)]
        pending = list(queue)
        while (pending or any(self.live)) and self.steps < max_steps:
            if pending and None in self.live:
                n_free = self.live.count(None)
                self.admit(pending[:n_free])
                pending = pending[n_free:]
            if any(r is not None for r in self.live):
                self.step()
        return queue
