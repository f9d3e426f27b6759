"""One run of one cell: set-up, a closed-loop client, the check.

A step is one batch of ops drawn by the cell's mix, split into the mix's
fixed sub-batches, issued in the mix's order, each to its entry. The step
ends when every result is on the host, and the next starts at once: one
step in flight. An op's latency runs from its step's issue (after its
keys were drawn) until its own sub-batch's result is on the host.

Set-up makes the data from the seed, builds the index and runs the first
``WARMUP_STEPS`` steps, which compile every shape the window uses. The
window then runs steps until ``seconds`` have passed. Every step's
results, warm-up included, are compared with the plain reference once the
window has closed and the index is freed.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import faults as F
from . import index as IX
from . import reference as R
from . import traffic as T
from . import ycsb

WARMUP_STEPS = 3
TRACE_STEPS = 40            # steps under the profiler in a --trace 1 run
READBACK_LANES = 4096       # lanes per read-back batch where no read shape


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


class Spans:
    """The harness's own host spans: each is a profiler annotation (so a
    trace shows it beside the device) and a running sum of host time."""

    def __init__(self):
        import jax.profiler
        self._annot = jax.profiler.TraceAnnotation
        self.total: Dict[str, float] = {}
        self.counting = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with self._annot(name):
            yield
        if self.counting:
            self.total[name] = self.total.get(name, 0.0) \
                + time.perf_counter() - t0


class Step:
    __slots__ = ("subs", "results", "failed", "done")

    def __init__(self, subs):
        self.subs = subs
        self.results: List[tuple] = []
        self.failed: List[Optional[np.ndarray]] = []
        self.done: List[float] = []


def percentile_ms(lat_s: List[float], counts: List[int], q: float) -> float:
    """Nearest-rank ``q``-quantile of latencies given with op counts."""
    lat = np.asarray(lat_s)
    cnt = np.asarray(counts)
    order = np.argsort(lat, kind="stable")
    cum = np.cumsum(cnt[order])
    rank = math.ceil(q * cum[-1])
    return float(lat[order][np.searchsorted(cum, rank)] * 1e3)


def _call(index, sb, args):
    kb, kl, vals = args
    if sb.op == "read":
        return index.read(kb, kl)
    if sb.op == "update":
        return index.update(kb, kl, vals)
    if sb.op == "insert":
        return index.insert(kb, kl, vals)
    if sb.op == "remove":
        return index.remove(kb, kl)
    if sb.op == "rmw":
        return index.rmw(kb, kl)
    return index.scan(kb, kl, index.max_items)


def issue(index, subs, spans: Spans):
    """Send one step's sub-batches in order; returns the step and its
    pending results. The clock of its ops starts here."""
    step = Step(subs)
    t_issue = time.perf_counter()
    pending = []
    for sb in subs:
        with spans("client.upload"):
            args = index.put(sb.kb, sb.kl, sb.vals)
        with spans(f"client.call.{sb.op}"):
            pending.append(_call(index, sb, args))
    return step, pending, t_issue


def finish(step: Step, pending, t_issue: float, spans: Spans) -> Step:
    """Wait for each sub-batch's results in issue order."""
    for res, failed in pending:
        with spans("client.wait"):
            host = IX.fetch(res)
            t = time.perf_counter()
        with spans("client.book"):
            step.results.append(host)
            step.failed.append(None if failed is None else np.asarray(failed))
            step.done.append(t - t_issue)
    return step


class Client:
    """The closed loop: one step in flight. The next step's ops are drawn
    while the device works on this one, then issued once it has ended."""

    def __init__(self, index, traffic: T.Traffic, spans: Spans):
        self.index, self.traffic, self.spans = index, traffic, spans
        with spans("client.generate"):
            self.next = traffic.step()

    def step(self) -> Step:
        step, pending, t_issue = issue(self.index, self.next, self.spans)
        with self.spans("client.generate"):
            self.next = self.traffic.step()
        return finish(step, pending, t_issue, self.spans)


class Run:
    """Everything one run needs: a configuration, a mix, a seed."""

    def __init__(self, conf: dict, mix: dict, seed: int,
                 phase: Callable, fault: Optional[str] = None):
        self.mix = mix
        n = int(conf["records"])
        self.data_word = ycsb.seed_word(seed, 1)
        with phase("data"):
            nums = ycsb.stratified_numbers(np.arange(n, dtype=np.uint64), n,
                                           self.data_word, 0)
            rng = np.random.default_rng([int(seed) & (2**63 - 1),
                                         int(seed) >> 63, 1])
            vals = rng.integers(0, 2**31 - 1, size=n, dtype=np.int32)
            self.traffic = T.Traffic(mix, n, seed, self.data_word)
        self.index = IX.DEPLOYMENTS[conf["layout"]](conf, nums, vals, phase)
        self.index.max_items = int(mix.get("max_items", 1))
        if fault:
            self.index = F.Faulty(self.index, fault)
        self.loaded = (nums, vals)
        self.steps: List[Step] = []


def run_cell(conf: dict, mix: dict, seed: int, seconds: float,
             trace: bool = False, fault: Optional[str] = None,
             t_start: Optional[float] = None, per_layer=None) -> dict:
    """One run; returns the result line's fields (``metrics`` holds every
    end-to-end and per-layer reading the run made)."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    setup: Dict[str, float] = {}

    @contextlib.contextmanager
    def phase(name):
        t0 = time.perf_counter()
        yield
        setup[name] = time.perf_counter() - t0
        emit(phase=name, s=setup[name])

    compiles = _CompileCounter()
    run = Run(conf, mix, seed, phase, fault)
    spans = Spans()
    with phase("warmup"):
        client = Client(run.index, run.traffic, spans)
        for _ in range(WARMUP_STEPS):
            run.steps.append(client.step())
    if fault == "stale_reads":
        run.index.first = run.index.inner.tree
    setup_s = time.perf_counter() - t_start
    emit(phase="setup", s=setup_s)

    compiles.start()
    tracer = _Tracer() if trace else None
    window: List[Step] = []
    t0 = time.perf_counter()
    t_end = t0
    traced_steps = 0
    while True:
        if tracer and traced_steps == 0:
            tracer.start()
            spans.counting = True
        window.append(client.step())
        t_end = time.perf_counter()
        if tracer and tracer.on:
            traced_steps += 1
            if traced_steps == TRACE_STEPS or t_end - t0 >= seconds:
                tracer.stop()
                spans.counting = False
        if t_end - t0 >= seconds:
            break
    window_s = t_end - t0
    compiles.stop()
    run.steps.extend(window)
    emit(phase="window", s=window_s, steps=len(window),
         compiles=compiles.count)

    peaks = [_peak_bytes(d) for d in run.index.devices]
    peak = max(peaks) if all(p is not None for p in peaks) else None
    tree_config = run.index.config
    n_devices = len(run.index.devices)

    lat, cnt = [], []
    for st in window:
        for sb, d in zip(st.subs, st.done):
            lat.append(d)
            cnt.append(sb.nums.shape[0])
    ops = int(sum(cnt))
    check = Check(run)
    check.collect()                       # reads the index: before freeing
    run.index.close()
    run.index = None
    gc.collect()
    checks = check.compare()

    metrics = {
        "ops_per_s": {"value": ops / window_s, "unit": "ops/s"},
        "p95_op_ms": {"value": percentile_ms(lat, cnt, 0.95), "unit": "ms"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    if peak is not None:
        metrics["peak_hbm_gb"] = {"value": peak / 1e9, "unit": "GB"}
    devices = jax.devices()
    out = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": ops,
        "failed": int(checks["unanswered"]["value"]),
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": n_devices,
                   "memory_peak_bytes": peak},
        "setup_phases": setup,
        "checks": checks,
    }
    if tracer:
        from . import layers
        red = tracer.reduce(n_devices=n_devices)
        ctx = layers.Context(reduced=red, steps=traced_steps, mix=mix,
                             config=tree_config, spans=dict(spans.total),
                             device_kind=devices[0].device_kind)
        out["metrics"] = layers.read_all(per_layer or [], ctx)
        if red is not None:
            out["device"]["busy_s"] = red["busy_s"]
            out["device"]["window_s"] = red["window_s"]
            out["breakdown"] = {"device_ops": red["top_ops"],
                                "idle_gaps": red["idle_gaps"]}
    return out


def _peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


class _CompileCounter:
    """Counts XLA compilations (cache loads included) while started."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.on = False

        def listener(event, duration, **kw):
            if self.on and event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listener)

    def start(self):
        self.on = True

    def stop(self):
        self.on = False


class _Tracer:
    """The JAX profiler over the first steps of the window, written to a
    temporary directory (``TMPDIR``) that is removed once read."""

    def __init__(self):
        import tempfile
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.on = False
        self._window = None

    def start(self):
        import jax.profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # annotations only, no call trace
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("client.window")
        self._window.__enter__()
        self.on = True

    def stop(self):
        import jax.profiler
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False

    def reduce(self, n_devices: int):
        import shutil

        from . import trace as TR
        try:
            events = TR.load_xspace_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return TR.reduce(events, n_devices=n_devices)


class Check:
    """Compares every result of the run with the plain reference."""

    def __init__(self, run: Run):
        self.run = run
        self.readback = None
        self.scan_nums: Dict[int, np.ndarray] = {}

    def collect(self):
        """What needs the index itself: key bytes behind scan results and a
        read-back of every key the run wrote."""
        run = self.run
        written = []
        for si, st in enumerate(run.steps):
            for j, (sb, res) in enumerate(zip(st.subs, st.results)):
                if sb.op == "scan":
                    emitted, kids = res[0], res[1]
                    kb = run.index.scan_keys(np.asarray(kids))
                    nums = ycsb.key_numbers(kb.reshape(-1, kb.shape[-1]))
                    self.scan_nums[(si, j)] = nums.reshape(kids.shape)
                if sb.op in ("update", "insert", "remove", "rmw"):
                    written.append(sb.nums)
        if not written:
            return
        keys = np.unique(np.concatenate(written))
        lanes = next((int(o["lanes"]) for o in run.mix["ops"]
                      if o["op"] == "read"), READBACK_LANES)
        found, vals = [], []
        for s in range(0, keys.size, lanes):
            chunk = keys[s:s + lanes]
            pad = np.resize(chunk, lanes)
            args = run.index.put(ycsb.key_bytes(pad),
                                 np.full(lanes, ycsb.KEY_LEN, np.int32), None)
            (f, v), _ = run.index.read(args[0], args[1])
            f, v = IX.fetch((f, v))
            found.append(f[:chunk.size])
            vals.append(v[:chunk.size])
        self.readback = (keys, np.concatenate(found), np.concatenate(vals))

    def compare(self) -> Dict[str, dict]:
        run = self.run
        ref = R.Reference(*run.loaded)
        run.loaded = None
        bad = {"read_mismatch": 0, "write_ack_mismatch": 0,
               "scan_mismatch": 0, "readback_mismatch": 0, "unanswered": 0}
        for si, st in enumerate(run.steps):
            for j, (sb, res, failed) in enumerate(
                    zip(st.subs, st.results, st.failed)):
                ok = np.ones(sb.nums.shape[0], bool)
                if failed is not None:
                    ok = ~failed
                    bad["unanswered"] += int(failed.sum())
                q = sb.nums[ok]
                if sb.op in ("read", "rmw"):
                    f, v = ref.lookup(q)
                    gf, gv = res[0][ok], res[1][ok]
                    bad["read_mismatch"] += int(
                        ((gf != f) | (f & (gv != v))).sum())
                if sb.op == "rmw":
                    want = ref.update(q, (v.astype(np.int64) + 1)
                                      .astype(np.int32))
                    bad["write_ack_mismatch"] += int((res[2][ok] != want).sum())
                elif sb.op in ("update", "insert"):
                    want = getattr(ref, sb.op)(q, sb.vals[ok])
                    bad["write_ack_mismatch"] += int((res[0][ok] != want).sum())
                elif sb.op == "remove":
                    want = ref.remove(q)
                    bad["write_ack_mismatch"] += int((res[0][ok] != want).sum())
                elif sb.op == "scan":
                    bad["scan_mismatch"] += self._scan(
                        ref, sb, res, self.scan_nums[(si, j)], ok)
        if self.readback is not None:
            keys, gf, gv = self.readback
            f, v = ref.lookup(keys)
            bad["readback_mismatch"] = int(((gf != f) | (f & (gv != v))).sum())
        return {k: {"value": v, "limit": 0} for k, v in bad.items()}

    @staticmethod
    def _scan(ref, sb, res, nums, ok) -> int:
        emitted, _, vals = res
        lengths = sb.lengths[ok]
        n_want, want_n, want_v = ref.scan(sb.nums[ok], lengths)
        got_n = np.minimum(emitted[ok], lengths)
        width = want_n.shape[1]
        in_len = np.arange(width)[None, :] < n_want[:, None]
        g_nums = nums[ok][:, :width]
        g_vals = vals[ok][:, :width]
        wrong = (got_n != n_want) | ((in_len & ((g_nums != want_n)
                                                | (g_vals != want_v))).any(-1))
        return int(wrong.sum())


def print_checks(checks: Dict[str, dict]) -> None:
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
