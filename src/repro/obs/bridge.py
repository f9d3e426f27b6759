"""Device-counter export: drain op reports into the registry.

The tree's modeled hardware counters (``BranchStats``/``LeafStats``, and
the op-level ``OpReport``/``BuildReport`` aggregates built from them) are
device arrays produced by the jitted ops — under a stats-free engine the
whole machinery compiles away (DESIGN.md §3) and there is nothing to
drain. This bridge is the host-side sink for the stats-on path: ONE
``jax.device_get`` per batch pulls the entire pytree across (never a
per-level or per-field sync), then per-lane counters are summed into
registry counters named ``tree.<field>`` labeled by op.

A drain is a host sync: it waits for the op's program to finish. It runs
only with telemetry enabled, after the op's span has closed, so enabling
telemetry is an operator's choice to trade that wait (which serialises
back-to-back launches) for the counters; with it off no op syncs.

Draining preserves the compile-away contract by construction: it only
touches values the op already returned, so enabling telemetry changes no
traced program — the A/B in ``tests/test_obs.py`` pins that the drained
totals match the ``BranchStats`` sums ``tests/test_traverse_parity.py``
asserts directly.
"""
from __future__ import annotations

from typing import Optional

from . import registry as _reg

__all__ = ["drain_op_report"]

# OpReport counter columns that come from BranchStats/LeafStats
# (DESIGN.md §3); `found` et al. are outcomes, not device counters.
_REPORT_COUNTERS = ("feat_rounds", "suffix_bs", "key_compares",
                    "lines_touched", "tag_candidates")


def _host(pytree):
    import jax
    return jax.device_get(pytree)


def drain_op_report(op: str, rep, batch: Optional[int] = None) -> None:
    """Drain a ``core.batch_ops.OpReport`` after one batched op: the
    BranchStats/LeafStats-derived per-lane counters, plus op-level
    ``op.calls`` / ``op.lanes`` / ``op.found`` / ``op.conflicts`` /
    ``op.splits`` outcomes, all labeled ``op=<name>``."""
    if not _reg.enabled() or rep is None:
        return
    host = _host(rep)                          # one device->host sync
    d = dict(zip(rep._fields, host))
    _reg.counter("op.calls", op=op).inc()
    found = d.get("found")
    if found is not None:
        _reg.counter("op.lanes", op=op).inc(int(found.size))
        _reg.counter("op.found", op=op).inc(int(found.sum()))
    for f in ("conflicts", "splits"):
        if f in d:
            _reg.counter(f"op.{f}", op=op).inc(int(d[f]))
    for f in _REPORT_COUNTERS:
        if f in d:
            _reg.counter(f"tree.{f}", op=op).inc(int(d[f].sum()))
