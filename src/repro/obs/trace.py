"""Nestable host-side tracing spans (DESIGN.md §9).

``span("op.lookup")`` always enters a ``jax.profiler.TraceAnnotation``
named exactly ``name`` (no labels, no parent path: one name is one kind
of span in a trace), so a profiler capturing the process sees the host
region beside the device timeline. With no profiler capturing, the
annotation costs well under a microsecond.

With telemetry enabled, the span also times the region into the metrics
registry: histogram ``span.<dotted.path>``, the path being the names of
the enclosing spans joined with dots (the same leaf name nested under
different parents stays distinguishable), labelled with ``labels``.

Never anything inside a jitted program: spans wrap launches and are
invisible to tracing, which keeps compiled HLO byte-identical with
telemetry on or off.
"""
from __future__ import annotations

import time
from typing import List

from jax import profiler as _profiler

from . import registry as _reg

__all__ = ["span", "current_path"]

_STACK: List[str] = []


def current_path() -> str:
    """Dotted path of the innermost open registry span ("" at top level;
    spans entered while telemetry is off are not on the path)."""
    return ".".join(_STACK)


class _Span:
    __slots__ = ("name", "labels", "path", "t0", "_annot")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = labels
        self.path = ""
        self.t0 = 0.0
        self._annot = _profiler.TraceAnnotation(name)

    def __enter__(self):
        _STACK.append(self.name)
        self.path = ".".join(_STACK)
        self._annot.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self._annot.__exit__(*exc)
        if _STACK and _STACK[-1] == self.name:
            _STACK.pop()
        _reg.histogram(f"span.{self.path}", **self.labels).observe(dt)
        return False


def span(name: str, **labels):
    """Context manager over a host region: a profiler annotation named
    ``name`` always, and with telemetry enabled a duration sample in
    histogram ``span.<path>`` (labels become metric labels — keep their
    cardinality bounded: shard ids and op names, not batch contents)."""
    if not _reg.enabled():
        return _profiler.TraceAnnotation(name)
    return _Span(name, labels)
