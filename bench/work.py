"""The work an operation needs, counted from the tree's geometry alone.

The count is what the algorithm must touch, whichever engine or kernel
does the descent, so every implementation is read against the same work.
A lookup lane reads its query key and length, then at each of the tree's
``n_levels`` inner levels one node's key count, prefix length, prefix row
(``key_width`` bytes), feature block (``fs * ns`` bytes) and the one child
id it follows; at the leaf, the hashtag row and occupancy row (``ns``
bytes each), the matching slot's key id, that key's row and length, and
the value; it writes a value and a found flag. The anchors of the suffix
search are left out: a lane needs them only where the features tie.
"""
from __future__ import annotations


def lookup_lane_bytes(cfg) -> int:
    """HBM bytes one lookup lane needs; ``cfg`` has ``key_width``, ``ns``,
    ``fs`` and ``n_levels`` (a ``TreeConfig``)."""
    L, ns, fs = int(cfg.key_width), int(cfg.ns), int(cfg.fs)
    query = L + 4
    level = 4 + 4 + L + fs * ns + 4
    leaf = ns + ns + 4 + L + 4 + 4
    written = 4 + 1
    return query + int(cfg.n_levels) * level + leaf + written


def lookup_lanes_per_step(mix: dict) -> int:
    """Lanes per step that go through the lookup program: reads and the
    read half of read-modify-writes."""
    return sum(int(o["lanes"]) for o in mix["ops"]
               if o["op"] in ("read", "rmw"))
