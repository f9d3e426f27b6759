"""Device-idle ms per traced step under the shard-dispatch layer's host
work: the idle gaps whose innermost host span is ``shard.route`` (the
router's launch and the owner fetch), ``shard.dispatch`` (the per-shard
launches) or ``shard.combine`` (results pulled and merged by owner),
summed and divided by the traced steps. ``trace.reduce`` gives each gap
label's seconds already as the mean over the cell's chips, and keeps only
the ten largest labels: a shard span outside them counts nothing here."""

SPANS = ("shard.route", "shard.dispatch", "shard.combine")


def read(ctx):
    r = ctx.reduced
    if not r or not ctx.steps:
        return None
    gaps = [s for name, s in r["idle_gaps"] if name in SPANS]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / ctx.steps
