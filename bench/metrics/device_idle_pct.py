"""Share of the traced window in which no operation ran on the device:
100 * (1 - busy / window), busy being the union of the device's op
intervals, averaged over the cell's chips."""


def read(ctx):
    r = ctx.reduced
    if not r or not r["window_s"] or r["busy_s"] is None:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
