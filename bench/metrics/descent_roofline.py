"""The lookup's share of its HBM roofline: the least time the chip needs
to move the bytes the step's lookups need (``bench/work.py``) at the
device's published HBM bandwidth (``bench/peaks.py``), over the lookup
program's device time per step. The descent moves a few KB per lane and
computes next to nothing, so bandwidth is its bound."""

from bench import peaks, work

PROGRAM = "_lookup_batch_jit"


def read(ctx):
    r = ctx.reduced
    s = r and r["program_s"].get(PROGRAM)
    lanes = work.lookup_lanes_per_step(ctx.mix)
    if not s or not ctx.steps or not lanes:
        return None
    need = lanes * work.lookup_lane_bytes(ctx.config)
    least_s = need / peaks.peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (s / ctx.steps)
