"""Share of their HBM roofline that the Pallas masking kernels reach: the
bytes their stages must move (``bench/counts.py``: the histogram and count
sweeps, the apply sweep, and the fused encode's read and narrow writes,
for every row of the cohort bucket) at the chip's peak bandwidth, over
the kernels' device time.  The stages do a few operations per byte, far
under the chip's ratio of FLOP/s to bytes/s, so bandwidth bounds them."""

UNIT = "%"


def read(ctx):
    """Percent of the bandwidth roofline, or None where no kernel ran."""
    t = ctx.get("trace")
    per_round = ctx.get("mask_kernel_bytes_per_round")
    if not t or not per_round or not t["mosaic_events"] or t["mosaic_s"] <= 0:
        return None
    least_s = t["rounds"] * per_round / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / t["mosaic_s"]
