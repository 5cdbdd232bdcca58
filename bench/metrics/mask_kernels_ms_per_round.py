"""Device time of the Pallas masking kernels (Mosaic custom calls: the
selection sweeps, the apply sweep and the fused encode) per traced
round."""

UNIT = "ms"


def read(ctx):
    """Kernel milliseconds per round, or None where no kernel ran."""
    t = ctx.get("trace")
    if not t or not t["mosaic_events"] or not t["rounds"]:
        return None
    return 1e3 * t["mosaic_s"] / t["rounds"]
