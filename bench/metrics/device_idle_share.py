"""Share of the traced window in which no op ran on the device (mean over
the chips used): 100 * (1 - busy / window)."""

UNIT = "%"


def read(ctx):
    """The idle share, or None without a reduced trace."""
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
