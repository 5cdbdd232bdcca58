"""Host time per round: each round span's length less the device busy
time inside it, averaged over the traced rounds."""

UNIT = "ms"


def read(ctx):
    """Mean host-only milliseconds per round, or None without a trace."""
    t = ctx.get("trace")
    if not t or not t["round_host_s"]:
        return None
    return 1e3 * sum(t["round_host_s"]) / len(t["round_host_s"])
