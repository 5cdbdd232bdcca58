"""Model FLOP utilization of the round: the configuration's forward and
backward FLOPs of every client update aggregated in the traced window
(padding rows of a cohort bucket not counted), per second of the window,
over the chips' bf16 peak."""

UNIT = "%"


def read(ctx):
    """Percent of peak, or None without a trace or a FLOP count."""
    t = ctx.get("trace")
    flops = ctx.get("flops_per_update")
    if not t or not flops or t["window_s"] <= 0:
        return None
    updates = sum(ctx["round_updates"][:t["rounds"]])
    rate = updates * flops / t["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops"])
