"""decode_roofline: the decode step's share of its roofline (model step
layer).

max(flops ÷ bf16 peak, bytes ÷ HBM bandwidth) of the mean traced decode
step (``lm_work``: every weight once, and k and v of the live positions of
every decoded sequence) over the step's device time (``decode_step_ms``)."""


def read(ctx):
    steps = ctx["trace"]["step_busy_ns"]
    if not steps or not ctx["work"]:
        return None
    step_s = sum(steps) / len(steps) / 1e9
    return 100.0 * ctx["work"]["roofline_s"] / step_s
