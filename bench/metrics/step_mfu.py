"""step_mfu: the whole serving step's share of the chip's int8 peak (model
step layer).

Ops per frame from the work counter (the 27 fused layers and the 1×1 head,
2 × nonzero weights × output pixels × input steps) times the frames served
per step, over the step's device time (the same per-step union of device
op intervals that ``step_device_ms`` reads) and the int8 peak of
``peaks.json``."""


def read(ctx):
    steps = ctx["trace"]["step_busy_ns"]
    if not steps or not ctx["frames_per_step"]:
        return None
    step_s = sum(steps) / len(steps) / 1e9
    ops_per_s = ctx["work"]["step_ops"] * ctx["frames_per_step"] / step_s
    return 100.0 * ops_per_s / ctx["peaks"]["int8_ops_per_s"]
