"""lm_step_mfu: the whole decode step's share of the chip's bf16 peak (model
step layer).

The model's flops of the mean traced decode step (``lm_work``: 2 × matrix
weights outside the embedding × sequences decoded, the head, and attention
over the live positions, from the harness's record of each tick) over the
step's device time (the per-step union of device op intervals that
``decode_step_ms`` reads) and the bf16 peak of ``peaks.json``."""


def read(ctx):
    steps = ctx["trace"]["step_busy_ns"]
    if not steps or not ctx["work"]:
        return None
    step_s = sum(steps) / len(steps) / 1e9
    return 100.0 * ctx["work"]["flops_per_step"] / step_s / ctx["peaks"]["bf16_flops_per_s"]
