"""kernel_ms: device time per serving step of the fused Pallas kernels
(kernels layer: ``kernels.fused_pipeline``), the trace's
``tpu_custom_call`` operations, summed and divided by the steps."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["n_steps"] or not tr["kernel_ns"]:
        return None
    return tr["kernel_ns"] / tr["n_steps"] / 1e6
