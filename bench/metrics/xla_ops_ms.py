"""xla_ops_ms: device time per serving step of every operation that is not
a fused kernel (glue layer: ``core.plan.run_fused`` and ``kernels.ops``
layout, halo, u8 quantisation, spike pools, head matmul, decode and NMS,
masking)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["n_steps"]:
        return None
    return tr["other_ns"] / tr["n_steps"] / 1e6
