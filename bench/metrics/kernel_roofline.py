"""kernel_roofline: the fused kernels' share of their roofline (kernels
layer).

Sum over the fused layers of max(ops ÷ int8 peak, bytes ÷ HBM bandwidth)
for one frame (``work.py``), times the frames served per step, over the
kernels' device time per step."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["n_steps"] or not tr["kernel_ns"]:
        return None
    kernel_s = tr["kernel_ns"] / tr["n_steps"] / 1e9
    return 100.0 * ctx["work"]["fused_roofline_s"] * ctx["frames_per_step"] / kernel_s
