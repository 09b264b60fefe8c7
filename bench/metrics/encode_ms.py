"""encode_ms: device time per serving step of the encode layer (kernels
layer), its fused kernel and its own glue: every op whose ``op_name``
lies under the model's ``encode`` scope (u8 quantisation of the frames,
block layout with halo, the kernel, unblocking)."""


def read(ctx):
    entry = ctx["trace"].get("by_scope", {}).get("encode")
    if not entry:
        return None
    return entry["kernel_ms"] + entry["glue_ms"]
