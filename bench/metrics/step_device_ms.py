"""step_device_ms: device time of one serving step (model step layer:
``CompiledDetector._masked``, the forward and postprocess in one jit).

Mean over the traced steps of the union of the device op intervals that
start inside the step's module execution."""


def read(ctx):
    steps = ctx["trace"]["step_busy_ns"]
    if not steps:
        return None
    return sum(steps) / len(steps) / 1e6
