"""decode_step_ms: device time of one decode step (model step layer:
``LMEngineCore._decode``, the jitted ``decode_fn`` of ``models.zoo``).

Mean over the traced decode steps of the union of the device op intervals
that start inside the step's module execution (``jit_decode``)."""


def read(ctx):
    steps = ctx["trace"]["step_busy_ns"]
    if not steps:
        return None
    return sum(steps) / len(steps) / 1e6
