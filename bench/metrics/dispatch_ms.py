"""dispatch_ms: host time per tick from the masks to the return of the
jitted serving step (engine layer: ``DetectorEngineCore.step``).

Mean over the traced ticks of the program's ``dispatch`` span: building
the active and cold masks, their upload, the plan check and the jitted
call until it returns (the argument pytree's dispatch)."""
import trace_spans


def read(ctx):
    spans = ctx.get("spans")
    if not spans:
        return None
    return trace_spans.per_tick_ms(spans, ("dispatch",))
