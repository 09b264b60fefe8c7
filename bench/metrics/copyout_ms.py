"""copyout_ms: host time per tick after the head is ready (engine layer:
``DetectorEngineCore.step``).

Mean over the traced ticks of the program's ``copy_out`` span (heads and
detections to the host, scattered into the requests) and ``retire`` span
(freeing finished streams' rows and shrinking the bucket)."""
import trace_spans


def read(ctx):
    spans = ctx.get("spans")
    if not spans:
        return None
    return trace_spans.per_tick_ms(spans, ("copy_out", "retire"))
