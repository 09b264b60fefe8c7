"""upload_ms: synchronous frame staging per tick (engine layer:
``DetectorEngineCore.step``).

Mean over the traced ticks of the program's ``assemble`` and ``upload``
spans, which run only when the double-buffered upload misses (a clip
boundary or another change of the batch's rows): the host-side assembly
of the batch and its transfer to the device, both before the dispatch."""
import trace_spans


def read(ctx):
    spans = ctx.get("spans")
    if not spans:
        return None
    return trace_spans.per_tick_ms(spans, ("assemble", "upload"))
