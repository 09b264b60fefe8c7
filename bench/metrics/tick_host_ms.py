"""tick_host_ms: host time per tick outside the serving step (engine layer:
``serve.engine.Engine`` + ``serve.detector.DetectorEngineCore``).

Mean over the traced ticks of the benchmark's span around
``Engine.run(max_steps=1)`` minus that tick's ``step_wall`` entry (the
program's own span from tick start to the head being ready): frame
assembly and admission bookkeeping before the step, the copy of heads and
detections to the host, and retirement after it."""


def read(ctx):
    gaps = [(tb - ta) - wall for ta, tb, wall, _ in ctx["ticks"] if wall > 0]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
