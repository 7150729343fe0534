"""p90 of the wall time the requests admitted in the window spent in the
waiting queue, from ``submit`` to admission (the program's
``serve.queued`` spans)."""
import statistics

import program_spans


def read(ctx):
    tracer = ctx.get("tracer")
    if tracer is None:
        return None
    waits = [s.end - s.start
             for s in program_spans.queued(tracer, *ctx["window"])]
    if len(waits) < 2:
        return None
    return 1e3 * statistics.quantiles(waits, n=100)[89]
