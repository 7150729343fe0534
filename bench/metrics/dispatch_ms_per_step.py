"""Mean wall milliseconds of the jitted decode call until it returns (the
program's ``serve.dispatch`` spans), over the window's steps."""
import program_spans


def read(ctx):
    tracer = ctx.get("tracer")
    if tracer is None:
        return None
    spans = program_spans.in_window(tracer, "serve.dispatch", *ctx["window"])
    if not spans:
        return None
    return 1e3 * sum(s.end - s.start for s in spans) / len(spans)
