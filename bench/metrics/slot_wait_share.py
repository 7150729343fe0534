"""Share of the queue wait of the requests admitted in the window spent
between a schedule call that skipped them for want of an adapter slot and
the next schedule call (``program_spans.wait_split``)."""
import program_spans


def read(ctx):
    tracer = ctx.get("tracer")
    if tracer is None:
        return None
    split = program_spans.wait_split(tracer, *ctx["window"])
    total = sum(split.values())
    if not total:
        return None
    return 100.0 * split["slot"] / total
