"""Host milliseconds per engine step outside the decode call and its wait:
the engine step's span less the span from the decode call to the
executor's return, averaged over the window's steps."""


def read(ctx):
    steps = ctx["counters"]["steps"]
    host = [(s.t1 - s.t0) - (s.ex1 - s.call0) for s in steps]
    return 1e3 * sum(host) / len(host)
