"""Requests decoded per engine step in the window (token hooks / steps)."""


def read(ctx):
    steps = ctx["counters"]["steps"]
    return sum(s.running for s in steps) / len(steps)
