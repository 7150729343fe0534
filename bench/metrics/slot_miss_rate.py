"""Adapter loads in the window per request admitted in the window."""


def read(ctx):
    c = ctx["counters"]
    if not c["admitted"]:
        return None
    return c["loads"] / c["admitted"]
