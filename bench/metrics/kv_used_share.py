"""Positions held by running requests (prompt and generated tokens), as a
share of the batch x cache positions reserved on the device, averaged
over the window's steps."""


def read(ctx):
    c = ctx["counters"]
    held = [s.context for s in c["steps"]]
    return 100.0 * sum(held) / len(held) / (c["rows"] * c["cache_len"])
