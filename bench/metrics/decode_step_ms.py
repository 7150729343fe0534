"""Device milliseconds of one run of the decode-step program, from the
trace."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["decode_n"]:
        return None
    return 1e3 * t["decode_s"] / t["decode_n"]
