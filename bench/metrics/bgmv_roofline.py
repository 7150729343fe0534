"""``bgmv``'s share of its roofline: the least time its calls need at the
chip's peaks (``counts.bgmv_call``: each distinct adapter's A and B once,
plus x and y, for each of the module's ``lora_calls``) over their summed
device time, per decode step."""
import numpy as np

import counts


def read(ctx):
    t = ctx["trace"]
    if not t or not t["decode_n"] or not t["bgmv_n"]:
        return None
    c, m = ctx["counters"], ctx["config"]["model"]
    peak = counts.peaks(ctx["device_kind"])
    fed = c["fed_idx"]
    least = []
    for s in c["steps"]:
        ids = fed[s.pos]
        distinct = len(np.unique(ids[ids >= 0]))
        total = 0.0
        for _, n_layers, out in ctx["arch"].lora_calls(m):
            f, b = counts.bgmv_call(m["d_model"], out, ctx["rank"], ids.size,
                                    distinct)
            total += n_layers * counts.roofline_s(f, b, peak)[0]
        least.append(total)
    per_step_s = t["bgmv_s"] / t["decode_n"]
    return 100.0 * (sum(least) / len(least)) / per_step_s
