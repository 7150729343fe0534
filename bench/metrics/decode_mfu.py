"""The decode step's model FLOPs over its device time and the chip's bf16
peak.  FLOPs (the architecture module's ``decode_step_flops``) count every
matmul weight but the embedding gather and attention over the positions
each running request attends, for the requests running in each window
step."""
import counts


def read(ctx):
    t = ctx["trace"]
    if not t or not t["decode_n"]:
        return None
    c, m = ctx["counters"], ctx["config"]["model"]
    flops = [ctx["arch"].decode_step_flops(m, ctx["rank"], s.running, s.pos)
             for s in c["steps"]]
    per_step_s = t["decode_s"] / t["decode_n"]
    peak = counts.peaks(ctx["device_kind"])["bf16_flop_s"]
    return 100.0 * (sum(flops) / len(flops)) / per_step_s / peak
