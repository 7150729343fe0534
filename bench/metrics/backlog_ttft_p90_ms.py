"""As ``ttft_p90_ms``, above the knee: the time to first token waits on a
queue that grows through the window, so it swings with the smallest
change in the step time and is read here, not bounded."""
import statistics


def read(ctx):
    ttft = ctx["counters"]["ttft_s"]
    if len(ttft) < 2:
        return None
    return 1e3 * statistics.quantiles(ttft, n=100)[89]
