"""One generator for every traffic mix: reads a mix's parameters from
``bench/traffic/<mix>.json`` and yields the requests of one run.

The arrival times and the request sizes of a mix are one fixed trace,
drawn from the mix's own ``trace_seed``, and so is the tenant (adapter)
that sends each request: every run of a cell offers the same work, so that
two runs differ by the system's timing and not by the luck of the draw.
A trace holds the expected number of arrivals, and sizes drawn one from
each of as many equal-probability strata, so that a trace seed changes the
order of the work and hardly its amount.
The run's ``--seed`` draws the weights, and which window steps the check
samples.  The length sampler and the adapter pool follow
``repro.core.workload`` (``_sample_lengths("sharegpt")``,
``make_adapter_pool``); the Zipf popularity and the warm start are the
benchmark's own.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    """A request as the benchmark plans it; ``due`` is seconds after the
    traffic starts.  ``warm`` requests stand for the work a server already
    has in flight when the traffic starts (see ``plan``)."""
    due: float
    adapter: int
    prompt_len: int
    output_len: int
    warm: bool = False


def sample_lengths(spec: dict, n: int, rng) -> tuple:
    """Clipped lognormal prompt and output lengths (ShareGPT-like)."""
    out = []
    for part in ("prompt", "output"):
        mu, sigma = spec[part]["lognormal"]
        lo, hi = spec[part]["clip"]
        out.append(np.clip(rng.lognormal(mu, sigma, n), lo, hi).astype(int))
    return out[0], out[1]


def stratified_lengths(spec: dict, n: int, rng) -> tuple:
    """As ``sample_lengths``, with one draw from each of ``n`` strata of
    equal probability, in random order (prompt and output independently):
    ``n`` requests whose sizes follow the mix as closely as ``n`` allows."""
    normal = statistics.NormalDist()
    out = []
    for part in ("prompt", "output"):
        mu, sigma = spec[part]["lognormal"]
        lo, hi = spec[part]["clip"]
        u = (rng.permutation(n) + rng.uniform(size=n)) / n
        z = np.array([normal.inv_cdf(min(max(x, 1e-12), 1 - 1e-12))
                      for x in u])
        out.append(np.clip(np.exp(mu + sigma * z), lo, hi).astype(int))
    return out[0], out[1]


def popularity(mix: dict) -> np.ndarray:
    """Zipf: the i-th most popular of n adapters draws ∝ 1 / i^s."""
    pop = mix["popularity"]
    if pop["kind"] != "zipf":
        raise ValueError(f"unknown popularity {pop['kind']!r}")
    p = 1.0 / np.arange(1, mix["adapters"] + 1, dtype=float) ** pop["s"]
    return p / p.sum()


def rate(mix: dict, knee: float) -> float:
    """Offered requests per second: a share of the knee, the configuration's
    under this mix (``bench/knees/<config>.<mix>.json``)."""
    return mix["rate_share_of_knee"] * knee


def mean_output(mix: dict) -> float:
    """Mean output tokens of a request of the mix (10**6 fixed draws)."""
    _, outs = sample_lengths(mix["lengths"], 1_000_000,
                             np.random.default_rng(0))
    return float(outs.mean())


def plan(mix: dict, knee: float, rows: int,
         span_s: float) -> List[Planned]:
    """Every request of one run that is due before ``span_s``.

    The warm start puts ``round(min(share, 1) * rows)`` requests in flight
    at time 0, so that the rows are as busy as in steady state from the
    first step: a request in flight at a random moment is drawn with
    probability proportional to its output length (length-biased), and has
    already produced a uniform share of it, which joins its prompt as
    context.  ``share * rows`` is the mean number in flight at a rate of
    ``share`` times the knee, since the knee fills ``rows`` rows.

    Poisson arrivals are drawn given their expected number,
    ``round(rate * span_s)``: times uniform over the span, sorted."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    r = rate(mix, knee)
    trace = np.random.default_rng(mix["trace_seed"])
    n = int(round(r * span_s))
    due = np.sort(trace.uniform(0.0, span_s, n))
    ins, outs = stratified_lengths(mix["lengths"], n, trace)
    reqs = [Planned(float(t), 0, int(i), int(o))
            for t, i, o in zip(due, ins, outs)]
    if mix.get("warm_start"):
        n0 = int(round(min(mix["rate_share_of_knee"], 1.0) * rows))
        pool_in, pool_out = sample_lengths(mix["lengths"], 100_000, trace)
        pick = trace.choice(pool_out.size, size=n0,
                            p=pool_out / pool_out.sum())
        warm = []
        for k in pick:
            done = int(trace.integers(0, pool_out[k]))
            warm.append(Planned(0.0, 0, int(pool_in[k]) + done,
                                int(pool_out[k]) - done, warm=True))
        reqs = warm + reqs
    # which tenant sends each request, and which tenants are the popular
    # ones, from the same trace
    order = trace.permutation(mix["adapters"])
    ids = order[trace.choice(mix["adapters"], size=len(reqs),
                             p=popularity(mix))]
    for q, a in zip(reqs, ids):
        q.adapter = int(a)
    return reqs
