"""Traffic from ``--seed``: an open-loop schedule of requests. One
generator for every mix; a mix is the parameters in its cell's file
(``workloads/<cell>.json``, key ``traffic``).

Every seed gets the same set of sizes and gaps, each in an order of its
own drawn from the seed, and its own token ids: the amount of work a
window is offered does not depend on the seed; which requests meet, which
of them the window finishes and when each prefill stalls the others does.
The set is not drawn at all: it is the mid-quantiles of the laws the mix
states, so it has no seed of its own.
"""

from statistics import NormalDist

import numpy as np


def _mid_quantiles(n):
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n, spec):
    """The ``n`` mid-quantiles of a log-normal law (``median``, ``sigma``),
    rounded and clipped to ``min`` .. ``max``."""
    z = np.array([NormalDist().inv_cdf(q) for q in _mid_quantiles(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n, rate):
    """The ``n`` mid-quantiles of the gaps of a Poisson stream."""
    return -np.log1p(-_mid_quantiles(n)) / rate


def serve_schedule(seed, traffic, seconds, vocab_size):
    """Open loop: ``[(due_s, prompt ids, max_new_tokens), ...]`` by due
    time. ``queued_at_open`` requests are due at 0 (a server that is
    offered more than it completes is never found empty: the window opens
    on the queue an earlier stretch left); after them ``round(rate_per_s *
    seconds)`` arrivals with exponential gaps. Prompt and output lengths
    log-normal and clipped, no shared prefixes."""
    rate = float(traffic["rate_per_s"])
    at_open = int(traffic["queued_at_open"])
    arrivals = max(1, int(round(rate * seconds)))
    n = at_open + arrivals
    rng = np.random.default_rng(int(seed))
    prompts = rng.permutation(lognormal_lengths(n, traffic["prompt_tokens"]))
    outputs = rng.permutation(lognormal_lengths(n, traffic["output_tokens"]))
    gaps = rng.permutation(exponential_gaps(arrivals, rate))
    due = np.concatenate([np.zeros(at_open), np.cumsum(gaps)])
    return [
        (float(t), rng.integers(1, vocab_size, size=int(p)).tolist(), int(o))
        for t, p, o in zip(due, prompts, outputs)]
