"""The decode program's share of its memory roofline: the bytes a decode
step must read (``costs.mixtral_decode_bytes``: non-expert weights and the
head once, each distinct routed expert once, the live keys and values)
over the HBM peak, over the median device time of the decode program.
Bound: HBM bandwidth (819 GB/s on a v5e).

The number of distinct experts a step routes to is not visible from
outside the program, so it is taken as its expectation under uniform
routing, E (1 - (1 - k/E)^n) for n live streams: with seeded random
weights the router is near uniform. Live streams and their cached tokens
are the window's means over the steps that ran no prefill."""

from benchmark import costs, trace_reduce

DECODE_MODULE = "jit__step"


def read(run):
    log = run.facts.get("steps_log")
    if run.trace_data is None or not log or run.peaks is None:
        return None
    durs = sorted(trace_reduce.module_durations_ns(
        run.trace_data, (DECODE_MODULE,)))
    if not durs:
        return None
    ms = durs[len(durs) // 2] / 1e6
    steps = [(n, kv) for s, e, n, kv, pf in log if pf == 0 and n > 0]
    if not steps:
        return None
    c = run.config
    E, k = c["num_local_experts"], c["num_experts_per_tok"]
    n = sum(a for a, _ in steps) / len(steps)
    kv = sum(b for _, b in steps) / len(steps)
    distinct = E * (1 - (1 - k / E) ** n)
    need = costs.mixtral_decode_bytes(
        c, n, [distinct] * c["num_hidden_layers"], kv)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (ms / 1e3)
