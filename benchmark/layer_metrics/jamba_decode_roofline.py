"""The hybrid decode program's share of its memory roofline: the bytes a
decode step must move (``costs_jamba.jamba_decode_bytes``: every weight
once, each live stream's slab in and out, the live keys and values) over
the HBM peak, over the median device time of the decode program. Bound:
HBM bandwidth (819 GB/s on a v5e). Live streams and their cached tokens
are the window's means over the steps that ran no prefill; the program
itself steps all ``max_batch`` slots."""

from benchmark import costs_jamba, trace_reduce

DECODE_MODULE = "jit__step"


def read(run):
    log = run.facts.get("steps_log")
    if run.trace_data is None or not log or run.peaks is None:
        return None
    durs = sorted(trace_reduce.module_durations_ns(
        run.trace_data, (DECODE_MODULE,)))
    steps = [(n, kv) for s, e, n, kv, pf in log if pf == 0 and n > 0]
    if not durs or not steps:
        return None
    ms = durs[len(durs) // 2] / 1e6
    n = sum(a for a, _ in steps) / len(steps)
    kv = sum(b for _, b in steps) / len(steps)
    need = costs_jamba.jamba_decode_bytes(run.config, n, kv)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (ms / 1e3)
