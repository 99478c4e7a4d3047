"""Median device time of one execution of the decode program (the
adapter's jitted ``_step``, found on the trace's "XLA Modules" line by
its name)."""

from benchmark import trace_reduce

DECODE_MODULE = "jit__step"


def read(run):
    if run.trace_data is None:
        return None
    durs = sorted(trace_reduce.module_durations_ns(
        run.trace_data, (DECODE_MODULE,)))
    if not durs:
        return None
    return durs[len(durs) // 2] / 1e6
