"""Share of the traced steady window in which no operation ran on the
device: 1 - (union of the device-operation intervals) / window, averaged
over the chips used. From the profiler's trace."""

from benchmark import trace_reduce


def read(run):
    if run.trace_data is None:
        return None
    share = trace_reduce.idle_share(run.trace_data)
    return None if share is None else 100.0 * share
