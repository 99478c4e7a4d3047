"""What the tracing still cannot name: share of the decode module's
device time (operations inside the ``jit__step`` events of "XLA Modules",
loop wrappers left out) on operations that the join with the compiled
program's HLO gives no scope (``benchmark/program_trace.py``); median
over the executed modules of the trace."""

from benchmark import program_trace


def read(run):
    pt = program_trace.of(run)
    return None if pt is None else program_trace.unscoped_share(pt)
