"""Share of the device's idle time that lies outside every
``serve/step`` and ``serve/submit`` span: what the caller's loop between
two steps, not the engine, costs the chip. Idle time is the gaps between
the first device's operations ("XLA Ops"), from the start of the first
traced ``serve/step`` span to the end of the last; each idle instant is
put down to the innermost program span that covers it
(``benchmark/program_trace.py``)."""

from benchmark import program_trace


def read(run):
    pt = program_trace.of(run)
    return None if pt is None else program_trace.outside_share(pt)
