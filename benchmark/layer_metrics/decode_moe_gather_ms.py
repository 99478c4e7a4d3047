"""Device time one decode step spends gathering the routed experts'
weights: median, over the executed ``jit__step`` modules of the trace, of
the time on device operations whose scope is ``moe_gather`` (the three
``lp["w*"][top_idx]`` of ``models/mixtral.py::_moe_token``, all layers
together). Scopes as in ``benchmark/program_trace.py``."""

from benchmark import program_trace


def read(run):
    pt = program_trace.of(run)
    return None if pt is None else program_trace.decode_ms(
        pt, program_trace.MOE_GATHER_SCOPES)
