"""Device time one decode step spends in the router, the routed experts'
matmuls and the combine: median, over the executed ``jit__step`` modules
of the trace, of the time on device operations whose scope is
``moe_router``, ``moe_experts`` or ``moe_combine`` (all layers together).
Scopes as in ``benchmark/program_trace.py``."""

from benchmark import program_trace


def read(run):
    pt = program_trace.of(run)
    return None if pt is None else program_trace.decode_ms(
        pt, program_trace.MOE_EXPERT_SCOPES)
