"""Device time one decode step spends in the expert layers: median, over
the executed ``jit__step`` modules of the trace, of the time under
``moe_router`` (the scores, the choice, the count of the experts the live
streams chose), ``moe_experts`` (every expert streamed once over all
rows, in the ``all_experts`` form) and ``moe_combine``, the eight expert
layers together. Scopes as in ``benchmark/program_scopes_lfm2.py``."""

from benchmark import program_scopes_lfm2 as scopes


def read(run):
    lt = scopes.of(run)
    return None if lt is None else scopes.decode_ms(lt, scopes.MOE)
