"""Device time one decode step spends in the expert layers' held share:
median, over the executed ``jit__step`` modules of the trace, of the time
under ``moe_router``, ``moe_shared``, ``moe_experts`` (every held expert
streamed once over all rows) and ``moe_combine``, the seven sparse layers
together. Scopes as in ``benchmark/program_scopes_kexaone.py``."""

from benchmark import program_scopes_kexaone as scopes


def read(run):
    kt = scopes.of(run)
    return None if kt is None else scopes.decode_ms(kt, scopes.MOE_DECODE)
