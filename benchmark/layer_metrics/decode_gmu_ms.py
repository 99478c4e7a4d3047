"""Device time one decode step spends in the gated memory units: median,
over the executed ``jit__step`` modules of the trace, of the time under
``gmu`` (``W_in``, the gate on the step's own scan output of layer 16,
``W_out``), the seven units together. Scopes as in
``benchmark/program_scopes_phi4flash.py``."""

from benchmark import program_scopes_phi4flash as scopes


def read(run):
    ft = scopes.of(run)
    return None if ft is None else scopes.decode_ms(ft.coarse, scopes.GMU)
