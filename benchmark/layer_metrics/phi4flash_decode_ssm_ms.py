"""Device time one decode step spends in the Mamba-1 mixers: median, over
the executed ``jit__step`` modules of the trace, of the time under the
``ssm_*`` scopes (the nine Mamba layers together: in_proj, conv window,
x_proj and dt_proj, the one-position scan over the slab and its masked
write, gate and out_proj). ``decode_ssm_ms`` is the jamba cell's: its
reader builds that family's programs. Scopes as in
``benchmark/program_scopes_phi4flash.py``."""

from benchmark import program_scopes_phi4flash as scopes


def read(run):
    ft = scopes.of(run)
    return None if ft is None else scopes.decode_ms(ft.coarse, scopes.SSM)
