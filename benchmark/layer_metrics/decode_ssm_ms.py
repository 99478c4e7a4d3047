"""Device time one decode step spends in the Mamba-1 mixers: median,
over the executed ``jit__step`` modules of the trace, of the time on
device operations under the ``ssm_*`` scopes (all 26 Mamba layers
together: in_proj, conv window, x_proj and dt_proj, the one-position
scan over the slab, gate and out_proj). Scopes as in
``benchmark/program_scopes_jamba.py``."""

from benchmark import program_scopes_jamba as scopes


def read(run):
    ht = scopes.of(run)
    return None if ht is None else scopes.decode_ms(ht, scopes.ssm_scopes())
