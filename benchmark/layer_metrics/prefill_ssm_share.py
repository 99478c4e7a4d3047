"""Share of the traced prefill programs' device time under the Mamba-1
mixer's scopes (``ssm_in_proj``, ``ssm_conv``, ``ssm_params``,
``ssm_scan``, ``ssm_gate_out``): what of a prefill the new mixer is, the
rest being the MLPs, the two attention layers, norms and the head. Scopes
as in ``benchmark/program_scopes_jamba.py``."""

from benchmark import program_scopes_jamba as scopes


def read(run):
    ht = scopes.of(run)
    if ht is None or not ht.prefills:
        return None
    total = scopes.prefill_ns(ht)
    if total <= 0:
        return None
    return 100.0 * scopes.prefill_ns(ht, scopes.ssm_scopes()) / total
