"""Device time of the selective scan per thousand padded prompt tokens:
over the prefill programs the trace holds (``jit__prefill_<tokens>``),
the time on device operations whose scope is ``ssm_scan`` (all 26 Mamba
layers together), over the padded tokens those programs ran. Scopes as
in ``benchmark/program_scopes_jamba.py``."""

from benchmark import program_scopes_jamba as scopes


def read(run):
    ht = scopes.of(run)
    if ht is None or not ht.prefills:
        return None
    return scopes.prefill_ns(ht, ("ssm_scan",)) / 1e6 / (
        scopes.prefill_tokens(ht) / 1e3)
