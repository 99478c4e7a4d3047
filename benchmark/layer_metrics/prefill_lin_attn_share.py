"""Share of the traced prefill programs' device time in the lightning
layers' mixer beyond its projections: ``lin_scan`` (the chunked form: a
masked product inside a chunk, the state between chunks) and ``lin_gate``
(the output norm and gate), six layers of eight. Lower is better. Over
the prefills that the trace holds with their ``done`` span; scopes as in
``benchmark/program_scopes_sala.py``."""

from benchmark import program_scopes_sala as scopes


def read(run):
    st = scopes.of(run)
    if st is None or not st.prefills:
        return None
    total = scopes.prefill_ns(st)
    if total <= 0:
        return None
    return 100.0 * scopes.prefill_ns(st, scopes.LIN_ATTN_PREFILL) / total
