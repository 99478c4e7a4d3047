"""Share of the traced prefill programs' device time in the sparse
layers' mixer beyond its projections: ``sparse_compress`` (a chunk's
compressed keys), ``sparse_select`` (the scores against the compressed
keys, their pooling, the top-k of every position past ``dense_len``),
``sparse_attn`` (the walk under each query's mask of chosen blocks) and
``attn`` (chunks whose every position is still dense), two layers of
eight. Lower is better. Over the prefills that the trace holds with
their ``done`` span; scopes as in ``benchmark/program_scopes_sala.py``."""

from benchmark import program_scopes_sala as scopes


def read(run):
    st = scopes.of(run)
    if st is None or not st.prefills:
        return None
    total = scopes.prefill_ns(st)
    if total <= 0:
        return None
    return 100.0 * scopes.prefill_ns(st, scopes.SPARSE_ATTN_PREFILL) / total
