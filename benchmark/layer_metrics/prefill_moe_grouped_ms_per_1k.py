"""Device time of the prefill's grouped expert product per thousand
positions **computed**: over the prefill programs the trace holds with
their ``done`` span, the time under ``moe_group`` (the sort of the
chunk's pairs by held expert), ``moe_experts`` (the grouped matmuls) and
``moe_combine``, the five MoE layers together, over the positions those
programs computed as the program itself counted them
(``serve/prefill.done``: ``computed_tokens``), not the bucket's. Scopes
and counts as in ``benchmark/program_scopes_sarvam.py``."""

from benchmark import program_scopes_sarvam as scopes


def read(run):
    st = scopes.of(run)
    if st is None or not st.prefills:
        return None
    computed = scopes.prefill_counts(st)["computed_tokens"]
    if computed <= 0:
        return None
    return scopes.prefill_ns(st, scopes.MOE_GROUPED) / 1e6 / (computed / 1e3)
