"""The prefill's choice and attention in the sparse layers against
their roofline: what they need (``costs_sala.sparse_prefill_attn_cost``:
per position its attended positions' two products a query head, all of
them up to ``dense_len`` and the chosen blocks' after, and one product a
compressed key where it chooses; or a chunk's operands once) at the
matrix unit's peak or the HBM peak, whichever takes longer, over the
device time under ``sparse_select``, ``sparse_attn`` and ``attn`` of the
same prefills. Per position **computed**, as the program counted it
(``computed_tokens`` on ``serve/prefill.done``), not per position of the
program's length. Low by nature while the walk multiplies every block of
the context and masks the unchosen (PERF.md section 7)."""

from benchmark import costs_sala
from benchmark import program_scopes_sala as scopes


def read(run):
    st = scopes.of(run)
    if st is None or not st.prefills or run.peaks is None or not st.chunk:
        return None
    ns = scopes.prefill_ns(st, scopes.SPARSE_ATTN_CORE_PREFILL)
    if ns <= 0:
        return None
    need_s = 0.0
    for _, _, counts in st.prefills:
        ops, byts = costs_sala.sparse_prefill_attn_cost(
            run.config, counts["computed_tokens"], st.chunk)
        need_s += max(ops / run.peaks["bf16_flops_per_s"],
                      byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s / (ns / 1e9)
