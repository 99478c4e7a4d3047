"""The prefill's lightning-attention scan against its roofline: what the
chunked form needs (``costs_sala.lin_scan_cost``: inside a chunk of 256
every causal pair's two products, per position the state read and
written, a head and lightning layer; or q, k, v in, the output out and
the state a chunk) at the matrix unit's peak or the HBM peak, whichever
takes longer, over the device time under ``lin_scan`` of the same
prefills, per position computed as the program counted it."""

from benchmark import costs_sala
from benchmark import program_scopes_sala as scopes


def read(run):
    st = scopes.of(run)
    if st is None or not st.prefills or run.peaks is None:
        return None
    ns = scopes.prefill_ns(st, scopes.LIN_SCAN_PREFILL)
    if ns <= 0:
        return None
    need_s = 0.0
    for _, _, counts in st.prefills:
        ops, byts = costs_sala.lin_scan_cost(
            run.config, counts["computed_tokens"])
        need_s += max(ops / run.peaks["bf16_flops_per_s"],
                      byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s / (ns / 1e9)
