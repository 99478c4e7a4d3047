"""The prefill's grouped expert product against its roofline: what it
needs (``costs_sarvam.moe_grouped_cost``: the operations of the pairs
that landed on held experts, **as the program counted them**, and the
expert bytes that the trips of the prefill's loop read), at the matrix
unit's peak or the HBM peak, whichever takes longer, over the device
time under ``moe_group``, ``moe_experts`` and ``moe_combine`` of the same
prefills. At 2048 positions a trip each held expert sees about 128 rows,
under the v5e's ridge of 240, so the bytes bound it."""

from benchmark import costs_sarvam
from benchmark import program_scopes_sarvam as scopes


def read(run):
    st = scopes.of(run)
    if st is None or not st.prefills or run.peaks is None:
        return None
    ns = scopes.prefill_ns(st, scopes.MOE_GROUPED)
    counts = scopes.prefill_counts(st)
    if ns <= 0 or not counts["chunks"]:
        return None
    ops, byts = costs_sarvam.moe_grouped_cost(
        run.config, counts["moe_pairs_held"], counts["chunks"])
    need_s = max(ops / run.peaks["bf16_flops_per_s"],
                 byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s / (ns / 1e9)
