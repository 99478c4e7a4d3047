"""The decode step's choice and attention in the sparse layers against
their roofline: what they need (``costs_sala.sparse_decode_attn_cost``:
the live streams' compressed keys and their chosen positions' keys and
values read once a sparse layer, and per compressed key one product, per
chosen position two, a query head), at the HBM peak or the matrix unit's
peak, whichever takes longer, over the median device time under
``sparse_select`` and ``sparse_attn``. The live streams' attended
positions and scored keys are the window's means over the engine steps
that ran no prefill, reckoned from each stream's own context
(``drivers/serve_sala.py::live_choice``): what the mathematics asks, not
what the program touches (it gathers every row of a stream's table for
the scores and steps all ``max_batch`` slots)."""

from benchmark import costs_sala
from benchmark import program_scopes_sala as scopes


def read(run):
    st = scopes.of(run)
    live = scopes.live_choice(run)
    if st is None or live is None or run.peaks is None:
        return None
    ms = scopes.decode_ms(st, scopes.SPARSE_ATTN_CORE_DECODE)
    if not ms:
        return None
    ops, byts = costs_sala.sparse_decode_attn_cost(
        run.config, live["attended"], live["scored"])
    need_s = max(ops / run.peaks["bf16_flops_per_s"],
                 byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s / (ms / 1e3)
