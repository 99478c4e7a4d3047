"""The decode step's attention over pages of 8 kv heads of 64 against its
roofline: what it needs (``costs_lfm2.decode_attn_cost``: the live
positions' keys and values read once an attention layer, and per cached
position and query head the two products over the head's own 64 values),
at the HBM peak or the matrix unit's peak, whichever takes longer (the
bytes, by far), over the median device time under ``kv_read`` and
``attn_full``. Live streams' cached tokens are the window's mean over the
engine steps that ran no prefill. The kernel reads two heads a row of
128 lanes, multiplies every query head against every row of a page and
masks the others': this share says what that and its grid of cells
cost."""

from benchmark import costs_lfm2
from benchmark import program_scopes_lfm2 as scopes


def read(run):
    lt = scopes.of(run)
    live = scopes.live_means(run)
    if lt is None or live is None or run.peaks is None:
        return None
    ms = scopes.decode_ms(lt, scopes.ATTN_CORE)
    if not ms:
        return None
    ops, byts = costs_lfm2.decode_attn_cost(run.config, live[1])
    need_s = max(ops / run.peaks["bf16_flops_per_s"],
                 byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s / (ms / 1e3)
