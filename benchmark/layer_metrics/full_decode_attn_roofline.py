"""The decode step's attention over the full layers' pages against its
roofline: what it needs (``costs_kexaone.full_decode_attn_cost``: the
live positions' keys and values read once a full layer, and per cached
position and query head the two products), at the HBM peak or the matrix
unit's peak, whichever takes longer, over the median device time under
``kv_read`` and ``attn_full``: the ragged paged kernel's share of its
roofline. Live streams' cached tokens are the window's mean over the
engine steps that ran no prefill. The kernel multiplies every query head
against every kv head's rows of a page and masks the others' (a block may
not take one head out of the minor tile), eight times the products asked:
this share says what that and its grid of cells cost."""

from benchmark import costs_kexaone
from benchmark import program_scopes_kexaone as scopes


def read(run):
    kt = scopes.of(run)
    live = scopes.live_means(run)
    if kt is None or live is None or run.peaks is None:
        return None
    ms = scopes.decode_ms(kt, scopes.FULL_ATTN_CORE_DECODE)
    if not ms:
        return None
    ops, byts = costs_kexaone.full_decode_attn_cost(run.config, live[1])
    need_s = max(ops / run.peaks["bf16_flops_per_s"],
                 byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s / (ms / 1e3)
