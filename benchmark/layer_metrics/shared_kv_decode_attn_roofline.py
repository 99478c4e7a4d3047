"""The paged differential attention of a decode step against its
roofline: what the eight reads of the full layer's pages need
(``costs_phi4flash.shared_kv_attn_bytes``: the live positions' keys and
values once a reading layer; ``shared_kv_attn_ops``: per cached position,
reading layer and query head the product with its key's 64 values and
with the pair's 128 value lanes), at the HBM peak or the matrix unit's
peak, whichever takes longer (the bytes, by far), over the median device
time under ``attn_full`` and ``attn_cross`` with their ``diff_combine``.
Live streams' cached positions are the window's mean over the engine
steps that ran no prefill. The ragged paged kernel reads a pair of heads
a row of 128 lanes, multiplies every query head against every row of a
page and masks the other pairs': this share says what that and its grid
of cells cost."""

from benchmark import costs_phi4flash
from benchmark import program_scopes_phi4flash as scopes


def read(run):
    ft, live = scopes.of(run), scopes.live(run)
    if ft is None or live is None or run.peaks is None:
        return None
    ms = scopes.decode_ms(ft.coarse, scopes.SHARED_KV_ATTN)
    if not ms:
        return None
    need_s = max(
        costs_phi4flash.shared_kv_attn_ops(run.config, live["kv_tokens"])
        / run.peaks["bf16_flops_per_s"],
        costs_phi4flash.shared_kv_attn_bytes(run.config, live["kv_tokens"])
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s / (ms / 1e3)
