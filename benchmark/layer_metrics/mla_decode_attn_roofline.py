"""The decode step's attention over the latent pages against its
roofline: what it needs (``costs_sarvam.mla_decode_attn_cost``: the live
latent pages and ``W_kvb`` read once, the absorbed products'
operations), at the HBM peak or the matrix unit's peak, whichever takes
longer, over the median device time under ``latent_gather``,
``mla_absorb`` and ``attn``. Live streams and their cached tokens are the
window's means over the engine steps that ran no prefill. The program
gathers blocks of pages up to the longest live stream for every slot, so
it moves more than the live pages: this share says by how much."""

from benchmark import costs_sarvam
from benchmark import program_scopes_sarvam as scopes


def read(run):
    st = scopes.of(run)
    live = scopes.live_means(run)
    if st is None or live is None or run.peaks is None:
        return None
    ms = scopes.decode_ms(st, scopes.ATTN_CORE_DECODE)
    if not ms:
        return None
    ops, byts = costs_sarvam.mla_decode_attn_cost(run.config, *live)
    need_s = max(ops / run.peaks["bf16_flops_per_s"],
                 byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s / (ms / 1e3)
