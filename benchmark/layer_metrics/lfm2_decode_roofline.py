"""The lfm2 decode program's share of its memory roofline: the bytes a
decode step must move (``costs_lfm2.lfm2_decode_bytes``: operators, dense
MLPs, routers and the tied head once, the experts that some live stream
chose as their expectation, the attention layers' live pages, the live
streams' windows) over the HBM peak, over the median device time of the
decode program. Bound: HBM bandwidth (819 GB/s on a v5e). Live streams
and their cached tokens are the window's means over the steps that ran no
prefill; the program itself steps all ``max_batch`` slots and streams
every expert."""

from benchmark import costs_lfm2
from benchmark import program_scopes_lfm2 as scopes


def read(run):
    if run.peaks is None or run.config.get("family") != "lfm2":
        return None
    ms = scopes.decode_step_ms(run)
    live = scopes.live_means(run)
    if not ms or live is None:
        return None
    need = costs_lfm2.lfm2_decode_bytes(run.config, *live)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (ms / 1e3)
