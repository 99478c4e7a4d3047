"""The phi4flash decode program's share of its memory roofline: the bytes
a decode step must move (``costs_phi4flash.decode_bytes``: every weight
once, the live streams' ring entries of the eight window layers, their
slabs read and written, and the full layer's live pages **eight times**,
once a layer that reads them) over the HBM peak, over the median device
time of the decode program. Bound: HBM bandwidth (819 GB/s on a v5e).
Live streams, their cached positions and their ring entries are the
window's means over the steps that ran no prefill, each stream's context
from its request's own record (``drivers/serve_phi4flash.py::
live_contexts``); the program itself steps all ``max_batch`` slots."""

from benchmark import costs_phi4flash
from benchmark import program_scopes_phi4flash as scopes


def read(run):
    if run.peaks is None or run.config.get("family") != "phi4flash":
        return None
    ms, live = scopes.decode_step_ms(run), scopes.live(run)
    if not ms or live is None:
        return None
    need = costs_phi4flash.decode_bytes(
        run.config, live["streams"], live["kv_tokens"], live["ring_positions"])
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (ms / 1e3)
