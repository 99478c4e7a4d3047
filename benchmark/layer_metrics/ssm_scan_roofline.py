"""The selective scan's share of its memory roofline in the traced
prefill programs: the bytes the scans must move
(``costs_jamba.selective_scan_bytes``: ``u``, ``dt``, ``B``, ``C`` in and
``y`` out per position, the state once a call; never the (S, d_inner, N)
history) over the HBM peak, over the device time under the ``ssm_scan``
scope. Bound: HBM bandwidth (819 GB/s on a v5e); the scan has 7 vector
operations and an exponential per (position, channel, state) on 12 bytes
a (position, channel), so the vector unit, not the memory, is what it
waits for, and this share says by how much."""

from benchmark import costs_jamba
from benchmark import program_scopes_jamba as scopes


def read(run):
    ht = scopes.of(run)
    if ht is None or not ht.prefills or run.peaks is None:
        return None
    ns = scopes.prefill_ns(ht, ("ssm_scan",))
    if ns <= 0:
        return None
    need = costs_jamba.selective_scan_bytes(
        run.config, scopes.prefill_tokens(ht), calls=len(ht.prefills))
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (ns / 1e9)
