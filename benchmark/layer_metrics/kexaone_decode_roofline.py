"""The kexaone decode program's share of its memory roofline: the bytes
a decode step must move (``costs_kexaone.kexaone_decode_bytes``:
attention, router, shared expert, dense MLP and head once, the held
experts that some live stream chose as their expectation, the full
layers' live pages, the live streams' rings) over the HBM peak, over the
median device time of the decode program. Bound: HBM bandwidth (819 GB/s
on a v5e). Live streams and their cached tokens are the window's means
over the steps that ran no prefill; the program itself steps all
``max_batch`` slots and streams every held expert."""

from benchmark import costs_kexaone, trace_reduce
from benchmark.program_scopes_kexaone import DECODE_MODULE, live_means


def read(run):
    if run.trace_data is None or run.peaks is None:
        return None
    if run.config.get("family") != "kexaone":
        return None
    durs = sorted(trace_reduce.module_durations_ns(
        run.trace_data, (DECODE_MODULE,)))
    live = live_means(run)
    if not durs or live is None:
        return None
    ms = durs[len(durs) // 2] / 1e6
    need = costs_kexaone.kexaone_decode_bytes(run.config, *live)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (ms / 1e3)
