"""The minicpm_sala decode program's share of its memory roofline: the
bytes a decode step must move (``costs_sala.sala_decode_bytes``: every
weight once, the live streams' chosen positions' keys and values and the
compressed keys they score, their lightning states read and written)
over the HBM peak, over the median device time of the decode program.
Bound: HBM bandwidth (819 GB/s on a v5e). The live streams' numbers are
the window's means over the steps that ran no prefill
(``drivers/serve_sala.py::live_choice``); the program itself steps all
``max_batch`` slots."""

from benchmark import costs_sala, trace_reduce
from benchmark.program_scopes_sala import DECODE_MODULE, live_choice


def read(run):
    if run.trace_data is None or run.peaks is None:
        return None
    if run.config.get("family") != "minicpm_sala":
        return None
    durs = sorted(trace_reduce.module_durations_ns(
        run.trace_data, (DECODE_MODULE,)))
    live = live_choice(run)
    if not durs or live is None:
        return None
    ms = durs[len(durs) // 2] / 1e6
    need = costs_sala.sala_decode_bytes(
        run.config, live["streams"], live["attended"], live["scored"])
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (ms / 1e3)
