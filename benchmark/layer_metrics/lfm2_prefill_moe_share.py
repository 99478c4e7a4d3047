"""Share of the traced prefill programs' device time under the expert
layers' scopes: ``moe_router``, ``moe_group`` (the sort of a chunk's
pairs by expert), ``moe_experts`` (the grouped products) and
``moe_combine``, eight layers of ten. Over the prefills that the trace
holds with their ``done`` span; scopes as in
``benchmark/program_scopes_lfm2.py``."""

from benchmark import program_scopes_lfm2 as scopes


def read(run):
    lt = scopes.of(run)
    if lt is None or not lt.prefills:
        return None
    total = scopes.prefill_ns(lt)
    if total <= 0:
        return None
    return 100.0 * scopes.prefill_ns(lt, scopes.MOE) / total
