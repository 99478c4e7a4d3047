"""Host time under the window's ``serve/prefill`` and
``serve/prefill_chunk`` spans per thousand positions the prefill
programs **computed** (``computed_tokens`` as the adapter counted them):
the whole window's prefill cost by the work the program ran, beside
``prefill_ms_per_1k_tokens`` (tokens as sent, wall from outside). From
the engine's record of every step of the window
(``benchmark/step_log.py``)."""

from benchmark import step_log


def read(run):
    recs = step_log.records(run)
    if recs is None:
        return None
    computed = sum(int(r["computed_tokens"]) for r in recs)
    if computed <= 0:
        return None
    return sum(float(r["prefill_us"]) for r in recs) / 1e3 / (computed / 1e3)
