"""Share of the window the host spent under ``serve/prefill`` and
``serve/prefill_chunk`` spans (a prefill program's call and the wait for
its first token), summed over every step: what ``prefill_stall_share``
infers from outside, measured where it happens. From the engine's record
of every step of the window (``benchmark/step_log.py``)."""

from benchmark import step_log


def read(run):
    recs = step_log.records(run)
    if recs is None:
        return None
    prefill_s = sum(float(r["prefill_us"]) for r in recs) / 1e6
    return 100.0 * prefill_s / run.facts["window_s"]
