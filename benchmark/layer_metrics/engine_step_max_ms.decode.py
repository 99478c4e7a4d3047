"""The longest step of the window that decoded and computed no prefill
position (``computed_tokens == 0`` and ``live > 0``), by the engine's
own clock around ``step()``: a decode step's length in a sound window,
seconds in one that stood still. From the engine's record of every step
of the window (``benchmark/step_log.py``)."""

from benchmark import step_log


def read(run):
    recs = step_log.records(run)
    if recs is None:
        return None
    walls = [
        float(r["wall_us"]) for r in recs
        if int(r["computed_tokens"]) == 0 and int(r["live"]) > 0]
    return max(walls) / 1e3 if walls else None
