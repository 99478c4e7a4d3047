"""The most pages in use at the end of any step of the window, over the
pages the pool can give a stream (its ``num_pages`` less the reserved,
``pages_total`` on every record). From the engine's record of every step
of the window (``benchmark/step_log.py``)."""

from benchmark import step_log


def read(run):
    recs = step_log.records(run)
    if recs is None:
        return None
    total = int(recs[0]["pages_total"])
    if total <= 0:
        return None
    return 100.0 * max(int(r["pages_in_use"]) for r in recs) / total
