"""Share of the window in which the engine could have taken a request
that nobody had sent: the wall of the steps whose admission loop stopped
at an empty queue with a slot free, plus the time between a step that
left no request live or queued and the next, over the window's seconds.
Near 0 the cell reads the engine; near 100 it reads its own offer, and a
faster engine changes nothing in it. From the engine's record of every
step of the window (``benchmark/step_log.py``)."""

from benchmark import step_log


def read(run):
    recs = step_log.records(run)
    if recs is None:
        return None
    return 100.0 * step_log.starved_s(recs) / run.facts["window_s"]
