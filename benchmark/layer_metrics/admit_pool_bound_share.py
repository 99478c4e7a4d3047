"""Share of the window spent in steps whose admission loop stopped
because the head of the queue did not fit the pool's free pages
(``admit_stopped == "no_pages"``): the pool binds there, not the slots.
From the engine's record of every step of the window
(``benchmark/step_log.py``)."""

from benchmark import step_log


def read(run):
    recs = step_log.records(run)
    if recs is None:
        return None
    bound = step_log.wall_s(recs, lambda r: r["admit_stopped"] == "no_pages")
    return 100.0 * bound / run.facts["window_s"]
