"""The engine's own record of every ``step()`` of the window, read from
the trace.

Since PR 37 the engine keeps a record of each ``step()``
(``fms_fsdp_tpu/obs/spans.py``: the host time under each of its spans,
why admission stopped, the pages in use, what the step prefilled and
decoded) in a ring, whether or not a profiler session runs, and hands a
running session the ring's earlier records, 64 to 256 a step, each a
zero-length ``serve/step.log`` span whose counts are the record's fields.
The drivers free the engine before any reader runs, so the trace file is
how the records of the 42 untraced seconds get here.

``records(run)`` joins them with the driver's own log of its
``engine.step()`` calls (``run.facts["steps_log"]``, seconds from the
window's start on ``time.perf_counter``, which is the clock the engine
was given, so a record's ``t`` is on it too): one record per ``step``,
those whose ``t`` lies from the start of the window's first call to the
end of its last. **A whole window or no number**: unless there is exactly
one record for each call that started inside the window, it returns
``None`` and so does every reader on it. Against a program that writes no
such span (the parent of PR 37) it finds nothing and returns ``None``.
"""

from benchmark import program_trace

SPAN = "step.log"


def one_per_step(spans):
    """The ``step.log`` spans' stats, one per ``step`` (a session is
    given a record once; a second session in the file would repeat
    some), by step."""
    by_step = {}
    for s in spans:
        if s.name == SPAN and "step" in s.stats:
            by_step.setdefault(int(s.stats["step"]), s.stats)
    return [by_step[k] for k in sorted(by_step)]


def window_records(recs, steps_log, t0, seconds):
    """-> (``recs`` that the calls of ``steps_log`` which started inside
    the window made, or ``None`` unless there is one for each; how many
    such calls)."""
    calls = [(s, e) for s, e, *_ in steps_log if s < seconds]
    if not calls:
        return None, 0
    lo, hi = t0 + calls[0][0], t0 + calls[-1][1]
    mine = [r for r in recs if lo <= float(r["t"]) <= hi]
    return (mine if len(mine) == len(calls) else None), len(calls)


def records(run):
    """The window's records by step (made at the first call and kept on
    ``run``), or ``None``."""
    if not hasattr(run, "step_log_records"):
        pt = program_trace.of(run)
        log, window = run.facts.get("steps_log"), run.facts.get("window")
        run.step_log_records = None
        if pt is not None and log and window:
            recs = one_per_step(pt.spans)
            run.step_log_records, calls = window_records(
                recs, log, window[0], run.facts["window_s"])
            print(f"step log: the trace holds {len(recs)} records, the "
                  f"window made {calls} engine steps: "
                  f"{'joined' if run.step_log_records else 'no reading'}",
                  flush=True)
    return run.step_log_records


def wall_s(recs, keep):
    """Seconds of ``step()`` over the records that ``keep`` takes."""
    return sum(float(r["wall_us"]) for r in recs if keep(r)) / 1e6


def starved_s(recs):
    """Seconds in which the engine could have taken a request nobody had
    sent: the steps whose admission stopped at an empty queue with a slot
    free, and the time from a step that left nothing queued and nothing
    live to the next (the caller waits for an arrival there)."""
    total = wall_s(
        recs, lambda r: r["admit_stopped"] == "queue_empty"
        and int(r["busy_after_admit"]) < int(r["slots"]))
    for a, b in zip(recs, recs[1:]):
        # submits come between steps only, so an empty queue at a's
        # admission is an empty queue at its end; slots change inside
        # steps only, so b's at entry are a's at its end
        if a["admit_stopped"] == "queue_empty" and int(b["busy"]) == 0:
            total += max(
                0.0, float(b["t"]) - float(a["t"]) - float(a["wall_us"]) / 1e6)
    return total
