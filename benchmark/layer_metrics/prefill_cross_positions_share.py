"""Positions the second half of the stack computed over those the first
half computed, of the traced prefills: ``cross_positions`` over
``self_positions`` of the ``serve/prefill.done`` spans the trace holds
(the adapter's own counts: whole chunks up to the prompt's end through
layers 0-16 and layer 17's key and value projection, one position a
prompt through layer 17's attention and layers 18-31). The prefill that
stops half way reads one over the positions a prompt computes, under 1%;
a program that ran every layer for every position would read 100%.
Against a program whose spans carry no such counts it returns ``None``."""

from benchmark import program_trace


def read(run):
    pt = program_trace.of(run)
    if pt is None:
        return None
    done = [s.stats for s in pt.spans
            if s.name == "prefill.done" and "self_positions" in s.stats]
    first = sum(int(s["self_positions"]) for s in done)
    if first <= 0:
        return None
    return 100.0 * sum(int(s["cross_positions"]) for s in done) / first
