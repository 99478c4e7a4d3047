"""Device time of the attention half of one decode step: median, over the
executed ``jit__step`` modules of the trace, of the time on device
operations whose scope is ``qkv``, ``kv_write``, ``kv_gather``, ``attn``
or ``attn_out`` (all layers of the scan together). An operation's scope
is its instruction's in the decode program's compiled HLO
(``benchmark/program_trace.py``)."""

from benchmark import program_trace


def read(run):
    pt = program_trace.of(run)
    return None if pt is None else program_trace.decode_ms(
        pt, program_trace.ATTN_SCOPES)
