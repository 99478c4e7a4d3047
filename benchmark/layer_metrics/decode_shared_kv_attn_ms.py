"""Device time one decode step spends attending the full layer's pages:
median, over the executed ``jit__step`` modules of the trace, of the time
under ``attn_full`` (layer 17's read) and ``attn_cross`` (the seven cross
layers' reads of the same pages), each with the ``diff_combine`` that
follows it (the lambda, the difference of the two softmaxes' outputs in
float32, the norm by head): eight reads of one layer's pages. It grows
with the streams' contexts. Scopes as in
``benchmark/program_scopes_phi4flash.py``."""

from benchmark import program_scopes_phi4flash as scopes


def read(run):
    ft = scopes.of(run)
    return None if ft is None else scopes.decode_ms(ft.coarse, scopes.SHARED_KV_ATTN)
