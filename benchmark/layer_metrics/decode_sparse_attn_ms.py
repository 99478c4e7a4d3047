"""Device time one decode step spends in the sparse layers' attention:
median, over the executed ``jit__step`` modules of the trace, of the time
on device operations under ``kv_write`` (the position's key and value
into its page), ``index_write`` (the compressed key its position
completes, read back from the pages), ``sparse_select`` (each row's
compressed keys gathered through its table, scored, pooled to blocks,
the top-k) and ``sparse_attn`` (the ragged paged kernel over each row and
kv head's chosen pages), the sparse layers together. Scopes as in
``benchmark/program_scopes_sala.py``."""

from benchmark import program_scopes_sala as scopes


def read(run):
    st = scopes.of(run)
    return None if st is None else scopes.decode_ms(st, scopes.SPARSE_ATTN_DECODE)
