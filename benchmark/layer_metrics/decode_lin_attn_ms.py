"""Device time one decode step spends in the lightning layers' mixer:
median, over the executed ``jit__step`` modules of the trace, of the time
under ``lin_step`` (every slot's float32 state decayed, given the
position's ``k v^T`` and read by its query: the state read and written
whole) and ``lin_gate`` (the output norm and gate), the lightning layers
together. Scopes as in ``benchmark/program_scopes_sala.py``."""

from benchmark import program_scopes_sala as scopes


def read(run):
    st = scopes.of(run)
    return None if st is None else scopes.decode_ms(st, scopes.LIN_ATTN_DECODE)
