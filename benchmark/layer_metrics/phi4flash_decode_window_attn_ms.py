"""Device time one decode step spends in the window layers' attention:
median, over the executed ``jit__step`` modules of the trace, of the time
under ``win_write`` (the position's key and value into its slot's ring at
``t mod sliding_window``) and ``attn_window`` (the ring's 512 positions a
stream, read as four pages of the ragged paged kernel, with the
``diff_combine`` that follows), the eight window layers together. It does
not grow with the streams' contexts; ``decode_shared_kv_attn_ms`` does.
``decode_window_attn_ms`` is the k-exaone cell's: its reader builds that
family's programs. Scopes as in
``benchmark/program_scopes_phi4flash.py``."""

from benchmark import program_scopes_phi4flash as scopes


def read(run):
    ft = scopes.of(run)
    return None if ft is None else scopes.decode_ms(ft.coarse, scopes.WINDOW_ATTN)
