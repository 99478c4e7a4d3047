"""Device time one decode step spends in the window layers' attention:
median, over the executed ``jit__step`` modules of the trace, of the time
under ``win_write`` (the position's key and value into its slot's ring at
``t mod sliding_window``) and ``attn_window`` (the ring's 128 positions a
stream, plain XLA), the window layers together. It does not grow with
the streams' contexts; ``decode_full_attn_ms`` does. Scopes as in
``benchmark/program_scopes_kexaone.py``."""

from benchmark import program_scopes_kexaone as scopes


def read(run):
    kt = scopes.of(run)
    return None if kt is None else scopes.decode_ms(
        kt, scopes.WINDOW_ATTN_DECODE)
