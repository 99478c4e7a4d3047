"""Device time one decode step spends in the gated short convolutions:
median, over the executed ``jit__step`` modules of the trace, of the time
under ``conv_in`` (``W_in`` and ``B * x``), ``short_conv`` (the taps over
window and position, the window's shift for the live slots) and
``conv_out`` (``C *`` and ``W_out``), the eight convolution layers
together. Scopes as in ``benchmark/program_scopes_lfm2.py``."""

from benchmark import program_scopes_lfm2 as scopes


def read(run):
    lt = scopes.of(run)
    return None if lt is None else scopes.decode_ms(lt, scopes.CONV)
