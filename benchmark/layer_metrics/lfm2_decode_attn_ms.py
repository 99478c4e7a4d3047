"""Device time one decode step spends in the attention layers: median,
over the executed ``jit__step`` modules of the trace, of the time under
``qkv``, ``qk_norm``, ``rope``, ``kv_write`` (the position's key and
value into its page), ``kv_read`` (the reference's gather; nothing under
the kernel), ``attn_full`` (the ragged paged kernel, two heads of 64 a
row of 128 lanes) and ``attn_out``, the two attention layers together.
Scopes as in ``benchmark/program_scopes_lfm2.py``."""

from benchmark import program_scopes_lfm2 as scopes


def read(run):
    lt = scopes.of(run)
    return None if lt is None else scopes.decode_ms(lt, scopes.ATTN_DECODE)
