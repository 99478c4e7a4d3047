"""Device time one decode step spends in latent attention: median, over
the executed ``jit__step`` modules of the trace, of the time on device
operations under ``mla_q``, ``mla_kv_down`` (``W_kva``, the latent's
norm, rotary), ``latent_write``, ``latent_gather``, ``mla_absorb``
(queries through ``W_kvb^K``, outputs through ``W_kvb^V``), ``attn`` and
``attn_out``, all layers together. Scopes as in
``benchmark/program_scopes_sarvam.py``."""

from benchmark import program_scopes_sarvam as scopes


def read(run):
    st = scopes.of(run)
    return None if st is None else scopes.decode_ms(st, scopes.ATTN_DECODE)
