"""Share of the traced prefill programs' device time under the attention
scopes (``mla_q``, ``mla_kv_down``, ``latent_write``, ``mla_expand``: the
keys and values of each latent block made as it is met, ``attn``: the
flash kernel over the chunk's own block and each earlier one,
``attn_out``): what of a prefill latent attention is, the rest being the
routed and shared experts, the dense MLP, norms and the head. Over the
prefills that the trace holds with their ``done`` span; scopes as in
``benchmark/program_scopes_sarvam.py``."""

from benchmark import program_scopes_sarvam as scopes


def read(run):
    st = scopes.of(run)
    if st is None or not st.prefills:
        return None
    total = scopes.prefill_ns(st)
    if total <= 0:
        return None
    return 100.0 * scopes.prefill_ns(st, scopes.ATTN_PREFILL) / total
