"""Share of the traced prefill programs' device time under
``attn_window``: the window layers' attention (the windowed flash kernel
over each chunk's band, and the carried positions' part), six layers of
eight. Lower is better: a kernel that masked the band and did not skip
what lies outside it would read near three times the full layers' share.
Over the prefills that the trace holds with their ``done`` span; scopes
as in ``benchmark/program_scopes_kexaone.py``."""

from benchmark import program_scopes_kexaone as scopes


def read(run):
    kt = scopes.of(run)
    if kt is None or not kt.prefills:
        return None
    total = scopes.prefill_ns(kt)
    if total <= 0:
        return None
    return 100.0 * scopes.prefill_ns(kt, scopes.WINDOW_ATTN_PREFILL) / total
