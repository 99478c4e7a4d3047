"""The prefill's window-layer attention against its roofline: the band's
operations (``costs_kexaone.prefill_window_attn_ops``: per position of
the prompt ``min(sliding_window, t + 1)`` keys, per pair and query head
the two products, the window layers together) at the matrix unit's peak,
over the device time under ``attn_window`` of the same prefills: the
windowed flash kernel's share of its roofline. Low by nature: a band of
128 is narrower than a block, so every block that is computed is mostly
masked, and the positions computed beyond a prompt's end are not asked
for. Reckoned from each traced prompt's own length (the ``prefill``
span), never from what the kernel touched."""

from benchmark import costs_kexaone
from benchmark import program_scopes_kexaone as scopes


def read(run):
    kt = scopes.of(run)
    if kt is None or not kt.prefills or run.peaks is None:
        return None
    ns = scopes.prefill_ns(kt, scopes.WINDOW_ATTN_PREFILL)
    if ns <= 0:
        return None
    ops = sum(
        costs_kexaone.prefill_window_attn_ops(run.config, c["prompt_tokens"])
        for _, _, c in kt.prefills)
    return 100.0 * ops / run.peaks["bf16_flops_per_s"] / (ns / 1e9)
