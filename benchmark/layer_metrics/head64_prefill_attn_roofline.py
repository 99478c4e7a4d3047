"""The prefill's attention at heads of 64 against its roofline: the
causal products' operations (``costs_lfm2.prefill_attn_ops``: per (query,
key) pair of the triangle and query head the two products over 64 values,
the attention layers together) at the matrix unit's peak, over the device
time under ``attn_full`` of the same prefills: the flash kernel's share of
its roofline (its two calls a layer and chunk, the merge of their
partials). Low by nature: a contraction over 64 values fills half of the
matrix unit's depth, the diagonal blocks are half masked, and the
positions computed beyond a prompt's end are not asked for. Reckoned from
each traced prompt's own length (the ``prefill`` span), never from what
the kernel touched."""

from benchmark import costs_lfm2
from benchmark import program_scopes_lfm2 as scopes


def read(run):
    lt = scopes.of(run)
    if lt is None or not lt.prefills or run.peaks is None:
        return None
    ns = scopes.prefill_ns(lt, ("attn_full",))
    if ns <= 0:
        return None
    ops = sum(
        costs_lfm2.prefill_attn_ops(run.config, c["prompt_tokens"])
        for _, _, c in lt.prefills)
    return 100.0 * ops / run.peaks["bf16_flops_per_s"] / (ns / 1e9)
