"""Trips the prefill's grouped expert product took per MoE layer and
chunk: over the traced ``serve/prefill.done`` spans that carry
``moe_slabs`` (a sarvam or kexaone engine, since PR 35), their sum over
the MoE layers times the chunks those prefills computed
(``ceil(computed_tokens / chunk)``, the chunk the program's own
``prefill_chunk`` of the padded length on the ``serve/prefill`` span of
the same ``rid``). 1.0: every layer and chunk moved one slab of rows;
above it the router sent this chip more than one and a half times its
share and the prefill paid a further pass of the held experts."""

import importlib

from benchmark import program_trace


def read(run):
    pt = program_trace.of(run)
    if pt is None:
        return None
    done = [s.stats for s in pt.spans
            if s.name == "prefill.done" and "moe_slabs" in s.stats
            and int(s.stats["computed_tokens"]) > 0]
    if not done:
        return None
    try:
        chunk_of = importlib.import_module(
            "fms_fsdp_tpu.models." + run.config["family"]).prefill_chunk
        layers = run.family.model_config(run.config).n_moe_layers
    except (ImportError, AttributeError):
        return None
    padded = {
        int(s.stats["rid"]): int(s.stats["padded_tokens"]) for s in pt.spans
        if s.name == "prefill" and "padded_tokens" in s.stats}
    trips = 0
    for d in done:
        computed = int(d["computed_tokens"])
        chunk = chunk_of(padded.get(int(d["rid"]), computed))
        trips += layers * -(-computed // chunk)
    return sum(int(d["moe_slabs"]) for d in done) / trips if trips else None
