"""Mixtral family adapter: paged-KV attention + expert-routed FFN.

The attention half is llama's paged path over mixtral's GQA shapes —
same PagedKVCache, same page accounting, same zero-page bit-parity
argument. The FFN half routes each decoded token through its top-k
experts (models/mixtral.py::_moe_token). ``moe_impl="routed"``, the
serving default, reads each expert's weights once where they lie in the
layer's stacked w1/w3/w2 and lets only the routed (row, expert) pairs
contribute; ``"dense"`` replays the training-path dense mix bit-for-bit.
A decode step is bound by the expert bytes it reads, ``min(n, E)``
expert copies a layer for ``n = max_batch * top_k`` routed pairs, so the
routed program takes one of two loop orders by that static shape
(``routed_moe_form``): ``"all_experts"`` streams every expert once over
all rows with an exactly-zero mix weight where a row did not choose it
(``n >= E``), ``"per_pair"`` runs one product per routed pair over the
expert it names (``n < E``, one or two live streams). The adapter says
which it built: ``moe_form``, the ``moe_form`` field of every
``serve/decode.dispatch`` span, and the gauge
``serve.moe_expert_reads_per_layer``. All compute the same mixture:
``"all_experts"`` is the dense arithmetic under other scopes,
``"per_pair"`` lowers to other dot-generals than the all-experts matmul
and so sits one ulp (~1e-10) off dense rather than bitwise on it.
tests/test_serving_families.py pins these facts: dense decode == jitted
dense forward walk bit-for-bit, routed == dense token-for-token with
single-ulp logits in both forms.
"""

import jax
import jax.numpy as jnp

from fms_fsdp_tpu.models.generation import sample_token
from fms_fsdp_tpu.models.mixtral import (
    mixtral_paged_decode_step,
    mixtral_prefill,
    routed_moe_form,
)
from fms_fsdp_tpu.serve.families import PagedAdapter, paged_geometry


def page_geometry(model_cfg, scfg):
    """``(page_size, block_kv, tune_how, max_pages, num_pages)`` of the
    paged cache a mixtral engine builds for these two configs: the page
    size through the kernel-tuning table (or pinned by
    ``scfg.page_size``), the pages one sequence can hold, the pool."""
    return paged_geometry(
        scfg, model_cfg.nheads, model_cfg.n_kv_heads, model_cfg.head_dim
    )


def decode_program(model_cfg, scfg, page_size: int, compute_dtype):
    """The jitted decode step of a mixtral engine: one ragged paged step
    over ``scfg.max_batch`` slots and the sampler, pools donated. A
    function of the two configs alone, so that anyone can build the
    *same* program again and read its compiled HLO (the scopes of its
    instructions: obs/scopes.py::scope_table). The traced function keeps
    the name ``_step``: the profiler's trace shows the program as
    ``jit__step``, and readers find it by that name.

    ``(params, pools, page_table, seq_lens, tokens, key) ->
    (tokens (B,) int32, logits (B, V), pools)``."""
    moe_impl = scfg.moe_impl

    def _step(params, pools, page_table, seq_lens, tokens, key):
        logits, pools = mixtral_paged_decode_step(
            params,
            pools,
            page_table,
            seq_lens,
            tokens,
            model_cfg,
            page_size=page_size,
            compute_dtype=compute_dtype,
            moe_impl=moe_impl,
        )
        tok = sample_token(
            logits, key, scfg.temperature, scfg.top_k, scfg.do_sample
        )
        return tok.astype(jnp.int32), logits, pools

    return jax.jit(_step, donate_argnums=(1,))


class MixtralAdapter(PagedAdapter):
    family = "mixtral"
    supports_handoff = True
    supports_layout = True
    _model_prefill = staticmethod(mixtral_prefill)

    def _setup(self) -> None:
        cfg, scfg = self.model_cfg, self.scfg
        self.moe_impl = moe_impl = scfg.moe_impl
        if moe_impl not in ("routed", "dense"):
            raise ValueError(
                f"unknown moe_impl {moe_impl!r}: mixtral decode supports "
                "'routed' (only the routed experts contribute, each "
                "expert's weights read once in place) or 'dense' "
                "(training-path full mixture, the strict bit-parity mode)"
            )
        # which loop the decode program runs over the experts, and the
        # expert copies it reads in each layer: static facts of the
        # program's shape, known where the program is built
        pairs, E = scfg.max_batch * cfg.top_k, cfg.num_experts
        routed = moe_impl == "routed"
        self.moe_form = routed_moe_form(pairs, E) if routed else "dense"
        self.moe_expert_reads_per_layer = min(pairs, E) if routed else E
        self._dispatch_fields = {"moe_form": self.moe_form}

        if scfg.attn_impl == "kernel":
            raise ValueError(
                "mixtral serving decodes attention through the reference "
                "gqa_attend for now: set attn_impl to 'auto' or "
                "'reference' (the ragged kernel is llama-only in v1)"
            )
        if scfg.kv_quant != "none":
            raise ValueError(
                "mixtral serving stores attn pages full-width in v1: "
                "set kv_quant='none'"
            )
        if scfg.speculator_path:
            raise ValueError(
                "mixtral serving has no speculative decode path yet: "
                "the MLPSpeculator draft/verify loop is llama-only (the "
                "verify forward has no expert-routed chunk step) — "
                "unset speculator_path"
            )
        self.attn_impl = "reference"
        # serve_layout: mesh + sharded params (attention follows the
        # llama megatron layout; expert weights keep their fsdp/tensor
        # in-expert sharding — the expert axis is absent from the
        # serving mesh, so resolve_spec replicates the E dim)
        self._init_layout(scfg)
        self._init_pages(
            int(self.params["layers"]["wq"].shape[0]),
            cfg.nheads,
            cfg.n_kv_heads,
            cfg.head_dim,
        )
        self._decode_fn = decode_program(
            cfg, scfg, self.page_size, self.compute_dtype
        )
