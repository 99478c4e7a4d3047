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

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fms_fsdp_tpu.models.generation import sample_token
from fms_fsdp_tpu.models.mixtral import (
    mixtral_paged_decode_step,
    mixtral_prefill,
    routed_moe_form,
)
from fms_fsdp_tpu.obs.spans import span
from fms_fsdp_tpu.serve.families import FamilyAdapter
from fms_fsdp_tpu.serve.kv_cache import RESERVED_PAGES, PagedKVCache


def page_geometry(model_cfg, scfg):
    """``(page_size, block_kv, tune_how, max_pages, num_pages)`` of the
    paged cache a mixtral engine builds for these two configs: the page
    size through the kernel-tuning table (or pinned by
    ``scfg.page_size``), the pages one sequence can hold, the pool."""
    from fms_fsdp_tpu.tune.lookup import resolve_paged_decode

    page_size, block_kv, tune_how = resolve_paged_decode(
        scfg.max_batch,
        model_cfg.nheads,
        model_cfg.n_kv_heads,
        model_cfg.head_dim,
        scfg.max_seq_len,
        scfg.compute_dtype,
        requested_page_size=scfg.page_size or None,
    )
    assert scfg.max_seq_len % page_size == 0, (scfg.max_seq_len, page_size)
    max_pages = scfg.max_seq_len // page_size
    num_pages = scfg.num_pages or (
        scfg.max_batch * max_pages + RESERVED_PAGES
    )
    return page_size, block_kv, tune_how, max_pages, num_pages


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
    moe_impl = getattr(scfg, "moe_impl", "routed")

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


class MixtralAdapter(FamilyAdapter):
    family = "mixtral"
    supports_handoff = True
    supports_layout = True

    def __init__(self, params, model_cfg, scfg, compute_dtype=None):
        from fms_fsdp_tpu.serve.engine import _DTYPES

        self.params = params
        self.model_cfg = model_cfg
        self.scfg = scfg
        self.compute_dtype = compute_dtype or _DTYPES[scfg.compute_dtype]
        self.moe_impl = moe_impl = getattr(scfg, "moe_impl", "routed")
        if moe_impl not in ("routed", "dense"):
            raise ValueError(
                f"unknown moe_impl {moe_impl!r}: mixtral decode supports "
                "'routed' (only the routed experts contribute, each "
                "expert's weights read once in place) or 'dense' "
                "(training-path full mixture, the strict bit-parity mode)"
            )
        cfg = model_cfg
        # which loop the decode program runs over the experts, and the
        # expert copies it reads in each layer: static facts of the
        # program's shape, known where the program is built
        pairs, E = scfg.max_batch * cfg.top_k, cfg.num_experts
        routed = moe_impl == "routed"
        self.moe_form = routed_moe_form(pairs, E) if routed else "dense"
        self.moe_expert_reads_per_layer = min(pairs, E) if routed else E

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
        if getattr(scfg, "speculator_path", ""):
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
        params = self.params

        nlayers = int(params["layers"]["wq"].shape[0])
        (
            page_size,
            self.block_kv,
            self.tune_how,
            self.max_pages,
            num_pages,
        ) = page_geometry(cfg, scfg)
        self.page_size = page_size
        self.cache = PagedKVCache(
            nlayers,
            num_pages,
            page_size,
            cfg.n_kv_heads,
            cfg.head_dim,
            dtype=self.compute_dtype,
            quant="none",
            shardings=self._pool_shardings(
                (nlayers, num_pages, page_size, cfg.n_kv_heads,
                 cfg.head_dim)
            ),
        )
        self._prefill_cache: Dict = {}
        self._table_key = None
        self._table_dev = None
        self._decode_fn = decode_program(
            cfg, scfg, page_size, self.compute_dtype
        )

    # -- capacity (same page math as llama) --------------------------------

    def _padded(self, n: int) -> int:
        return self._padded_len(n, self.scfg.prefill_bucket)

    def admission_error(self, prompt_len: int, max_new: int) -> Optional[str]:
        worst = self._padded(prompt_len + max_new - 1) + 1
        need = self.cache.pages_needed(worst)
        total = self.cache.num_pages - RESERVED_PAGES
        if need > total:
            return (
                f"request needs up to {need} pages but the pool holds "
                f"{total}; raise num_pages or shrink "
                f"prompt/max_new_tokens"
            )
        return None

    def can_admit(self, rid: int, prompt_len: int) -> bool:
        return self.cache.can_ensure(rid, self._padded(prompt_len) + 1)

    def grow(self, rid: int, n_tokens: int) -> bool:
        return self.cache.ensure(rid, n_tokens)

    def release(self, rid: int, slot: int) -> None:
        self.cache.free(rid)

    # -- prefill -----------------------------------------------------------

    def _get_prefill(self, p_len: int, s_pad: int, full_logits: bool):
        key = (p_len, s_pad, full_logits)
        fn = self._prefill_cache.get(key)
        if fn is None:
            self.prefill_programs_built += 1
            fn = jax.jit(
                partial(
                    mixtral_prefill,
                    cfg=self.model_cfg,
                    max_seq_len=s_pad,
                    compute_dtype=self.compute_dtype,
                    full_logits=full_logits,
                )
            )
            self._prefill_cache[key] = fn
        return fn

    def prefill(self, rid: int, slot: int, prompt):
        p = len(prompt)
        p_pad = self._padded(p)
        s_pad = self.cache.pages_needed(p_pad) * self.page_size
        ok = self.cache.ensure(rid, p_pad)
        assert ok, "admission checked capacity; ensure cannot fail here"
        full_logits = p_pad != p
        built = self.prefill_programs_built
        fn = self._get_prefill(p_pad, s_pad, full_logits)
        with span(
            "prefill.dispatch",
            rid=rid,
            built=self.prefill_programs_built - built,
        ):
            toks = np.zeros((1, p_pad), np.int32)
            toks[0, :p] = prompt
            logits, _, kv = fn(self.params, self._dev(toks))
            self.prefill_computed_tokens += p_pad
        with span("prefill.write_pages", rid=rid):
            self.cache.write_prompt(rid, kv["k"][:, 0], kv["v"][:, 0])
        row = logits[0, p - 1] if full_logits else logits[0, 0]
        return np.asarray(row) if self.mesh is not None else row

    # -- decode ------------------------------------------------------------

    def decode(self, slot_rids, lens, tokens, key):
        self._upload_table(slot_rids)
        # the jitted call returns before the device ends; the read of the
        # sampled tokens is what waits for it
        with span("decode.dispatch", moe_form=self.moe_form):
            toks, logits, pools = self._decode_fn(
                self.params,
                self.cache.pools,
                self._table_dev,
                self._dev(lens),
                self._dev(tokens),
                self._dev(key),
            )
            self.cache.pools = pools
        with span("decode.wait"):
            toks = np.asarray(toks)
        return toks, logits
