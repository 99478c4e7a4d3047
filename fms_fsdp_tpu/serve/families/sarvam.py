"""Sarvam family adapter: a paged latent cache + the held share of a
sigmoid-routed expert layer (models/sarvam.py).

What a position leaves behind is one ``latent_dim``-wide row a layer
(the normed latent and the rotated shared key), whatever the head
count, so the cache is **one** pool ``(L, P, page_size, pool_width)``
with no head axis (``pool_width``: the 576 values in whole rows of 128
lanes, 640, as the chip's tiling would lay them out anyway):
``PagedKVCache`` with ``pools={"latent": ...}``, the same allocator,
page tables, LIFO eviction and recompute-on-resume as every paged family.

Decode: one ragged paged step over ``max_batch`` slots. Each layer
writes the position's latent to its page and attends in the absorbed
form (``W_kvb`` is never applied to the cache): through the ragged
paged latent kernel, each stream's own pages read where they lie
(``attn_impl`` ``"kernel"``, and ``"auto"`` on a TPU), or over blocks of
gathered pages up to the longest live stream in plain jax
(``"reference"``). The expert layer takes one of
models/mixtral.py's two routed forms over the experts held
(``routed_moe_form``); the adapter says which it built: ``moe_form``,
the ``moe_form`` field of every ``serve/decode.dispatch`` span, and the
gauge ``serve.moe_expert_reads_per_layer``.

Prefill: the prompt as a sequence, ``PREFILL_CHUNK`` positions at a
time in a loop inside its bucket's program that stops at the prompt's
length (``serve.prefill_computed_tokens`` says what ran); attention in
the expanded form, keys 192 and values 128 wide as published, made from
each latent block as it is met (``attn_form`` on ``prefill.dispatch``
says which form a program runs); the experts routed and grouped, no pair
on a held expert dropped. Beside the first token's logits and the latent
for the pages the program returns the pairs that landed on held experts,
the trips its grouped product took for them and the row tiles a product
met: ``serve.moe_pairs_held`` / ``_routed``, ``serve.moe_slabs`` and
``serve.moe_row_tiles``, all on ``serve/prefill.done``.

Not here yet (PERF.md section 7): a serving layout over chips (the
expert layer's exchange), handoff of latent pages, quantized latent
pages, speculative decode, prefix reuse, a prompt's chunks between
decode steps.
"""

import jax
import jax.numpy as jnp
import numpy as np

from fms_fsdp_tpu.models.generation import sample_token
from fms_fsdp_tpu.models.sarvam import (
    pool_width,
    prefill_attn_form,
    prefill_positions,
    sarvam_paged_decode_step,
    sarvam_prefill,
)
from fms_fsdp_tpu.serve.families import (
    HeldExpertsAdapter,
    jit_prefill,
    kernel_or_reference as resolve_attn_impl,
    paged_geometry,
    sequence_prefill_attn_impl as _prefill_attn_impl,
)


# positions a page of the latent pool holds unless ``scfg.page_size`` pins
# it: a page is one fetch of the decode kernel (128 x 640 lanes: 160 KB in
# bfloat16), and a stream wastes half a page, 64 positions of thousands
PAGE_SIZE = 128


def page_geometry(model_cfg, scfg):
    """``(page_size, max_pages, num_pages)`` of the latent cache a sarvam
    engine builds (untuned: the latent pool has no kv heads for the
    tuning table to key on; ``PAGE_SIZE`` positions a page unless
    ``scfg.page_size`` pins it)."""
    import dataclasses

    if not scfg.page_size:
        scfg = dataclasses.replace(scfg, page_size=PAGE_SIZE)
    page_size, _, _, max_pages, num_pages = paged_geometry(
        scfg, model_cfg.nheads, 1, model_cfg.latent_dim, tuned=False
    )
    return page_size, max_pages, num_pages


def decode_program(model_cfg, scfg, page_size: int, compute_dtype):
    """The jitted decode step of a sarvam engine: one ragged paged step
    over ``scfg.max_batch`` slots and the sampler, the latent pool
    donated. A function of the two configs alone (families/mixtral.py::
    decode_program says why); the traced function keeps the name
    ``_step``, so the profiler shows the program as ``jit__step``.

    ``(params, pools, page_table, seq_lens, tokens, key) ->
    (tokens (B,) int32, logits (B, V), pools)``."""
    moe_impl, attn_impl = scfg.moe_impl, resolve_attn_impl(scfg)

    def _step(params, pools, page_table, seq_lens, tokens, key):
        logits, pools = sarvam_paged_decode_step(
            params, pools, page_table, seq_lens, tokens, model_cfg,
            page_size=page_size, compute_dtype=compute_dtype,
            moe_impl=moe_impl, attn_impl=attn_impl,
        )
        tok = sample_token(
            logits, key, scfg.temperature, scfg.top_k, scfg.do_sample
        )
        return tok.astype(jnp.int32), logits, pools

    return jax.jit(_step, donate_argnums=(1,))


def prefill_program(model_cfg, scfg, p_pad: int, kv_len: int, compute_dtype):
    """The jitted prefill of one padded prompt length: ``(params, tokens
    (1, p_pad), lengths (1,)) -> (logits (1, V), latent (L, 1, kv_len,
    pool_width), pairs on held experts, the grouped product's trips, the
    row tiles it met)``. The traced function is named
    by the length: ``jit__prefill_<p_pad>`` in the profiler's trace."""
    return jit_prefill(
        p_pad, sarvam_prefill, model_cfg, compute_dtype=compute_dtype,
        kv_len=kv_len, attn_impl=_prefill_attn_impl(scfg),
        moe_impl=scfg.moe_impl,
    )


class SarvamAdapter(HeldExpertsAdapter):
    family = "sarvam"
    _pages_noun = "latent pages"

    def _setup(self) -> None:
        cfg, scfg = self.model_cfg, self.scfg
        self._init_held_experts()
        self._refuse(
            ("serve_layout", scfg.serve_layout,
             "the expert layer's exchange over chips is not built: run "
             "one chip's share (SarvamConfig.experts_held)"),
            ("kv_quant", scfg.kv_quant != "none" and scfg.kv_quant,
             "latent pages are stored full-width"),
            ("speculator_path", scfg.speculator_path,
             "the draft/verify loop is llama-only"),
        )
        self.attn_impl = resolve_attn_impl(scfg)
        from fms_fsdp_tpu.serve.kv_cache import PagedKVCache

        self.page_size, self.max_pages, num_pages = page_geometry(cfg, scfg)
        self.cache = PagedKVCache(
            cfg.nlayers,
            num_pages,
            self.page_size,
            dtype=self.compute_dtype,
            pools={"latent": (pool_width(cfg),)},
        )
        self._decode_fn = decode_program(
            cfg, scfg, self.page_size, self.compute_dtype
        )
        gauge = self.registry.gauge
        gauge("serve.latent_bytes_per_token").set(self.latent_bytes_per_token)
        # the width of the values the prefill's attention runs at: the
        # published one, nothing is padded to the keys' width
        gauge("serve.prefill_attn_value_width").set(cfg.v_head_dim)

    @property
    def latent_bytes_per_token(self) -> int:
        """What one position takes of the pool, over all layers (the
        latent in whole rows of 128 lanes: ``pool_width``)."""
        cfg = self.model_cfg
        return (
            cfg.nlayers * pool_width(cfg)
            * jnp.dtype(self.compute_dtype).itemsize
        )

    # -- prefill: one program a padded length, told the prompt's length ----

    def _prefill_key(self, p: int, p_pad: int, kv_len: int):
        return (p_pad, kv_len)

    def _build_prefill(self, key):
        return prefill_program(
            self.model_cfg, self.scfg, *key, self.compute_dtype
        )

    def _prefill_fields(self, key) -> dict:
        form = prefill_attn_form(
            self.model_cfg, _prefill_attn_impl(self.scfg), key[0]
        )
        return {"attn_form": form}

    def _call_prefill(self, fn, toks, p: int):
        # the program's counts stay on the device until read
        logits, latent, *self._program_counts = fn(
            self.params, jnp.asarray(toks), jnp.asarray([p], np.int32)
        )
        return (
            logits[0],
            {"latent": latent},
            None,
            prefill_positions(p, toks.shape[1]),
        )
