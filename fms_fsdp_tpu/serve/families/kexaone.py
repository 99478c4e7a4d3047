"""K-EXAONE family adapter: a cache of each kind of attention layer + the
held share of a sigmoid-routed expert layer (models/kexaone.py).

**What a stream holds follows the kind of layer.**

- A *full* layer keeps a key and a value for every position: pages of
  ``PagedKVCache``, whose layer axis runs over the **full layers only**.
  This is the only thing that grows with the context, and the only thing
  ``admission_error``, ``can_admit``, ``grow``, eviction and ``release``
  reckon with (the skeleton's page rule over ``self.cache``):
  ``kv_bytes_per_token`` bytes a position.
- A *window* layer can only ever read its last ``sliding_window``
  positions, so each slot keeps a **ring** of that many keys and values a
  window layer, ``(L_window, max_batch, sliding_window, Nkv, H)`` for K
  and for V (``self._state``), written at ``t mod sliding_window``:
  ``window_state_bytes_per_stream`` bytes a stream whatever its context,
  a fixed cost of a slot like the hybrids' recurrent slab. A prefill
  hands the prompt's last ``sliding_window`` positions over in ring order
  (``prefill.write_state``) beside the full layers' pages
  (``prefill.write_pages``); nothing is zeroed at release, because the
  next prefill writes the slot's whole ring and a decode step masks the
  entries its stream has not written yet.

``cache_bytes(model_cfg, dtype)`` says both costs in one place.

Decode: one ragged step over ``max_batch`` slots. A window layer attends
its ring, 128 positions a stream, in plain jax; a full layer reads each
stream's own pages where they lie through the ragged paged kernel
(``ops/paged_attention.py::paged_attention_kernel``: ``attn_impl``
``"kernel"``, and ``"auto"`` on a TPU) or gathers them (``"reference"``);
``attn_form`` on every ``serve/decode.dispatch`` span says which. The
expert layer is models/moe_held.py's, as the sarvam family runs it
(``moe_form``, the gauge ``serve.moe_expert_reads_per_layer``).

Prefill: the prompt as a sequence, ``PREFILL_CHUNK`` positions at a time
in a loop inside its bucket's program that stops at the prompt's length
(``serve.prefill_computed_tokens``); a window layer runs the windowed
flash kernel over the chunk's band and carries its last
``sliding_window`` positions to the next chunk, a full layer walks its
earlier blocks; ``attn_form`` on ``prefill.dispatch`` says which forms a
program runs. The pairs that landed on held experts, the grouped
product's trips and the row tiles it met are counted as the sarvam
adapter counts them (``serve.moe_pairs_held`` / ``_routed``,
``serve.moe_slabs``, ``serve.moe_row_tiles``).

Not here yet (PERF.md section 7): a serving layout over chips (the expert
layer's exchange), handoff of rings and pages, quantized pages,
speculative decode (the model's multi-token-prediction module is the
draft head it would take), prefix reuse (a window layer keeps no prefix),
a prompt's chunks between decode steps.
"""

import jax
import jax.numpy as jnp
import numpy as np

from fms_fsdp_tpu.models.generation import sample_token
from fms_fsdp_tpu.models.kexaone import (
    kexaone_paged_decode_step,
    kexaone_prefill,
    prefill_attn_form,
    prefill_positions,
)
from fms_fsdp_tpu.serve.families import (
    HeldExpertsAdapter,
    block_paged_geometry as page_geometry,  # the full layers' pages
    jit_prefill,
    kernel_or_reference as resolve_attn_impl,
    sequence_prefill_attn_impl as _prefill_attn_impl,
    slot_writer,
)


def cache_bytes(model_cfg, dtype) -> dict:
    """What a stream costs by layer kind: ``per_token`` bytes a position
    in the full layers' pools (K and V, every full layer) and
    ``per_stream`` bytes a slot in the window layers' rings (K and V,
    ``sliding_window`` positions, every window layer), whatever the
    context."""
    row = 2 * model_cfg.kvheads * model_cfg.head_dim * jnp.dtype(dtype).itemsize
    return {
        "per_token": len(model_cfg.full_layers) * row,
        "per_stream": (
            len(model_cfg.window_layers) * model_cfg.sliding_window * row
        ),
    }


def ring_shape(model_cfg, scfg):
    """The window layers' rings, K or V: (L_window, slots, window, Nkv, H)."""
    return (
        len(model_cfg.window_layers), scfg.max_batch,
        model_cfg.sliding_window, model_cfg.kvheads, model_cfg.head_dim,
    )


def decode_program(model_cfg, scfg, page_size: int, block_kv, compute_dtype):
    """The jitted decode step of a K-EXAONE engine: one ragged step over
    ``scfg.max_batch`` slots and the sampler, rings and pools donated. A
    function of the two configs alone; the traced function keeps the name
    ``_step``, so the profiler shows the program as ``jit__step``.

    ``(params, ring, pools, page_table, seq_lens, tokens, key) -> (tokens
    (B,) int32, logits (B, V), ring, pools)``."""
    moe_impl, attn_impl = scfg.moe_impl, resolve_attn_impl(scfg)

    def _step(params, ring, pools, page_table, seq_lens, tokens, key):
        logits, ring, pools = kexaone_paged_decode_step(
            params, ring, pools, page_table, seq_lens, tokens, model_cfg,
            page_size=page_size, compute_dtype=compute_dtype,
            moe_impl=moe_impl, attn_impl=attn_impl, block_kv=block_kv,
        )
        tok = sample_token(
            logits, key, scfg.temperature, scfg.top_k, scfg.do_sample
        )
        return tok.astype(jnp.int32), logits, ring, pools

    return jax.jit(_step, donate_argnums=(1, 2))


def prefill_program(model_cfg, scfg, p_pad: int, kv_len: int, compute_dtype):
    """The jitted prefill of one padded prompt length: ``(params, tokens
    (1, p_pad), lengths (1,)) -> (logits (1, V), the full layers' k and v
    (L_full, 1, kv_len, Nkv, H), the window layers' rings (L_window, 1,
    window, Nkv, H), pairs on held experts, the grouped product's trips,
    the row tiles it met)``. The traced function is named by the length:
    ``jit__prefill_<p_pad>`` in the profiler's trace."""
    return jit_prefill(
        p_pad, kexaone_prefill, model_cfg, compute_dtype=compute_dtype,
        kv_len=kv_len, attn_impl=_prefill_attn_impl(scfg),
        moe_impl=scfg.moe_impl,
    )


class KExaoneAdapter(HeldExpertsAdapter):
    family = "kexaone"
    _pages_noun = "full-attention pages"

    def _setup(self) -> None:
        cfg, scfg = self.model_cfg, self.scfg
        self._init_held_experts()
        self._refuse(
            ("serve_layout", scfg.serve_layout,
             "the expert layer's exchange over chips is not built: run "
             "one chip's share (KExaoneConfig.experts_held)"),
            ("kv_quant", scfg.kv_quant != "none" and scfg.kv_quant,
             "rings and pages are stored full-width"),
            ("speculator_path", scfg.speculator_path,
             "the draft/verify loop is llama-only (the model's "
             "multi-token-prediction module is not built)"),
        )
        if not cfg.window_layers or not cfg.full_layers:
            raise ValueError(
                "kexaone serving keeps a ring for its window layers and "
                "pages for its full layers and is not built for a stack "
                f"without one of them (layer_types={cfg.layer_types})"
            )
        self.attn_impl = resolve_attn_impl(scfg)
        # how the full layers read their pages, beside ``moe_form``
        self._dispatch_fields = dict(
            self._dispatch_fields, attn_form=self.attn_impl
        )

        from fms_fsdp_tpu.serve.kv_cache import PagedKVCache

        (
            self.page_size, self.block_kv, self.max_pages, num_pages,
        ) = page_geometry(cfg, scfg)
        # pages for the full layers alone; a ring a slot for the others
        self.cache = PagedKVCache(
            len(cfg.full_layers), num_pages, self.page_size,
            cfg.kvheads, cfg.head_dim, dtype=self.compute_dtype,
        )
        self._state = {
            name: jnp.zeros(ring_shape(cfg, scfg), self.compute_dtype)
            for name in ("k", "v")
        }

        self._write_slot = slot_writer(1)  # one stream's rings
        self._decode_fn = decode_program(
            cfg, scfg, self.page_size, self.block_kv, self.compute_dtype
        )
        cost = cache_bytes(cfg, self.compute_dtype)
        gauge = self.registry.gauge
        gauge("serve.window_layers").set(len(cfg.window_layers))
        gauge("serve.full_layers").set(len(cfg.full_layers))
        gauge("serve.window_positions").set(cfg.sliding_window)
        gauge("serve.kv_bytes_per_token").set(cost["per_token"])
        gauge("serve.window_state_bytes_per_stream").set(cost["per_stream"])

    @property
    def state_bytes_per_stream(self) -> int:
        """The window layers' rings of one slot: constant in the
        stream's context."""
        return cache_bytes(self.model_cfg, self.compute_dtype)["per_stream"]

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
        logits, kv, ring, *self._program_counts = fn(
            self.params, jnp.asarray(toks), jnp.asarray([p], np.int32)
        )
        return logits[0], kv, ring, prefill_positions(p, toks.shape[1])
