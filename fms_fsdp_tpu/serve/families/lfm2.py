"""LFM2-MoE family adapter: a window a slot for the short-convolution
layers, pages for the attention layers alone, and an expert layer with
every expert resident (models/lfm2.py).

**What a stream holds follows the kind of operator.**

- An *attention* layer keeps a key and a value for every position: pages
  of ``PagedKVCache``, whose layer axis runs over the **attention layers
  only**. This is the only thing that grows with the context, and the
  only thing ``admission_error``, ``can_admit``, ``grow``, eviction and
  ``release`` reckon with (the skeleton's page rule over ``self.cache``):
  ``kv_bytes_per_token`` bytes a position.
- A *convolution* layer can only ever read the ``conv_kernel - 1``
  positions of ``z = B * x`` before its own, so each slot keeps a
  **window** of that many a convolution layer, ``(L_conv, max_batch,
  conv_kernel - 1, D)`` (``self._state["z"]``), oldest first:
  ``conv_state_bytes_per_stream`` bytes a stream whatever its context. A
  prefill hands the prompt's last ``conv_kernel - 1`` values over
  (``prefill.write_state``, counter ``serve.conv_windows_written``)
  beside the attention layers' pages (``prefill.write_pages``); a decode
  step shifts the position's ``z`` in, for the live slots alone
  (``seq_lens > 0``, as ``serve/families/mamba.py::_mask_state``).
  Nothing is zeroed at release: the next prefill writes the slot's whole
  window, zeros where its prompt is shorter.

``cache_bytes(model_cfg, dtype)`` says both costs in one place.

Decode: one ragged step over ``max_batch`` slots. The expert layer is
models/moe_held.py's with ``held`` = every expert; which loop the step
runs over them is a fact of its shape and stands on every
``serve/decode.dispatch`` span (``moe_form``: ``all_experts`` once the
slots' routed pairs outnumber the experts, ``per_pair`` below, ``dense``
under ``moe_impl="dense"``), beside how the attention layers read their
pages (``attn_form``: the ragged paged kernel over pages of 8 kv heads of
64, or ``reference``). The program also returns, beside the tokens, the
(layer, expert) pairs that some live stream chose in the step and the
(row, choice) pairs the live streams routed: read with the tokens, after
the step has ended (``serve.moe_experts_touched``, ``serve.moe_pairs``,
and ``serve.moe_steps``, the steps so counted).

Prefill: the prompt as a sequence, ``PREFILL_CHUNK`` positions at a time
in a loop inside its program that stops at the prompt's length
(``serve.prefill_computed_tokens``), each convolution layer's last values
of ``z`` carried from chunk to chunk. A program serves every prompt up
to its length, so the adapter builds one for each doubling of the bucket
(``serve/families/__init__.py::program_len``). ``attn_form`` on
``serve/prefill.dispatch`` says what the attention layers run
(``flash_head64`` or ``einsum``), ``moe_form`` how the chunk's pairs meet
their experts (``grouped`` or ``dense``).

Not here yet (PERF.md section 7): a serving layout over chips (the expert
layer's exchange), handoff of windows and pages, quantized pages,
speculative decode, prefix reuse (a convolution layer keeps no prefix), a
prompt's chunks between decode steps, several prompts in one prefill
program.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np

from fms_fsdp_tpu.models.generation import sample_token
from fms_fsdp_tpu.models.lfm2 import (
    lfm2_paged_decode_step,
    lfm2_prefill,
    prefill_attn_form,
    prefill_positions,
)
from fms_fsdp_tpu.ops.paged_attention import packed_row_width, tile_rows
from fms_fsdp_tpu.serve.families import (
    HeldExpertsAdapter,
    block_paged_geometry as page_geometry,  # the attention layers' pages
    jit_prefill,
    kernel_or_reference as resolve_attn_impl,
    sequence_prefill_attn_impl as _prefill_attn_impl,
    slot_writer,
)


def cache_bytes(model_cfg, dtype) -> dict:
    """What a stream costs by kind of operator: ``per_token`` bytes a
    position in the attention layers' pools (K and V, every attention
    layer) and ``per_stream`` bytes a slot in the convolution layers'
    windows (``conv_kernel - 1`` positions of ``D`` values, every
    convolution layer), whatever the context."""
    size = jnp.dtype(dtype).itemsize
    return {
        "per_token": (
            len(model_cfg.attn_layers) * 2 * model_cfg.kvheads
            * model_cfg.head_dim * size
        ),
        "per_stream": (
            len(model_cfg.conv_layers) * (model_cfg.conv_kernel - 1)
            * model_cfg.emb_dim * size
        ),
    }


def window_shape(model_cfg, scfg):
    """The convolution layers' windows: (L_conv, slots, K - 1, D)."""
    return (
        len(model_cfg.conv_layers), scfg.max_batch,
        model_cfg.conv_kernel - 1, model_cfg.emb_dim,
    )


def decode_program(model_cfg, scfg, page_size: int, block_kv, compute_dtype):
    """The jitted decode step of an lfm2 engine: one ragged step over
    ``scfg.max_batch`` slots and the sampler, windows and pools donated. A
    function of the two configs alone; the traced function keeps the name
    ``_step``, so the profiler shows the program as ``jit__step``.

    ``(params, windows, pools, page_table, seq_lens, tokens, key) ->
    (tokens (B,) int32, logits (B, V), counts (2,) int32, windows,
    pools)``."""
    moe_impl, attn_impl = scfg.moe_impl, resolve_attn_impl(scfg)

    def _step(params, windows, pools, page_table, seq_lens, tokens, key):
        logits, windows, pools, counts = lfm2_paged_decode_step(
            params, windows, pools, page_table, seq_lens, tokens, model_cfg,
            page_size=page_size, compute_dtype=compute_dtype,
            moe_impl=moe_impl, attn_impl=attn_impl, block_kv=block_kv,
        )
        tok = sample_token(
            logits, key, scfg.temperature, scfg.top_k, scfg.do_sample
        )
        return tok.astype(jnp.int32), logits, counts, windows, pools

    return jax.jit(_step, donate_argnums=(1, 2))


def prefill_program(model_cfg, scfg, n: int, kv_len: int, compute_dtype):
    """The jitted prefill of prompts up to ``n`` positions, their keys
    and values in buffers of ``kv_len`` (``n`` in whole pages): ``(params,
    tokens (1, n), lengths (1,)) -> (logits (1, V), the attention layers'
    k and v as the pages hold them (L_attn, 1, kv_len * tile_rows, 128), the
    convolution layers' windows
    (L_conv, 1, K - 1, D), pairs on held experts, the grouped product's
    trips, the row tiles it met)``. The traced function is named by the length:
    ``jit__prefill_<n>`` in the profiler's trace."""
    return jit_prefill(
        n, lfm2_prefill, model_cfg, compute_dtype=compute_dtype,
        kv_len=kv_len, attn_impl=_prefill_attn_impl(scfg),
        moe_impl=scfg.moe_impl,
    )


class Lfm2Adapter(HeldExpertsAdapter):
    family = "lfm2"
    _pages_noun = "attention pages"

    def _setup(self) -> None:
        cfg, scfg = self.model_cfg, self.scfg
        self._init_held_experts()
        self._refuse(
            ("serve_layout", scfg.serve_layout,
             "the expert layer's exchange over chips is not built: run "
             "one chip (Lfm2MoeConfig.experts_held for a share)"),
            ("kv_quant", scfg.kv_quant != "none" and scfg.kv_quant,
             "windows and pages are stored full-width"),
            ("speculator_path", scfg.speculator_path,
             "the draft/verify loop is llama-only (a convolution's window "
             "cannot roll back)"),
            ("role", scfg.role != "unified" and scfg.role,
             "handoff of windows and pages is not built: run unified "
             "replicas"),
            ("prefill_chunk_tokens", scfg.prefill_chunk_tokens,
             "a prompt's chunks between decode steps are not built"),
        )
        if not cfg.conv_layers or not cfg.attn_layers:
            raise ValueError(
                "lfm2 serving keeps a window for its convolution layers and "
                "pages for its attention layers and is not built for a "
                f"stack without one of them (layer_types={cfg.layer_types})"
            )
        self.attn_impl = resolve_attn_impl(scfg)
        # how the attention layers read their pages, beside ``moe_form``
        self._dispatch_fields = dict(
            self._dispatch_fields, attn_form=self.attn_impl
        )

        from fms_fsdp_tpu.serve.kv_cache import PagedKVCache

        (
            self.page_size, self.block_kv, self.max_pages, num_pages,
        ) = page_geometry(cfg, scfg)
        # pages for the attention layers alone; a window a slot for the
        # others. A prefill program is as long as a doubling of the
        # bucket: what it writes past a stream's own pages is zeros. A
        # page is rows of 128 lanes, ``tile_rows`` a position (its kv
        # heads side by side, two of 64 a row): what the decode kernel's
        # cells read, at the published width
        rows = self.page_size * tile_rows(cfg.kvheads, cfg.head_dim)
        row = (packed_row_width(cfg.kvheads, cfg.head_dim),)
        self.cache = PagedKVCache(
            len(cfg.attn_layers), num_pages, self.page_size,
            cfg.kvheads, cfg.head_dim, dtype=self.compute_dtype,
            pools={"k": row, "v": row},
            page_rows={"k": rows, "v": rows}, scratch_tail=True,
        )
        self._state = {
            "z": jnp.zeros(window_shape(cfg, scfg), self.compute_dtype)
        }

        self._write_slot = slot_writer(1)  # one stream's windows
        program = decode_program(
            cfg, scfg, self.page_size, self.block_kv, self.compute_dtype
        )
        # the counts of the steps dispatched and not yet collected, on
        # the device: a step's are read after its tokens
        self._step_counts = collections.deque()

        def _decode(*args):
            toks, logits, counts, *state = program(*args)
            self._step_counts.append(counts)
            return (toks, logits, *state)

        self._decode_fn = _decode
        self.ssm_layers = len(cfg.conv_layers)
        cost = cache_bytes(cfg, self.compute_dtype)
        gauge = self.registry.gauge
        gauge("serve.conv_layers").set(len(cfg.conv_layers))
        gauge("serve.attn_layers").set(len(cfg.attn_layers))
        gauge("serve.conv_window_positions").set(cfg.conv_kernel - 1)
        gauge("serve.kv_bytes_per_token").set(cost["per_token"])
        gauge("serve.conv_state_bytes_per_stream").set(cost["per_stream"])

    @property
    def state_bytes_per_stream(self) -> int:
        """The convolution layers' windows of one slot: constant in the
        stream's context."""
        return cache_bytes(self.model_cfg, self.compute_dtype)["per_stream"]

    # -- prefill: one program a doubling of the bucket ---------------------

    def _prefill_key(self, p: int, p_pad: int, kv_len: int):
        n = self.program_len_of(p)
        return (n, self.cache.pages_needed(n) * self.page_size)

    def _build_prefill(self, key):
        return prefill_program(
            self.model_cfg, self.scfg, *key, self.compute_dtype
        )

    def _prefill_fields(self, key) -> dict:
        form = prefill_attn_form(
            self.model_cfg, _prefill_attn_impl(self.scfg), key[0]
        )
        moe = "grouped" if self.moe_impl == "routed" else "dense"
        return {"attn_form": form, "moe_form": moe}

    def _call_prefill(self, fn, toks, p: int):
        n = self.program_len_of(p)
        row = np.zeros((1, n), np.int32)
        row[0, : toks.shape[1]] = toks[0]
        # the program's counts stay on the device until read
        logits, kv, windows, *self._program_counts = fn(
            self.params, jnp.asarray(row), jnp.asarray([p], np.int32)
        )
        self.registry.counter("serve.conv_windows_written").add(
            len(self.model_cfg.conv_layers)
        )
        return logits[0], kv, windows, prefill_positions(p, n)

    # -- decode: the step's counts beside the skeleton's collect -----------

    def decode_collect(self, toks):
        """The tokens, and behind them (the step has ended: no wait) what
        the step counted."""
        out = super().decode_collect(toks)
        touched, pairs = map(int, np.asarray(self._step_counts.popleft()))
        count = self.registry.counter
        count("serve.moe_experts_touched").add(touched)
        count("serve.moe_pairs").add(pairs)
        count("serve.moe_steps").add()
        return out
