"""Phi-4-mini-flash family adapter: pages that one layer writes and eight
layers read, beside a ring a window layer and a slab a Mamba layer
(models/phi4flash.py).

**What a stream holds follows the kind of layer**, and this family has
all three kinds of state the repo has, in one adapter:

- The *full* layer (``cfg.full_layer``) keeps a key and a value for every
  position: pages of ``PagedKVCache``, declared over **one layer**. The
  cross layers behind it have no key or value weights and read these same
  pages, so a position costs ``kv_bytes_per_position`` bytes once
  whatever the depth of the second half. This is the only thing that
  grows with the context, and the only thing ``admission_error``,
  ``can_admit``, ``grow``, eviction and ``release`` reckon with (the
  skeleton's page rule over ``self.cache``). A page is rows of ``2
  head_dim`` lanes, a pair of kv heads side by side, ``kvheads / 2`` rows
  a position: what the decode kernel's cells read.
- A *window* layer keeps a **ring** of ``sliding_window`` keys and values
  a slot (``_state["ring_k"]``, ``["ring_v"]``: ``(L_window, max_batch,
  sliding_window * kvheads / 2, 2 head_dim)``, rows as a page's), written
  at ``t mod sliding_window``.
- A *Mamba* layer keeps its **slab** a slot (``_state["conv"]``:
  ``(L_mamba, max_batch, d_conv - 1, d_inner)``; ``["ssd"]``: ``(L_mamba,
  max_batch, d_state, d_inner)`` float32).
- The gated memory units and the cross layers keep **nothing**.

Ring and slab are ``state_bytes_per_stream`` bytes a slot whatever the
context. A prefill hands all of it over in one write
(``prefill.write_state``, one ``slot_writer`` over the four arrays)
beside the full layer's pages (``prefill.write_pages``); nothing is
zeroed at release, because the next prefill writes the slot's whole ring
and slab, and a decode step masks the ring's entries its stream has not
written yet and leaves a dead slot's slab as it was.
``cache_bytes(model_cfg, dtype)`` says both costs in one place.

Decode: one ragged step over ``max_batch`` slots; ``attn_form`` on every
``serve/decode.dispatch`` span says how the full layer's pages are read
(the ragged paged kernel, or ``reference``) and ``ssm_form`` how a Mamba
layer's scan state is stepped (``kernel``: one pass over the slab in
place, ops/selective_scan.py; or ``jnp``).

Prefill: the prompt's positions through the first half of the stack,
``PREFILL_CHUNK`` at a time in a loop inside its program that stops at
the prompt's length, and the second half for its last position alone
(models/phi4flash.py, "A prefill stops half way"). Counted as the
program defines it: ``serve.prefill_self_positions`` (whole chunks up to
the prompt's end, the first half's; also ``serve.prefill_computed_tokens``)
and ``serve.prefill_cross_positions`` (one a prompt, the second half's),
both fields of ``serve/prefill.done``. A program serves every prompt up
to its length, so the adapter builds one for each doubling of the bucket
(``serve/families/__init__.py::program_len``).

Not here yet (PERF.md section 7): a serving layout over chips, handoff of
ring, slab and pages, quantized pages, speculative decode, prefix reuse
(a prefix is one layer's pages, a ring snapshot and a slab snapshot), a
prompt's chunks between decode steps, several prompts in one prefill
program.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from fms_fsdp_tpu.models.generation import sample_token
from fms_fsdp_tpu.models.phi4flash import (
    pair_rows,
    phi4flash_decode_step,
    phi4flash_prefill,
    prefill_attn_form,
    prefill_positions,
)
from fms_fsdp_tpu.ops.selective_scan import scan_step_form
from fms_fsdp_tpu.serve.families import (
    FamilyAdapter,
    block_paged_geometry as page_geometry,  # the full layer's pages
    jit_prefill,
    kernel_or_reference as resolve_attn_impl,
    sequence_prefill_attn_impl as _prefill_attn_impl,
    slot_writer,
)


def state_shapes(model_cfg, slots: int, dtype) -> dict:
    """name -> (shape, dtype) of what the slots keep beside the pages:
    the window layers' rings and the Mamba layers' slabs, layers leading,
    slots behind them."""
    cfg = model_cfg
    n_win, n_mamba = len(cfg.layers_of("window")), len(cfg.layers_of("mamba"))
    pairs, width = pair_rows(cfg)
    ring = (n_win, slots, cfg.sliding_window * pairs, width)
    return {
        "ring_k": (ring, dtype),
        "ring_v": (ring, dtype),
        "conv": ((n_mamba, slots, cfg.d_conv - 1, cfg.d_inner), dtype),
        "ssd": ((n_mamba, slots, cfg.d_state, cfg.d_inner), jnp.float32),
    }


def cache_bytes(model_cfg, dtype) -> dict:
    """What a stream costs: ``per_token`` bytes a position in the full
    layer's pools (K and V, one layer, whatever the number of layers that
    read them) and ``per_stream`` bytes a slot in rings and slabs,
    whatever the context."""
    return {
        "per_token": (
            2 * model_cfg.kvheads * model_cfg.head_dim
            * jnp.dtype(dtype).itemsize
        ),
        "per_stream": sum(
            math.prod(shape) * jnp.dtype(dt).itemsize
            for shape, dt in state_shapes(model_cfg, 1, dtype).values()
        ),
    }


def pool_row(model_cfg, page_size: int):
    """(rows a page holds, lanes of a row) of the full layer's pools."""
    pairs, width = pair_rows(model_cfg)
    return page_size * pairs, width


def decode_program(model_cfg, scfg, page_size: int, block_kv, compute_dtype):
    """The jitted decode step of a phi4flash engine: one ragged step over
    ``scfg.max_batch`` slots and the sampler, state and pools donated. A
    function of the two configs alone; the traced function keeps the name
    ``_step``, so the profiler shows the program as ``jit__step``.

    ``(params, state, pools, page_table, seq_lens, tokens, key) -> (tokens
    (B,) int32, logits (B, V), state, pools)``."""
    attn_impl = resolve_attn_impl(scfg)

    def _step(params, state, pools, page_table, seq_lens, tokens, key):
        logits, state, pools = phi4flash_decode_step(
            params, state, pools, page_table, seq_lens, tokens, model_cfg,
            page_size=page_size, compute_dtype=compute_dtype,
            attn_impl=attn_impl, block_kv=block_kv,
        )
        tok = sample_token(
            logits, key, scfg.temperature, scfg.top_k, scfg.do_sample
        )
        return tok.astype(jnp.int32), logits, state, pools

    return jax.jit(_step, donate_argnums=(1, 2))


def prefill_program(model_cfg, scfg, n: int, kv_len: int, compute_dtype):
    """The jitted prefill of prompts up to ``n`` positions, the full
    layer's keys and values in buffers of ``kv_len`` (``n`` in whole
    pages): ``(params, tokens (1, n), lengths (1,)) -> (logits (1, V), the
    full layer's k and v as the pages hold them, one slot's rings and
    slabs)``. The traced function is named by the length:
    ``jit__prefill_<n>`` in the profiler's trace."""
    return jit_prefill(
        n, phi4flash_prefill, model_cfg, compute_dtype=compute_dtype,
        kv_len=kv_len, attn_impl=_prefill_attn_impl(scfg),
    )


class Phi4FlashAdapter(FamilyAdapter):
    family = "phi4flash"
    _pages_noun = "full-attention pages"

    def _setup(self) -> None:
        cfg, scfg = self.model_cfg, self.scfg
        self._refuse(
            ("serve_layout", scfg.serve_layout,
             "the stack is built for one chip, which holds the model whole"),
            ("kv_quant", scfg.kv_quant != "none" and scfg.kv_quant,
             "ring, slab and pages are stored full-width"),
            ("speculator_path", scfg.speculator_path,
             "the draft/verify loop is llama-only (a slab and a ring cannot "
             "roll back)"),
            ("role", scfg.role != "unified" and scfg.role,
             "handoff of ring, slab and pages is not built: run unified "
             "replicas"),
            ("prefill_chunk_tokens", scfg.prefill_chunk_tokens,
             "a prompt's chunks between decode steps are not built"),
        )
        self.attn_impl = resolve_attn_impl(scfg)
        # how the Mamba layers step their scan state, beside ``attn_form``
        self.ssm_form = scan_step_form(
            scfg.max_batch, cfg.d_state, cfg.d_inner
        )
        self._dispatch_fields = {
            "attn_form": self.attn_impl, "ssm_form": self.ssm_form,
        }

        from fms_fsdp_tpu.serve.kv_cache import PagedKVCache

        (
            self.page_size, self.block_kv, self.max_pages, num_pages,
        ) = page_geometry(cfg, scfg)
        # pages for one layer, which eight read; a prefill program is as
        # long as a doubling of the bucket: what it writes past a
        # stream's own pages is zeros
        rows, width = pool_row(cfg, self.page_size)
        self.cache = PagedKVCache(
            1, num_pages, self.page_size, cfg.kvheads, cfg.head_dim,
            dtype=self.compute_dtype,
            pools={"k": (width,), "v": (width,)},
            page_rows={"k": rows, "v": rows}, scratch_tail=True,
        )
        self._state = {
            name: jnp.zeros(shape, dt)
            for name, (shape, dt) in state_shapes(
                cfg, scfg.max_batch, self.compute_dtype
            ).items()
        }
        self._write_slot = slot_writer(1)  # one stream's rings and slabs
        self._decode_fn = decode_program(
            cfg, scfg, self.page_size, self.block_kv, self.compute_dtype
        )
        self.ssm_layers = len(cfg.layers_of("mamba"))
        cost = cache_bytes(cfg, self.compute_dtype)
        gauge = self.registry.gauge
        gauge("serve.window_layers").set(len(cfg.layers_of("window")))
        gauge("serve.full_layers").set(1)
        gauge("serve.cross_layers").set(len(cfg.layers_of("cross")))
        gauge("serve.gmu_layers").set(len(cfg.layers_of("gmu")))
        gauge("serve.window_positions").set(cfg.sliding_window)
        gauge("serve.kv_bytes_per_position").set(cost["per_token"])
        gauge("serve.kv_bytes_per_token").set(cost["per_token"])
        gauge("serve.state_bytes_per_stream").set(cost["per_stream"])

    @property
    def state_bytes_per_stream(self) -> int:
        """Rings and slabs of one slot: constant in the stream's context."""
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
        return {"attn_form": form}

    def _call_prefill(self, fn, toks, p: int):
        n = self.program_len_of(p)
        row = np.zeros((1, n), np.int32)
        row[0, : toks.shape[1]] = toks[0]
        logits, kv, state = fn(
            self.params, jnp.asarray(row), jnp.asarray([p], np.int32)
        )
        return logits[0], kv, state, prefill_positions(p, n)

    def _count_prefill(self, rid: int, computed: int, program_counts) -> None:
        """Beside the positions computed (the first half's): the same
        number under its own name, and the one position of the prompt
        that the second half of the stack computed."""
        count = self.registry.counter
        count("serve.prefill_self_positions").add(computed)
        count("serve.prefill_cross_positions").add(1)
        super()._count_prefill(
            rid, computed, self_positions=computed, cross_positions=1
        )
