"""MiniCPM-SALA family adapter: three kinds of state in one manager
(models/minicpm_sala.py).

**What a stream holds follows the kind of layer.**

- A *sparse* layer (``minicpm4``) keeps a key and a value for every
  position: pages of ``PagedKVCache`` whose layer axis runs over the
  **sparse layers' kv heads** (a kv head's chosen pages are its own
  list, so each is a layer of one head), ``block_size`` positions a page:
  a chosen block is a page. This is the only thing that grows with the
  context and the only thing ``admission_error``, ``can_admit``,
  ``grow``, eviction and ``release`` reckon with (the skeleton's page rule
  over ``self.cache``).
- Beside the pages, under the same allocator and the same table, the
  **index cache**: the compressed keys (the mean of ``kernel_size`` keys
  every ``kernel_stride`` positions), ``block_size / kernel_stride`` rows
  a page (``PagedKVCache(page_rows=...)``). A decode step's choice of
  blocks reads a stream's index cache whole and its attention never
  does; a page's rows are written as the stream's positions complete
  their windows.
- A *lightning* layer keeps a float32 state ``(heads, H, H)`` a slot,
  ``(L_lightning, max_batch, heads, H, H)`` (``self._state["S"]``):
  constant bytes whatever the context, a fixed cost of a slot like the
  hybrids' recurrent slab. A prefill hands the prompt's state over
  (``prefill.write_state``) beside the sparse layers' pages and index rows
  (``prefill.write_pages``); nothing is zeroed at release, because the
  next prefill writes the slot's whole state.

``cache_bytes(model_cfg, dtype)`` says the three costs in one place.

Decode: one ragged step over ``max_batch`` slots. A sparse layer chooses
each row's blocks through its index cache and attends the chosen pages
alone, each (row, kv head) a row of the ragged paged kernel whose table
is the chosen pages (``ops/paged_attention.py::chosen_pages_attention``:
``attn_impl`` ``"kernel"``, and ``"auto"`` on a TPU) or of the gathered
form (``"reference"``); ``attn_form`` on every ``serve/decode.dispatch``
span says which, ``live_chose`` how many live streams stood past
``dense_len`` and chose.

Prefill: the prompt as a sequence, ``PREFILL_CHUNK`` positions at a time
in a loop inside its program that stops at the prompt's length
(``serve.prefill_computed_tokens``). A program serves every prompt up to
its length, so the adapter builds one for each doubling of the bucket
(``program_len``) and not one a bucket: a 64k deployment has six, not
32. ``attn_form`` on ``serve/prefill.dispatch`` says what the sparse
layers' attention runs in the program's chunks (``prefill_attn_form``:
``flash`` or ``einsum``, ``+chosen_blocks`` where chunks lie past
``dense_len``). ``serve/prefill.done`` carries what the program counted:
``chose_tokens`` (positions with ``t + 1 > dense_len``, which chose
their blocks), ``chosen_blocks`` (the blocks those chose, a kv head and
layer), ``context_blocks`` (the blocks they could have chosen from) and
``multiplied_blocks`` (the blocks the attention's products touched for
them: the chosen ones and the band's masked corners where a chunk lies
past ``dense_len``, every block of the context where a chunk holds
dense positions too and takes the masked walk).

Not here (PERF.md section 7): a serving layout over chips, handoff of
pages, index rows and state, quantized pages, speculative decode, prefix
reuse (most layers hold a state, not keys), state snapshots for
preemption (an evicted stream is prefilled again), a prompt's chunks
between decode steps.
"""

import jax
import jax.numpy as jnp
import numpy as np

from fms_fsdp_tpu.models.generation import sample_token
from fms_fsdp_tpu.models.minicpm_sala import (
    prefill_attn_form,
    prefill_positions,
    sala_paged_decode_step,
    sala_prefill,
)
from fms_fsdp_tpu.serve.families import (
    FamilyAdapter,
    block_paged_geometry,
    jit_prefill,
    kernel_or_reference as resolve_attn_impl,
    program_len,  # noqa: F401  (benchmark/ takes it from here)
    sequence_prefill_attn_impl as _prefill_attn_impl,
    slot_writer,
)


def cache_bytes(model_cfg, dtype) -> dict:
    """What a stream costs by kind of state: ``per_token`` bytes a
    position in the sparse layers' pages (K and V), ``index_per_token``
    bytes a position in their index cache (one compressed key every
    ``kernel_stride`` positions), ``per_stream`` bytes a slot in the
    lightning layers' float32 states, whatever the context."""
    sp = model_cfg.sparse
    row = model_cfg.kvheads * model_cfg.head_dim * jnp.dtype(dtype).itemsize
    n_sparse = len(model_cfg.sparse_layers)
    return {
        "per_token": n_sparse * 2 * row,
        "index_per_token": n_sparse * row // sp.kernel_stride,
        "per_stream": (
            len(model_cfg.lightning_layers) * model_cfg.lightning_nh
            * model_cfg.lightning_head_dim**2 * 4
        ),
    }


def page_geometry(model_cfg, scfg):
    """``(page_size, block_kv, max_pages, num_pages)`` of the sparse
    layers' paged cache: a page is a block of the choice, the decode
    kernel's cells in whole pages of the longest list a row attends
    (``block_paged_geometry``)."""
    sp = model_cfg.sparse
    if scfg.page_size not in (0, sp.block_size):
        raise ValueError(
            f"minicpm_sala serving does not take page_size={scfg.page_size}"
            f": a page is a block of the choice ({sp.block_size} positions)"
        )
    return block_paged_geometry(
        model_cfg, scfg, page_size=sp.block_size, longest=sp.list_blocks
    )


def state_shape(model_cfg, scfg):
    """The lightning layers' states: (L_lightning, slots, heads, H, H)."""
    return (
        len(model_cfg.lightning_layers), scfg.max_batch,
        model_cfg.lightning_nh,
    ) + (model_cfg.lightning_head_dim,) * 2


def decode_program(model_cfg, scfg, page_size: int, block_kv, compute_dtype):
    """The jitted decode step of a MiniCPM-SALA engine: one ragged step
    over ``scfg.max_batch`` slots and the sampler, states and pools
    donated. A function of the two configs alone; the traced function
    keeps the name ``_step``, so the profiler shows the program as
    ``jit__step``.

    ``(params, state, pools, page_table, seq_lens, tokens, key) ->
    (tokens (B,) int32, logits (B, V), state, pools)``."""
    attn_impl = resolve_attn_impl(scfg)

    def _step(params, state, pools, page_table, seq_lens, tokens, key):
        logits, state, pools = sala_paged_decode_step(
            params, state, pools, page_table, seq_lens, tokens, model_cfg,
            page_size=page_size, compute_dtype=compute_dtype,
            attn_impl=attn_impl, block_kv=block_kv,
        )
        tok = sample_token(
            logits, key, scfg.temperature, scfg.top_k, scfg.do_sample
        )
        return tok.astype(jnp.int32), logits, state, pools

    return jax.jit(_step, donate_argnums=(1, 2))


def prefill_program(model_cfg, scfg, n: int, compute_dtype):
    """The jitted prefill of prompts up to ``n`` positions: ``(params,
    tokens (1, n), lengths (1,)) -> (logits (1, V), the sparse layers'
    pages and index rows ``{"k", "v", "kc"}``, the lightning layers'
    states ``{"S"}``, the counts)`` (``sala_prefill``). The traced
    function is named by the length: ``jit__prefill_<n>`` in the
    profiler's trace."""
    return jit_prefill(
        n, sala_prefill, model_cfg, compute_dtype=compute_dtype,
        kv_len=n, attn_impl=_prefill_attn_impl(scfg),
    )


class MiniCPMSalaAdapter(FamilyAdapter):
    family = "minicpm_sala"
    _pages_noun = "sparse-attention pages"

    def _setup(self) -> None:
        cfg, scfg = self.model_cfg, self.scfg
        self._refuse(
            ("serve_layout", scfg.serve_layout,
             "the family runs on one chip: a layout over chips is not built"),
            ("kv_quant", scfg.kv_quant != "none" and scfg.kv_quant,
             "pages and index cache are stored full-width"),
            ("speculator_path", scfg.speculator_path,
             "the draft/verify loop is llama-only (a lightning state "
             "cannot roll back)"),
            ("role", scfg.role != "unified" and scfg.role,
             "handoff of pages, index rows and states is not built: run "
             "unified replicas"),
            ("prefill_chunk_tokens", scfg.prefill_chunk_tokens,
             "a prompt's chunks between decode steps are not built"),
        )
        if not cfg.sparse_layers or not cfg.lightning_layers:
            raise ValueError(
                "minicpm_sala serving keeps pages for its sparse layers "
                "and a state for its lightning layers and is not built "
                f"for a stack without one of them (mixer_types="
                f"{cfg.mixer_types})"
            )
        sp = cfg.sparse
        if max(1, scfg.prefill_bucket) % sp.block_size:
            raise ValueError(
                f"minicpm_sala serving prefills whole blocks: "
                f"prefill_bucket={scfg.prefill_bucket} is no multiple of "
                f"{sp.block_size}"
            )
        self.attn_impl = resolve_attn_impl(scfg)
        self._dispatch_fields = {"attn_form": self.attn_impl, "live_chose": 0}

        from fms_fsdp_tpu.serve.kv_cache import PagedKVCache

        (
            self.page_size, self.block_kv, self.max_pages, num_pages,
        ) = page_geometry(cfg, scfg)
        # a layer of the cache for each kv head of each sparse layer; the
        # index cache beside the pages, ``per_block`` rows a page
        self.cache = PagedKVCache(
            len(cfg.sparse_layers) * cfg.kvheads, num_pages, self.page_size,
            1, cfg.head_dim, dtype=self.compute_dtype,
            pools={
                "k": (1, cfg.head_dim),
                "v": (1, cfg.head_dim),
                "kc": (cfg.head_dim,),
            },
            page_rows={"kc": sp.per_block}, scratch_tail=True,
        )
        self._state = {"S": jnp.zeros(state_shape(cfg, scfg), jnp.float32)}

        self._write_slot = slot_writer(1)  # one stream's states
        self._decode_fn = decode_program(
            cfg, scfg, self.page_size, self.block_kv, self.compute_dtype
        )
        self.ssm_layers = len(cfg.lightning_layers)
        cost = cache_bytes(cfg, self.compute_dtype)
        gauge = self.registry.gauge
        gauge("serve.sparse_layers").set(len(cfg.sparse_layers))
        gauge("serve.lightning_layers").set(len(cfg.lightning_layers))
        gauge("serve.kv_bytes_per_token").set(cost["per_token"])
        gauge("serve.index_bytes_per_token").set(cost["index_per_token"])
        gauge("serve.lightning_state_bytes_per_stream").set(cost["per_stream"])

    @property
    def state_bytes_per_stream(self) -> int:
        """The lightning layers' states of one slot: constant in the
        stream's context."""
        return cache_bytes(self.model_cfg, self.compute_dtype)["per_stream"]

    # -- prefill: one program a doubling of the bucket ---------------------

    def _prefill_key(self, p: int, p_pad: int, kv_len: int):
        return (self.program_len_of(p),)

    def _build_prefill(self, key):
        return prefill_program(
            self.model_cfg, self.scfg, key[0], self.compute_dtype
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
        logits, kv, state, counts = fn(
            self.params, jnp.asarray(row), jnp.asarray([p], np.int32)
        )
        self._program_counts = counts  # on the device until read
        # pages and index rows at the program's length: what lies past the
        # stream's own pages is zeros and lands on the scratch page
        # (``PagedKVCache.scratch_tail``), so a write has the program's
        # shape and compiles once a program, not once a padded length
        return logits[0], kv, state, prefill_positions(p, n, self.model_cfg)

    def _count_prefill(self, rid: int, computed: int, program_counts) -> None:
        """Beside the positions computed: those of them that chose their
        blocks (``t + 1 > dense_len``), the blocks they chose, the blocks
        they chose from and the blocks the attention's products touched
        for them, a kv head and sparse layer (the program's own counts,
        read behind the stream's first token)."""
        chose, blocks, context, multiplied = map(
            int, np.asarray(program_counts)
        )
        counter = self.registry.counter
        counter("serve.sparse_chose_tokens").add(chose)
        counter("serve.sparse_chosen_blocks").add(blocks)
        counter("serve.sparse_context_blocks").add(context)
        counter("serve.sparse_multiplied_blocks").add(multiplied)
        super()._count_prefill(
            rid, computed, chose_tokens=chose, chosen_blocks=blocks,
            context_blocks=context, multiplied_blocks=multiplied,
        )

    # -- what the ragged paged decode kernel walks: the chosen pages ------

    def attn_blocks(self, lens) -> int:
        """A row of the kernel is a (stream, kv head) and its length the
        chosen positions (``chosen_pages_attention``): every block up to
        the query's own while it is dense, ``topk`` of them past that."""
        block = self.attn_block
        if not block:
            return 0
        sp, ps = self.model_cfg.sparse, self.page_size
        total = 0
        for t in map(int, lens):
            n = t // ps + 1
            if t + 1 > sp.dense_len:
                n = min(n, sp.topk)
            total += ((n - 1) * ps + t % ps) // block + 1
        return total * self.model_cfg.kvheads

    @property
    def attn_grid_blocks(self) -> int:
        block = self.attn_block
        if not block:
            return 0
        width = min(self.model_cfg.sparse.list_blocks, self.max_pages)
        return self.scfg.max_batch * self.model_cfg.kvheads * -(
            -width // (block // self.page_size)
        )

    # -- decode: the step's count beside the skeleton's dispatch -----------

    def decode_dispatch(
        self, slot_rids, lens, tokens, key, fresh, in_flight=0, first=()
    ):
        dense_len = self.model_cfg.sparse.dense_len
        chose = sum(
            1 for rid, n in zip(slot_rids, lens)
            if rid is not None and n + 1 > dense_len
        )
        self.registry.counter("serve.sparse_decode_chose").add(chose)
        self._dispatch_fields = dict(self._dispatch_fields, live_chose=chose)
        return super().decode_dispatch(
            slot_rids, lens, tokens, key, fresh, in_flight, first
        )
