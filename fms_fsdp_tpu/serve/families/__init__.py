"""Family adapters: one serving engine, eight model families.

The ServingEngine owns admission, continuous batching, eviction and
metrics — none of which care what a "slot" stores. What differs per
model family is (a) what decode state a stream holds, (b) how a prompt
prefills into it, (c) what one ragged batched decode step computes, and
(d) how a checkpoint resolves to a family in the first place. A
:class:`FamilyAdapter` owns exactly those four things:

==============  ========================================================
family          decode-state per stream
==============  ========================================================
``llama``       paged KV pages (grows with generated length; the
                PR-11 path, ragged paged-attention kernel and all —
                untouched, still the engine's bit-parity anchor)
``mamba``       fixed-size recurrent slab: per mamba layer a conv
                window (d_conv-1, conv_dim) + fp32 state, (H, headdim,
                d_state) for a Mamba-2 mixer and (d_state, d_inner)
                for a Mamba-1 mixer (the Jamba hybrids) — constant
                bytes regardless of generated length; hybrid configs'
                attn layers ride paged KV pages like llama
``mixtral``     paged KV pages for attention + nothing for the MoE:
                expert routing is stateless per token (top-k gather
                of expert weights at decode)
``sarvam``      paged latent pages (one pool, ``latent_dim`` values a
                position and layer whatever the head count: MLA);
                the held share of a sigmoid-routed expert layer and
                its shared expert are stateless per token
``kexaone``     a cache of each kind of attention layer: paged KV
                pages for the full layers alone (the only thing that
                grows) and, for the window layers, a ring of
                ``sliding_window`` keys and values a slot, constant
                bytes whatever the context; the held share of the
                expert layer is the code sarvam runs
``minicpm_sala``  three kinds of state in one manager: paged KV pages
                for the sparse-attention layers alone (a page is a
                block of the choice; the only thing that grows), an
                index cache of compressed keys beside them under the
                same table (read whole by the choice, never by the
                attention), and for the lightning linear-attention
                layers a float32 ``(heads, H, H)`` state a slot,
                constant bytes whatever the context
``lfm2``        paged KV pages for the attention layers alone (the only
                thing that grows) and, for the gated short-convolution
                layers, a window of ``conv_kernel - 1`` positions of
                ``B * x`` a slot, constant bytes whatever the context;
                the expert layer is the code sarvam runs, every expert
                held and no shared one
``phi4flash``   all three kinds of state in one adapter: paged KV pages
                declared over ONE layer (the full-attention layer's, the
                only thing that grows) that the seven cross-attention
                layers behind it read too; a ring of ``sliding_window``
                keys and values a slot for the window layers; a
                recurrent slab (conv window + fp32 state) a slot for the
                Mamba-1 layers; the gated memory units and the cross
                layers keep nothing. A prefill computes the first half
                of the stack for every position and the second for the
                last alone
==============  ========================================================

Every adapter is parity-anchored: greedy decode through the engine is
bit-identical (float32 + reference impls) to the family's jitted dense
full-forward argmax walk (tests/test_serving_families.py).

This module is deliberately jax-free at import time (configs + stdlib
only): the fleet router and replica arg parser resolve families on
hosts where jax may be absent. Adapter classes import lazily inside
:func:`resolve_adapter`.

Obs note: the schema-v12 ``serving`` map is flat str->number, so the
family travels as a numeric code (:data:`FAMILY_CODES`), not a string.
"""

from functools import cache, partial
from typing import Optional

import numpy as np

from fms_fsdp_tpu.models.configs import (
    KEXAONE_LAYER_KINDS,
    KExaoneConfig,
    Lfm2MoeConfig,
    LlamaConfig,
    MambaConfig,
    MixtralConfig,
    Phi4FlashConfig,
    SALA_MIXER_KINDS,
    SalaConfig,
    SarvamConfig,
)
from fms_fsdp_tpu.obs.registry import MetricRegistry
from fms_fsdp_tpu.obs.spans import done, span

# the wire encoding of a family in numeric-only maps (obs schema v12
# "serving"): family = FAMILY_CODES[name]
FAMILY_CODES = {
    "llama": 0, "mamba": 1, "mixtral": 2, "sarvam": 3, "kexaone": 4,
    "minicpm_sala": 5, "lfm2": 6, "phi4flash": 7,
}
FAMILY_NAMES = {v: k for k, v in FAMILY_CODES.items()}

_CONFIG_FAMILIES = (
    (MambaConfig, "mamba"),
    (MixtralConfig, "mixtral"),
    (SarvamConfig, "sarvam"),
    (KExaoneConfig, "kexaone"),
    (SalaConfig, "minicpm_sala"),
    (Lfm2MoeConfig, "lfm2"),
    (Phi4FlashConfig, "phi4flash"),
    (LlamaConfig, "llama"),
)


def family_of(model_cfg) -> str:
    """Model config dataclass -> family name."""
    for cls, name in _CONFIG_FAMILIES:
        if isinstance(model_cfg, cls):
            return name
    known = ", ".join(
        f"{cls.__name__} ({name})" for cls, name in _CONFIG_FAMILIES
    )
    raise ValueError(
        f"unknown model config type {type(model_cfg).__name__}: expected "
        f"one of {known} (fms_fsdp_tpu/models/configs.py)"
    )


def load_model_config(d: dict):
    """Plain dict (a fleet model_cfg.json) -> the right config dataclass.

    An explicit ``"family"`` key wins; otherwise the family is inferred
    from architecture-distinguishing keys (``d_model`` -> mamba,
    ``num_experts`` -> mixtral, else llama). A published Jamba
    ``config.json`` (``"model_type": "jamba"``, or ``"family": "jamba"``)
    resolves to the mamba family through its own key mapping, and a
    published ``"model_type": "sarvam_mla"`` one (or ``"family":
    "sarvam"``) to the sarvam family through its own, a published
    ``"model_type": "exaone_moe"`` one (or ``"family": "kexaone"``) to
    the kexaone family through its own, a published ``"model_type":
    "minicpm_sala"`` one (or ``"family": "minicpm_sala"``) to the
    minicpm_sala family through its own, a published ``"model_type":
    "lfm2_moe"`` one (or ``"family": "lfm2"``) to the lfm2 family through
    its own, a published ``"model_type": "phi4flash"`` one (or ``"family":
    "phi4flash"``) to the phi4flash family through its own. This is the single
    resolution point replica.py and the engine share — the two can no
    longer diverge on model construction (the PR-11 bug this replaces:
    replica.py:71 hardwired its own ``init_llama_params`` copy)."""
    d = dict(d)
    family = d.pop("family", None)
    if family == "jamba" or d.get("model_type") == "jamba":
        # a published Jamba config.json: the mamba family's hybrid
        # stack with Mamba-1 mixers (models/configs.py::jamba_config)
        from fms_fsdp_tpu.models.configs import jamba_config

        return jamba_config(d)
    if family == "sarvam" or d.get("model_type") == "sarvam_mla":
        from fms_fsdp_tpu.models.configs import sarvam_config

        return sarvam_config(d)
    if family == "kexaone" or d.get("model_type") == "exaone_moe":
        from fms_fsdp_tpu.models.configs import kexaone_config

        return kexaone_config(d)
    if family == "minicpm_sala" or d.get("model_type") == "minicpm_sala":
        from fms_fsdp_tpu.models.configs import minicpm_sala_config

        return minicpm_sala_config(d)
    if family == "lfm2" or d.get("model_type") == "lfm2_moe":
        from fms_fsdp_tpu.models.configs import lfm2_moe_config

        return lfm2_moe_config(d)
    if family == "phi4flash" or d.get("model_type") == "phi4flash":
        from fms_fsdp_tpu.models.configs import phi4flash_config

        return phi4flash_config(d)
    if family is None:
        if "d_model" in d or "n_layer" in d:
            family = "mamba"
        elif "num_experts" in d or "top_k" in d:
            family = "mixtral"
        else:
            family = "llama"
    if family not in FAMILY_CODES:
        raise ValueError(
            f"unknown model family {family!r} in model config: expected "
            f"one of {sorted(FAMILY_CODES)} — set \"family\" explicitly "
            f"or drop it to infer from the config keys"
        )
    try:
        if family == "mamba":
            from fms_fsdp_tpu.models.configs import MambaAttnConfig

            attn = d.get("attn_cfg")
            if isinstance(attn, dict):
                d["attn_cfg"] = MambaAttnConfig(**attn)
            if "attn_layer_idx" in d and d["attn_layer_idx"] is not None:
                d["attn_layer_idx"] = tuple(d["attn_layer_idx"])
            return MambaConfig(**d)
        if family == "mixtral":
            return MixtralConfig(**d)
        return LlamaConfig(**d)
    except TypeError as e:
        raise ValueError(
            f"model config keys do not match the {family} family "
            f"({type(e).__name__}: {e}) — if the family was inferred "
            f"wrongly, set \"family\" explicitly in the model config"
        ) from None


def check_params_family(params, family: str) -> None:
    """Validate a params tree actually belongs to ``family``.

    Structural fingerprints: mamba stacks layers as a python list of
    per-layer dicts; mixtral's stacked layer dict carries the router
    ``gate``; llama's carries ``wq`` without ``gate``. A mismatch means
    the checkpoint and the model config disagree — fail at build with
    the fix spelled out, not at the first prefill with a shape error."""
    layers = params.get("layers") if hasattr(params, "get") else None
    stacks = (
        set(params) if layers is None and hasattr(params, "get") else set()
    )
    if stacks & set(SALA_MIXER_KINDS.values()):
        actual = "minicpm_sala"  # a stack for each kind of mixer
    elif stacks & set(KEXAONE_LAYER_KINDS):
        actual = "kexaone"  # a stack for each kind of layer, no "layers"
    elif isinstance(layers, (list, tuple)) and layers and (
        "operator_norm" in layers[0]
    ):
        actual = "lfm2"  # a list of layers, each behind its operator's norm
    elif isinstance(layers, (list, tuple)) and layers and isinstance(
        layers[0].get("norm"), dict
    ):
        actual = "phi4flash"  # a list of layers behind LayerNorms with a bias
    elif isinstance(layers, (list, tuple)):
        actual = "mamba"
    elif isinstance(layers, dict) and "wkv_a" in layers:
        actual = "sarvam"  # latent attention's down-projection
    elif isinstance(layers, dict) and "gate" in layers:
        actual = "mixtral"
    elif isinstance(layers, dict) and "wq" in layers:
        actual = "llama"
    else:
        raise ValueError(
            "params do not look like any serveable family (no "
            "recognizable 'layers' structure): expected the output of "
            "the params initializer of one of "
            f"{sorted(FAMILY_CODES)} (init_params_for) or a checkpoint "
            "thereof"
        )
    if actual != family:
        raise ValueError(
            f"checkpoint/model-config family mismatch: params look like "
            f"{actual!r} but the model config says {family!r} — pass the "
            f"matching config dataclass (or fix \"family\" in "
            f"model_cfg.json)"
        )


def init_params_for(model_cfg):
    """Family -> its params initializer, ``fn(key) -> params``. The one
    bootstrap the engine's ``from_checkpoint`` and replica.py both use."""
    family = family_of(model_cfg)
    if family == "mamba":
        from fms_fsdp_tpu.models.mamba import init_mamba_params

        return lambda key: init_mamba_params(key, model_cfg)
    if family == "mixtral":
        from fms_fsdp_tpu.models.mixtral import init_mixtral_params

        return lambda key: init_mixtral_params(key, model_cfg)
    if family == "sarvam":
        from fms_fsdp_tpu.models.sarvam import init_sarvam_params

        return lambda key: init_sarvam_params(key, model_cfg)
    if family == "kexaone":
        from fms_fsdp_tpu.models.kexaone import init_kexaone_params

        return lambda key: init_kexaone_params(key, model_cfg)
    if family == "minicpm_sala":
        from fms_fsdp_tpu.models.minicpm_sala import init_sala_params

        return lambda key: init_sala_params(key, model_cfg)
    if family == "lfm2":
        from fms_fsdp_tpu.models.lfm2 import init_lfm2_params

        return lambda key: init_lfm2_params(key, model_cfg)
    if family == "phi4flash":
        from fms_fsdp_tpu.models.phi4flash import init_phi4flash_params

        return lambda key: init_phi4flash_params(key, model_cfg)
    from fms_fsdp_tpu.models.llama import init_llama_params

    return lambda key: init_llama_params(key, model_cfg)


def paged_geometry(scfg, nheads: int, n_kv_heads: int, head_dim: int,
                   tuned: bool = True):
    """``(page_size, block_kv, tune_how, max_pages, num_pages)`` of the
    paged cache an engine builds for attention of these shapes: the page
    size through the kernel-tuning table when ``tuned`` (llama, mixtral),
    else 16 (the hybrid's attention shape has no table entry; 16 is the
    table's common resolution), either way pinned by ``scfg.page_size``;
    then the pages one sequence can hold and the pool."""
    from fms_fsdp_tpu.serve.kv_cache import RESERVED_PAGES

    if tuned:
        from fms_fsdp_tpu.tune.lookup import resolve_paged_decode

        page_size, block_kv, tune_how = resolve_paged_decode(
            scfg.max_batch,
            nheads,
            n_kv_heads,
            head_dim,
            scfg.max_seq_len,
            scfg.compute_dtype,
            requested_page_size=scfg.page_size or None,
        )
    else:
        page_size, block_kv, tune_how = scfg.page_size or 16, 0, "n/a"
    assert scfg.max_seq_len % page_size == 0, (scfg.max_seq_len, page_size)
    max_pages = scfg.max_seq_len // page_size
    num_pages = scfg.num_pages or (
        scfg.max_batch * max_pages + RESERVED_PAGES
    )
    return page_size, block_kv, tune_how, max_pages, num_pages


# positions a page of a family's own pools holds unless ``scfg.page_size``
# pins it (one page of 8 kv heads of 128 is 256 KB of keys in bfloat16, one
# fetch of the decode kernel), and the positions of one block that kernel
# fetches by hand and multiplies at once: two blocks are resident in VMEM
# (5.2 MB at ten rows of 128 lanes a position) while a stream's pages are
# walked four at a time
PAGE_SIZE = 128
DECODE_BLOCK_TOKENS = 512


def block_paged_geometry(
    model_cfg, scfg, page_size: int = PAGE_SIZE, longest: int = 0
):
    """``(page_size, block_kv, max_pages, num_pages)`` of the paged cache
    of a family whose decode kernel walks a stream's pages in blocks
    (kexaone, lfm2, minicpm_sala, phi4flash): ``page_size`` positions a page unless
    ``scfg.page_size`` pins it, blocks of up to ``DECODE_BLOCK_TOKENS``
    positions in whole pages of the ``longest`` run of pages a row
    attends (all a stream can hold, unless given)."""
    import dataclasses

    from fms_fsdp_tpu.models.sequence_prefill import largest_divisor

    if not scfg.page_size:
        scfg = dataclasses.replace(scfg, page_size=page_size)
    page_size, _, _, max_pages, num_pages = paged_geometry(
        scfg, model_cfg.nheads, model_cfg.kvheads, model_cfg.head_dim,
        tuned=False,
    )
    block_kv = page_size * largest_divisor(
        min(longest or max_pages, max_pages),
        max(1, DECODE_BLOCK_TOKENS // page_size),
    )
    return page_size, block_kv, max_pages, num_pages


def program_len(p_pad: int, bucket: int, longest: int) -> int:
    """The length of the prefill program that takes a prompt padded to
    ``p_pad``, for a family that builds one program a doubling of the
    bucket (minicpm_sala, lfm2): the bucket doubled until it holds it, at
    most ``longest`` (``max_seq_len`` in whole buckets). The program's
    loop stops at the prompt's end, so a longer program costs a shorter
    prompt its buffers' zeros and a choice's scores over their rows, not
    positions."""
    n = bucket
    while n < p_pad:
        n *= 2
    return max(p_pad, min(n, longest))


def jit_prefill(n: int, prefill, model_cfg, **how):
    """The jitted prefill of prompts up to ``n`` positions by a model's
    sequence prefill: ``(params, tokens (1, n), lengths (1,)) ->
    prefill(params, tokens, lengths, model_cfg, **how)``. The traced
    function is named by the length, so the profiler shows each shape's
    program under its own name, ``jit__prefill_<n>``."""
    import jax

    def _prefill(params, tokens, lengths):
        return prefill(params, tokens, lengths, model_cfg, **how)

    _prefill.__name__ = f"_prefill_{n}"
    return jax.jit(_prefill)


@cache
def slot_writer(axis: int):
    """The program that lands one stream's state ``rows`` (a tree of
    arrays one slot wide along ``axis``) in its slot of the engine's
    ``state``: jitted with the state donated, so a write moves the rows
    and not the whole. The slots lead a mamba slab (axis 0) and follow
    the layers in the other families' states (axis 1)."""
    import jax

    def _write_slot(state, rows, slot):
        return jax.tree.map(
            lambda s, r: jax.lax.dynamic_update_slice_in_dim(
                s, r.astype(s.dtype), slot, axis
            ),
            state,
            rows,
        )

    return jax.jit(_write_slot, donate_argnums=(0,))


def resolve_adapter(
    params, model_cfg, serve_cfg, compute_dtype=None, registry=None
):
    """Checkpoint + config -> the family's adapter (jax imports here).
    ``registry`` is where the adapter counts (the engine hands over its
    own); with none given the adapter makes one."""
    family = family_of(model_cfg)
    check_params_family(params, family)
    if family == "mamba":
        from fms_fsdp_tpu.serve.families.mamba import MambaAdapter as cls
    elif family == "mixtral":
        from fms_fsdp_tpu.serve.families.mixtral import MixtralAdapter as cls
    elif family == "sarvam":
        from fms_fsdp_tpu.serve.families.sarvam import SarvamAdapter as cls
    elif family == "kexaone":
        from fms_fsdp_tpu.serve.families.kexaone import KExaoneAdapter as cls
    elif family == "minicpm_sala":
        from fms_fsdp_tpu.serve.families.minicpm_sala import (
            MiniCPMSalaAdapter as cls,
        )
    elif family == "lfm2":
        from fms_fsdp_tpu.serve.families.lfm2 import Lfm2Adapter as cls
    elif family == "phi4flash":
        from fms_fsdp_tpu.serve.families.phi4flash import (
            Phi4FlashAdapter as cls,
        )
    else:
        from fms_fsdp_tpu.serve.families.llama import LlamaAdapter as cls
    return cls(params, model_cfg, serve_cfg, compute_dtype, registry)


@cache
def _put_token():
    """The program that writes one token, a device scalar, into a step's
    token row at a slot: jitted once for every engine of the process."""
    import jax

    return jax.jit(lambda row, slot, tok: row.at[slot].set(tok.astype(row.dtype)))


class FamilyAdapter:
    """The skeleton of a family's device work (docs/serving.md "Family
    adapters" has the table). The engine owns scheduling, sampling, rng
    and the request metrics; this class owns what every family does the
    same way around its programs and its state, and counts what happens
    there into the registry it was given:

    - ``admission_error`` / ``can_admit`` / ``grow`` / ``release``: the
      page-capacity rule over ``self.cache`` (constant answers for a
      family with no pages);
    - ``prefill(rid, slot, prompt)``: pad to the bucket, allocate, look
      the program up (``_program``), call it, land its outputs (slab
      rows, K/V pages), hand back the (V,) logits row of the last real
      prompt position; nothing is read, and ``count_prefill(rid)``, which
      the engine calls once it has read the first token, reads what the
      program counted and writes ``prefill.done``;
    - ``decode_dispatch(slot_rids, lens, tokens, key, fresh, first=)``:
      upload the page table when stale, one jitted ragged step over all
      slots (pools, slab donated) fed its predecessor's tokens, and the
      ``first`` tokens of the streams just prefilled, on the device ->
      (tokens (B,), logits (B, V)) unread; ``decode_collect(tokens)``
      reads, waits.

    A family sets its state (``cache``, ``_state``) and ``_decode_fn`` in
    ``_setup`` and writes the three prefill hooks (``_prefill_key``,
    ``_build_prefill``, ``_call_prefill``) and, where a released stream
    leaves something behind, ``_release_state``.

    Disaggregation (serve/disagg/): paged families additionally set
    ``supports_handoff`` and inherit the base ``export_handoff`` /
    ``import_handoff`` (the whole transferable state IS the page set,
    so the generic pool gather/scatter covers llama and mixtral
    identically); mamba's non-page decode state travels through its
    own slab codec (serve/disagg/slab.py — conv window + fp32 SSD
    state + hybrid-layer pages), overriding all three methods.
    ``supports_layout`` gates ``ServeConfig.serve_layout`` the same
    way.
    """

    family: str = "?"
    cache = None  # PagedKVCache when the family uses pages, else None
    # the state a slot keeps beside its pages, when the family has one
    # (with ``_write_slot``, the program that lands one stream's rows in
    # it: ``slot_writer``)
    _state = None
    page_size: int = 0
    max_pages: int = 0
    attn_impl: str = "none"
    block_kv: int = 0
    tune_how: str = "n/a"
    mesh = None  # the serving mesh when serve_layout is set, else None
    _repl = None  # its replicated sharding
    supports_handoff: bool = False
    supports_layout: bool = False
    # speculative serving (ServeConfig.speculator_path): the adapter
    # flips ``speculative`` when it loaded a draft head; the engine then
    # routes through its ``decode_spec`` and budgets ``spec_draft_tokens``
    # extra cache positions per stream for in-flight draft writes
    speculative: bool = False
    spec_draft_tokens: int = 0
    # chunked prefill (ServeConfig.prefill_chunk_tokens): a family that
    # can advance a prompt in slices has ``prefill_start(rid, slot,
    # prompt)`` and ``prefill_chunk(rid)`` (llama.py) and sets this; the
    # engine rejects the knob for the rest at build
    supports_chunked_prefill: bool = False
    # expert weight copies one decode step reads in each layer (the gauge
    # serve.moe_expert_reads_per_layer); 0 for a family with no experts
    moe_expert_reads_per_layer: int = 0
    # layers that keep a recurrent slab slice per stream (the gauge
    # serve.ssm_layers); 0 for a family with no such state
    ssm_layers: int = 0
    _pages_noun: str = "pages"  # what a rejection calls the pool's pages
    _dispatch_fields: dict = {}  # the family's fields of decode.dispatch
    # what the prefill program called last counted itself, on the device
    # and unread: a family's ``_call_prefill`` leaves it here
    _program_counts = ()

    def __init__(
        self, params, model_cfg, scfg, compute_dtype=None, registry=None
    ):
        from fms_fsdp_tpu.serve.engine import _DTYPES

        self.params = params
        self.model_cfg = model_cfg
        self.scfg = scfg
        self.compute_dtype = compute_dtype or _DTYPES[scfg.compute_dtype]
        self.registry = MetricRegistry() if registry is None else registry
        self._prefill_cache: dict = {}
        # rid -> (positions computed, the program's own counts unread) of
        # the prefills dispatched and not yet counted (``count_prefill``)
        self._uncounted: dict = {}
        self._table_key = None
        self._table_dev = None
        self._setup()

    # -- what the ragged paged decode kernel walks -------------------------

    @property
    def attn_block(self) -> int:
        """The positions of one block of the ragged paged decode kernel's
        walk (ops/paged_attention.py); 0 where the decode program does
        not run that kernel."""
        return self.block_kv if self.attn_impl == "kernel" else 0

    def attn_blocks(self, lens) -> int:
        """The blocks that kernel's loops walk in a step over live streams
        whose queries sit at positions ``lens``, for one layer that reads
        the pages: a stream's live blocks, the last one partly filled."""
        block = self.attn_block
        return sum(int(n) // block + 1 for n in lens) if block else 0

    @property
    def attn_grid_blocks(self) -> int:
        """What a grid of every block a slot could hold walks a call (the
        kernel's grid before a cell was a stream): ``max_batch x
        max_pages / pages a block``. Over ``attn_blocks`` of a step: how
        much of that grid was dead."""
        block = self.attn_block
        if not block:
            return 0
        return self.scfg.max_batch * -(
            -self.max_pages // (block // self.page_size)
        )

    def _setup(self) -> None:
        """The family's refusals, state (``_init_pages``, ``_state``) and
        ``_decode_fn``, from ``self.params``, ``model_cfg`` and ``scfg``."""
        raise NotImplementedError

    def _refuse(self, *knobs) -> None:
        """``(knob, value, why)``: a set knob that this family does not
        take is refused by name at build."""
        for knob, value, why in knobs:
            if value:
                raise ValueError(
                    f"{self.family} serving does not take {knob}={value!r}: "
                    f"{why}"
                )

    def _init_pages(
        self, nlayers, nheads, n_kv_heads, head_dim, quant="none", tuned=True
    ) -> None:
        """The paged cache of ``nlayers`` attention layers of these
        shapes (``paged_geometry``), kv-head-sharded on a serving mesh."""
        from fms_fsdp_tpu.serve.kv_cache import PagedKVCache

        (
            self.page_size,
            self.block_kv,
            self.tune_how,
            self.max_pages,
            num_pages,
        ) = paged_geometry(self.scfg, nheads, n_kv_heads, head_dim, tuned)
        shape = (nlayers, num_pages, self.page_size, n_kv_heads, head_dim)
        self.cache = PagedKVCache(
            *shape,
            dtype=self.compute_dtype,
            quant=quant,
            shardings=self._pool_shardings(shape),
        )

    # -- capacity: the page rule, once -------------------------------------

    def _padded(self, n: int) -> int:
        """``n`` rounded up to the prefill bucket."""
        b = max(1, self.scfg.prefill_bucket)
        return -(-n // b) * b

    def admission_error(self, prompt_len: int, max_new: int) -> Optional[str]:
        """Worst-case capacity check at submit; a message means reject
        (reason=too_large). A constant slab fits iff a slot exists."""
        if self.cache is None:
            return None
        from fms_fsdp_tpu.serve.kv_cache import RESERVED_PAGES

        # speculative verify writes draft tokens past the committed
        # length before rollback — budget those cache positions too
        worst = (
            self._padded(prompt_len + max_new - 1)
            + 1
            + self.spec_draft_tokens
        )
        need = self.cache.pages_needed(worst)
        total = self.cache.num_pages - RESERVED_PAGES
        if need > total:
            return (
                f"request needs up to {need} {self._pages_noun} but the "
                f"pool holds {total}; raise num_pages or shrink "
                f"prompt/max_new_tokens"
            )
        return None

    def can_admit(self, rid: int, prompt_len: int) -> bool:
        """Would a prefill of this (resumed) prompt fit right now
        (pre-admission, nothing allocated)?"""
        return self.cache is None or self.cache.can_ensure(
            rid, self._padded(prompt_len) + 1
        )

    def grow(self, rid: int, n_tokens: int) -> bool:
        """Make room for the next token; False triggers the engine's
        LIFO eviction loop. Constant-state families always grow."""
        return self.cache is None or self.cache.ensure(rid, n_tokens)

    def release(self, rid: int, slot: int) -> None:
        """Return the stream's state. Eviction, expiry and completion
        all land here; recompute-on-resume re-prefills into whatever
        slot comes next."""
        self._release_state(rid, slot)
        if self.cache is not None:
            self.cache.free(rid)

    def _release_state(self, rid: int, slot: int) -> None:
        """What a family holds for a stream beside its pages."""

    # -- prefill: the template and the family's three hooks ----------------

    def _program(self, key, build):
        """-> (the program cached under ``key``, 1 if ``build(key)`` made
        it now else 0); counts ``serve.prefill_programs_built``."""
        fn = self._prefill_cache.get(key)
        if fn is not None:
            return fn, 0
        self.registry.counter("serve.prefill_programs_built").add()
        fn = self._prefill_cache[key] = build(key)
        return fn, 1

    def program_len_of(self, p: int) -> int:
        """The length of the program that prefills a prompt of ``p``, in
        a family that builds one a doubling of the bucket."""
        return program_len(
            self._padded(p), max(1, self.scfg.prefill_bucket),
            self._padded(self.scfg.max_seq_len),
        )

    def _prefill_key(self, p: int, p_pad: int, kv_len: int):
        """The key of the program that prefills ``p`` tokens padded to
        ``p_pad`` into a K/V of ``kv_len`` positions."""
        raise NotImplementedError

    def _build_prefill(self, key):
        """The jitted prefill program of ``key``."""
        raise NotImplementedError

    def _call_prefill(self, fn, toks, p: int):
        """Call ``fn`` on the padded token row ``toks`` (1, p_pad) ->
        (logits row (V,) of position ``p - 1``, K/V ``{"k", "v"}`` for
        the pages or None, slab rows or None, positions computed)."""
        raise NotImplementedError

    def _count_prefill(
        self, rid: int, computed: int, program_counts=(), **counts
    ) -> None:
        """The positions the prefill programs compute for ``rid``: the
        counter and the ``prefill.done`` marker's field, beside which a
        family may put ``counts`` of its own, read from what its program
        counted (``program_counts``, on the device)."""
        self.registry.counter("serve.prefill_computed_tokens").add(computed)
        done("prefill", rid=rid, computed_tokens=computed, **counts)

    def count_prefill(self, rid: int) -> None:
        """Count the prefill dispatched for ``rid`` (``_count_prefill``),
        once: the engine calls it when it has read the stream's first
        token, so the program has ended, the read of its counts waits for
        nothing and ``prefill.done`` starts after the program's end.
        Nothing where the positions were counted when they were staged
        (a chunked prefill)."""
        uncounted = self._uncounted.pop(rid, None)
        if uncounted is not None:
            self._count_prefill(rid, *uncounted)

    def prefill(self, rid: int, slot: int, prompt):
        """Allocate the stream's state, dispatch the family's prefill,
        write the slot's state; -> the (V,) logits row of the last real
        prompt position, on the device and unread (``count_prefill``
        reads the program's counts later)."""
        p = len(prompt)
        p_pad = self._padded(p)
        kv_len = 0
        if self.cache is not None:
            kv_len = self.cache.pages_needed(p_pad) * self.page_size
            ok = self.cache.ensure(rid, p_pad)
            assert ok, "admission checked capacity; ensure cannot fail here"
        key = self._prefill_key(p, p_pad, kv_len)
        fn, built = self._program(key, self._build_prefill)
        fields = self._prefill_fields(key)
        with span("prefill.dispatch", rid=rid, built=built, **fields):
            toks = np.zeros((1, p_pad), np.int32)
            toks[0, :p] = prompt
            row, kv, rows, computed = self._call_prefill(fn, toks, p)
        if rows is not None:
            with span("prefill.write_state", rid=rid):
                # land the 1-row prefill state in the stream's slab slice
                self._state = self._write_slot(
                    self._state, rows, np.int32(slot)
                )
                self.registry.counter("serve.prefill_state_writes").add()
        if kv is not None:
            with span("prefill.write_pages", rid=rid):
                self.cache.write_prompt(
                    rid, *(kv[name][:, 0] for name in self.cache.entry_shapes)
                )
        self._uncounted[rid] = (computed, self._program_counts)
        # on a mesh, hand the engine a host row: the engine's eager
        # sampler mixes it with its single-device rng key, which jax
        # refuses across device sets
        return np.asarray(row) if self.mesh is not None else row

    # -- decode: the template ----------------------------------------------

    # the token output of the last dispatched step, (B,) int32 on the
    # device, maybe still being computed: the next step's token input
    # wherever the engine brings no fresh token from the host
    _toks = None

    def decode_dispatch(
        self, slot_rids, lens, tokens, key, fresh, in_flight=0, first=()
    ):
        """Dispatch one jitted ragged decode step over all slots and
        return its sampled tokens (B,) int32 and logits (B, V) as device
        arrays, unread: the call returns before the device ends. A slot's
        token input is the host's ``tokens`` where ``fresh`` (a token the
        host holds: an imported stream's last, a first token it had to
        read early), the device scalar of ``first`` (``[(slot, token),
        ...]``: the first token of a stream prefilled since the last
        dispatch, sampled and not read) and the last dispatched step's
        own output elsewhere, which never leaves the device.
        ``in_flight`` 1: the engine dispatches this step before it read
        the last one's tokens. The program takes ``(params, [slab],
        [pools, page table], seq_lens, tokens, key)`` and returns
        ``(tokens, logits, [slab], [pools])``: the state a family has,
        donated and put back."""
        import jax.numpy as jnp

        row = self._toks
        if row is None or fresh.any():
            host = self._dev(tokens)
            row = jnp.where(
                self._dev(fresh), host, host if row is None else row
            )
        for slot, tok in first:
            row = _put_token()(row, np.int32(slot), tok)
        state = []
        if self._state is not None:
            state.append(self._state)
        if self.cache is not None:
            self._upload_table(slot_rids)
            state += [self.cache.pools, self._table_dev]
        with span(
            "decode.dispatch", in_flight=in_flight, **self._dispatch_fields
        ):
            self._toks, logits, *state = self._decode_fn(
                self.params,
                *state,
                self._dev(lens),
                row,
                # the key is on the device: only a mesh wants it replicated
                key if self._repl is None else self._dev(key),
            )
            if self._state is not None:
                self._state = state.pop(0)
            if self.cache is not None:
                self.cache.pools = state.pop(0)
        return self._toks, logits

    def decode_collect(self, toks):
        """The sampled tokens of a dispatched step on the host, (B,)
        np.int32: the read is what waits for the device."""
        with span("decode.wait"):
            return np.asarray(toks)

    # -- serving layout (ServeConfig.serve_layout) -------------------------

    def _init_layout(self, scfg) -> None:
        """Resolve ``scfg.serve_layout`` into the replica's serving mesh
        and place ``self.params`` through the family's spec rulebook
        (parallel/sharding.py::serve_param_specs — tp over heads/ffn,
        fsdp ZeRO-style, exactly the train-side placements). The empty
        layout is a strict no-op: single-chip engines never touch a
        mesh, so every existing parity anchor runs byte-identical code.
        Adapters that support layouts call this before building pools;
        the engine rejects ``serve_layout`` for families that don't."""
        self.mesh = None
        self._repl = None
        if not scfg.serve_layout or not self.supports_layout:
            return
        from jax.sharding import NamedSharding, PartitionSpec

        from fms_fsdp_tpu.parallel.sharding import (
            build_serve_mesh,
            serve_param_specs,
            shard_params,
        )

        self.mesh = build_serve_mesh(scfg.serve_layout)
        if self.mesh is None:  # "tp=1" etc: explicit single-chip
            return
        self.params = shard_params(
            self.params, serve_param_specs(self.family), self.mesh
        )
        self._repl = NamedSharding(self.mesh, PartitionSpec())

    def _pool_shardings(self, value_shape):
        """NamedShardings for pool leaves of ``value_shape`` =
        (L, num_pages, page_size, Nkv, H): kv-heads over the tensor
        axis (serve_kv_pool_specs). None single-chip — the pool then
        builds exactly as before."""
        if self.mesh is None:
            return None
        from fms_fsdp_tpu.parallel.sharding import (
            named_sharding,
            serve_kv_pool_specs,
        )

        specs = serve_kv_pool_specs(self.scfg.kv_quant)
        return {
            name: named_sharding(
                self.mesh,
                spec,
                value_shape[:-1] + (1,)
                if name.endswith("_scale")
                else value_shape,
            )
            for name, spec in specs.items()
        }

    def _dev(self, x):
        """Host array -> device, replicated over the serving mesh when
        one exists (page tables, seq lens, tokens, rng keys — the small
        per-step inputs every mesh device reads whole). Single-chip:
        plain jnp.asarray, the historical path."""
        import jax
        import jax.numpy as jnp

        x = jnp.asarray(x)
        if self._repl is not None:
            x = jax.device_put(x, self._repl)
        return x

    # -- disaggregation (generic paged implementation) ---------------------

    def export_handoff(self, rid: int, slot: "Optional[int]" = None):
        """Read rid's transferable decode state: returns (header
        fields, leaf arrays) for serve/disagg/handoff.py::pack_handoff.
        The generic implementation ships the sequence's KV pages in
        storage dtype; the engine adds the sampling fields (prompt,
        generated) before packing. ``slot`` is the stream's batch slot
        — unused here (the page set is keyed by rid), required by
        families with slot-indexed state (the mamba slab)."""
        assert self.supports_handoff and self.cache is not None, (
            f"{self.family} does not support page handoff"
        )
        from fms_fsdp_tpu.serve.disagg.handoff import PAGE_CODEC_VERSION

        cache = self.cache
        return (
            {
                "family": self.family,
                "codec": "pages",
                "codec_version": PAGE_CODEC_VERSION,
                "quant": cache.quant,
                "page_size": cache.page_size,
                "n_kv_heads": cache.n_kv_heads,
                "head_dim": cache.head_dim,
                "n_layers": cache.n_layers,
                "alloc_tokens": cache.tokens_of(rid),
            },
            cache.gather_pages(rid),
        )

    def check_handoff_header(self, header) -> None:
        """Raise HandoffError when a handoff's pool geometry does not
        match this replica's — a fleet whose prefill and decode replicas
        disagree on model config / ServeConfig is misconfigured, not out
        of capacity, so this is a typed error, not a deferral. Called at
        submit (fail the resume at the door) and again by
        ``import_handoff`` (belt and braces for direct callers)."""
        from fms_fsdp_tpu.serve.disagg import HandoffError
        from fms_fsdp_tpu.serve.disagg.handoff import (
            PAGE_CODEC_VERSION,
            check_codec_version,
        )

        assert self.supports_handoff and self.cache is not None, (
            f"{self.family} does not support page handoff"
        )
        check_codec_version(header, "pages", PAGE_CODEC_VERSION)
        cache = self.cache
        for field, mine in (
            ("family", self.family),
            ("quant", cache.quant),
            ("page_size", cache.page_size),
            ("n_kv_heads", cache.n_kv_heads),
            ("head_dim", cache.head_dim),
            ("n_layers", cache.n_layers),
        ):
            if header.get(field) != mine:
                raise HandoffError(
                    f"handoff {field}={header.get(field)!r} does not "
                    f"match this replica's {field}={mine!r}: prefill "
                    f"and decode replicas must share one model config "
                    f"and ServeConfig pool geometry"
                )

    def import_handoff(self, rid: int, slot: int, header, arrays) -> bool:
        """The receiving half: allocate rid's pages in this pool and
        scatter the shipped leaves in, bit-exact. Returns False when the
        pool cannot hold them right now (the engine defers/evicts, same
        contract as ``grow``)."""
        self.check_handoff_header(header)
        return self.cache.scatter_pages(
            rid, arrays, int(header["alloc_tokens"])
        )

    @property
    def pages_in_use(self) -> int:
        return self.cache.pages_in_use if self.cache is not None else 0

    @property
    def pages_total(self) -> int:
        """The pages streams can hold: the pool's less the reserved."""
        if self.cache is None:
            return 0
        return self.cache.capacity_tokens // self.cache.page_size

    @property
    def state_bytes_per_stream(self) -> int:
        """Constant per-stream recurrent-state bytes (0 for families
        whose only decode state is paged KV — that grows, and is
        reported through kv pages instead)."""
        return 0

    def _upload_table(self, slot_rids) -> None:
        """The device copy of the page table (``self._table_dev``), made
        again only when the allocator or the slots' membership changed:
        steady-state decode re-uploads nothing. Under the
        ``decode.table`` span; counts ``serve.page_table_uploads``."""
        tkey = (self.cache.table_version, tuple(slot_rids))
        stale = tkey != self._table_key
        with span("decode.table", uploaded=int(stale)):
            if stale:
                self._table_key = tkey
                self._table_dev = self._dev(
                    self.cache.page_table(list(slot_rids), self.max_pages)
                )
                self.registry.counter("serve.page_table_uploads").add()

    def _prefill_fields(self, key) -> dict:
        """The family's fields of the ``prefill.dispatch`` span of the
        program of ``key`` (what it built, like ``_dispatch_fields``)."""
        return {}


class PagedAdapter(FamilyAdapter):
    """The prefill hooks of a family whose every layer keeps K/V pages
    and whose model prefill is ``_model_prefill(params, tokens, cfg=,
    max_seq_len=, compute_dtype=, full_logits=) -> (logits, embeds,
    kv)`` (llama, mixtral). A prompt that fills its bucket takes the
    program that returns the last position's logits alone
    (``full_logits`` False), so a bucket has up to two programs."""

    _model_prefill = None
    _draft_seed = None  # set by a speculative llama's prefill

    def _prefill_key(self, p: int, p_pad: int, kv_len: int):
        return (p_pad, kv_len, p_pad != p)

    def _build_prefill(self, key):
        import jax

        _, kv_len, full_logits = key
        return jax.jit(
            partial(
                self._model_prefill,
                cfg=self.model_cfg,
                max_seq_len=kv_len,
                compute_dtype=self.compute_dtype,
                full_logits=full_logits,
            )
        )

    def _call_prefill(self, fn, toks, p: int):
        logits, embeds, kv = fn(self.params, self._dev(toks))
        p_pad = toks.shape[1]
        if self.speculative:
            # the hidden state that produced this stream's first token
            # (llama.py::prefill seeds the draft chain with it)
            self._draft_seed = embeds[0, p - 1]
        # logits of the last REAL position predict the next token
        row = logits[0, p - 1] if p_pad != p else logits[0, 0]
        return row, kv, None, p_pad


def kernel_or_reference(scfg) -> str:
    """``"kernel"`` or ``"reference"`` for a family whose decode attention
    has a ragged paged kernel: ``auto`` takes the kernel on a TPU and the
    gathered form elsewhere."""
    import jax

    if scfg.attn_impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "reference"
    return scfg.attn_impl


def sequence_prefill_attn_impl(scfg) -> str:
    """A sequence prefill's name (``auto``, ``pallas``, ``xla``) for
    ``scfg.attn_impl``."""
    return {"auto": "auto", "kernel": "pallas"}.get(scfg.attn_impl, "xla")


class HeldExpertsAdapter(FamilyAdapter):
    """What the adapters of the families that run models/moe_held.py
    share (sarvam, kexaone, lfm2): the ``moe_impl`` rule, which loop the decode
    program runs over the held experts (``moe_form``, the gauge
    ``serve.moe_expert_reads_per_layer``), the gauges of the share, and
    the count of the (token, choice) pairs a prefill routed, of those
    that landed on a held expert, of the trips the grouped product's
    loop took for them and of the row tiles a product of those trips
    met. ``model_cfg`` has ``top_k``, ``held``, ``num_experts`` and
    ``n_moe_layers``; the family's ``_call_prefill`` leaves the prefill
    program's own counts (pairs held, trips, row tiles), on the device
    until read, in ``self._program_counts``."""

    def _init_held_experts(self) -> None:
        from fms_fsdp_tpu.models.mixtral import routed_moe_form

        cfg, scfg = self.model_cfg, self.scfg
        self.moe_impl = moe_impl = scfg.moe_impl
        if moe_impl not in ("routed", "dense"):
            raise ValueError(
                f"unknown moe_impl {moe_impl!r}: {self.family} serving "
                "supports 'routed' (decode reads each held expert once or "
                "one a routed pair, prefill groups the pairs by held "
                "expert) or 'dense' (every held expert over every row, "
                "the parity mode)"
            )
        # which loop the decode program runs over the held experts and the
        # expert copies it reads in each layer: facts of its shape
        pairs, held = scfg.max_batch * cfg.top_k, cfg.held[1]
        routed = moe_impl == "routed"
        self.moe_form = routed_moe_form(pairs, held) if routed else "dense"
        self.moe_expert_reads_per_layer = min(pairs, held) if routed else held
        self._dispatch_fields = {"moe_form": self.moe_form}
        self.registry.gauge("serve.moe_experts_held").set(held)
        self.registry.gauge("serve.moe_experts_published").set(
            cfg.num_experts
        )

    def _count_prefill(self, rid: int, computed: int, program_counts) -> None:
        """Beside the positions computed: the (token, choice) pairs they
        routed, those that landed on a held expert, and the trips the
        grouped product's loop took for them: one a MoE layer and chunk
        where the landed pairs fit a slab
        (models/moe_held.py::grouped_slab), more where the routing was
        skewed onto the experts held; and the (group, row tile) meetings
        a grouped product of those trips ran (``_moe_grouped``'s count:
        times the tile's rows, ``grouped_tile_rows`` of the program's
        chunk, the rows a product multiplied, of which the pairs held
        are the ones stored). The
        program's own counts, read behind the stream's first token; the
        dense form weighs every pair, counts none and takes no trip."""
        cfg = self.model_cfg
        routed = computed * cfg.top_k * cfg.n_moe_layers
        held, slabs, tiles = (
            map(int, program_counts) if self.moe_impl == "routed"
            else (0, 0, 0)
        )
        self.registry.counter("serve.moe_pairs_routed").add(routed)
        self.registry.counter("serve.moe_pairs_held").add(held)
        self.registry.counter("serve.moe_slabs").add(slabs)
        self.registry.counter("serve.moe_row_tiles").add(tiles)
        super()._count_prefill(
            rid, computed, moe_pairs_routed=routed, moe_pairs_held=held,
            moe_slabs=slabs, moe_row_tiles=tiles,
        )


__all__ = [
    "FAMILY_CODES",
    "FAMILY_NAMES",
    "FamilyAdapter",
    "HeldExpertsAdapter",
    "PagedAdapter",
    "block_paged_geometry",
    "check_params_family",
    "family_of",
    "init_params_for",
    "jit_prefill",
    "kernel_or_reference",
    "load_model_config",
    "paged_geometry",
    "program_len",
    "resolve_adapter",
    "sequence_prefill_attn_impl",
    "slot_writer",
]
