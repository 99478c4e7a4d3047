"""Family adapters: one serving engine, three model families.

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

from typing import Optional

from fms_fsdp_tpu.models.configs import (
    LlamaConfig,
    MambaConfig,
    MixtralConfig,
)
from fms_fsdp_tpu.obs.spans import span

# the wire encoding of a family in numeric-only maps (obs schema v12
# "serving", BENCH_SERVING.json rows): family = FAMILY_CODES[name]
FAMILY_CODES = {"llama": 0, "mamba": 1, "mixtral": 2}
FAMILY_NAMES = {v: k for k, v in FAMILY_CODES.items()}

_CONFIG_FAMILIES = (
    (MambaConfig, "mamba"),
    (MixtralConfig, "mixtral"),
    (LlamaConfig, "llama"),
)


def family_of(model_cfg) -> str:
    """Model config dataclass -> family name."""
    for cls, name in _CONFIG_FAMILIES:
        if isinstance(model_cfg, cls):
            return name
    raise ValueError(
        f"unknown model config type {type(model_cfg).__name__}: expected "
        f"LlamaConfig, MambaConfig or MixtralConfig "
        f"(fms_fsdp_tpu/models/configs.py)"
    )


def load_model_config(d: dict):
    """Plain dict (a fleet model_cfg.json) -> the right config dataclass.

    An explicit ``"family"`` key wins; otherwise the family is inferred
    from architecture-distinguishing keys (``d_model`` -> mamba,
    ``num_experts`` -> mixtral, else llama). A published Jamba
    ``config.json`` (``"model_type": "jamba"``, or ``"family": "jamba"``)
    resolves to the mamba family through its own key mapping. This is the single
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
    if isinstance(layers, (list, tuple)):
        actual = "mamba"
    elif isinstance(layers, dict) and "gate" in layers:
        actual = "mixtral"
    elif isinstance(layers, dict) and "wq" in layers:
        actual = "llama"
    else:
        raise ValueError(
            "params do not look like any serveable family (no "
            "recognizable 'layers' structure): expected init_llama_params"
            " / init_mamba_params / init_mixtral_params output or a "
            "checkpoint thereof"
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
    from fms_fsdp_tpu.models.llama import init_llama_params

    return lambda key: init_llama_params(key, model_cfg)


def resolve_adapter(params, model_cfg, serve_cfg, compute_dtype=None):
    """Checkpoint + config -> the family's adapter (jax imports here)."""
    family = family_of(model_cfg)
    check_params_family(params, family)
    if family == "mamba":
        from fms_fsdp_tpu.serve.families.mamba import MambaAdapter

        return MambaAdapter(params, model_cfg, serve_cfg, compute_dtype)
    if family == "mixtral":
        from fms_fsdp_tpu.serve.families.mixtral import MixtralAdapter

        return MixtralAdapter(params, model_cfg, serve_cfg, compute_dtype)
    from fms_fsdp_tpu.serve.families.llama import LlamaAdapter

    return LlamaAdapter(params, model_cfg, serve_cfg, compute_dtype)


class FamilyAdapter:
    """The protocol (docs/serving.md "Family adapters" has the table).

    The engine owns scheduling, sampling, rng and metrics; the adapter
    owns every family-specific device interaction:

    - ``admission_error(prompt_len, max_new)`` — worst-case capacity
      check at submit; a message means reject (reason=too_large).
    - ``can_admit(rid, prompt_len)`` — would a prefill of this resumed
      prompt fit right now (pre-admission, nothing allocated)?
    - ``prefill(rid, slot, prompt)`` — allocate the stream's state,
      run the family prefill, write slot state; returns the (V,)
      logits row of the last real prompt position.
    - ``grow(rid, n_tokens)`` — make room for the next token; False
      triggers the engine's LIFO eviction loop. Constant-state
      families always return True.
    - ``release(rid, slot)`` — return the stream's state (free pages /
      zero the slab slice). Eviction, expiry and completion all land
      here; recompute-on-resume re-prefills into whatever slot comes
      next.
    - ``decode(slot_rids, lens, tokens, key)`` — one jitted ragged
      decode step over all max_batch slots; returns (sampled tokens
      (B,) np.int32, logits (B, V)). The adapter owns donation and
      page-table upload caching.
    - ``pages_in_use`` / ``state_bytes_per_stream`` — obs.

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
    page_size: int = 0
    max_pages: int = 0
    attn_impl: str = "none"
    block_kv: int = 0
    tune_how: str = "n/a"
    mesh = None  # the serving mesh when serve_layout is set, else None
    supports_handoff: bool = False
    supports_layout: bool = False
    # speculative serving (ServeConfig.speculator_path): the adapter
    # flips ``speculative`` when it loaded a draft head; the engine then
    # routes through ``decode_spec`` and budgets ``spec_draft_tokens``
    # extra cache positions per stream for in-flight draft writes
    speculative: bool = False
    spec_draft_tokens: int = 0
    # chunked prefill (ServeConfig.prefill_chunk_tokens): families that
    # can advance a prompt in slices through prefill_start/prefill_chunk
    # set this; the engine rejects the knob for the rest at build
    supports_chunked_prefill: bool = False
    # what the adapter did that only it can see, counted where it
    # happens; the engine adds each step's difference to its registry
    # (serve.prefill_programs_built, serve.page_table_uploads)
    prefill_programs_built: int = 0
    page_table_uploads: int = 0
    # expert weight copies one decode step reads in each layer (the gauge
    # serve.moe_expert_reads_per_layer); 0 for a family with no experts
    moe_expert_reads_per_layer: int = 0
    # layers that keep a recurrent slab slice per stream (the gauge
    # serve.ssm_layers), and the prefills that wrote one
    # (serve.prefill_state_writes); 0 for a family with no such state
    ssm_layers: int = 0
    prefill_state_writes: int = 0
    # positions the prefill programs computed (serve.prefill_computed_
    # tokens): the padded tokens, but for a prefill that stops at the
    # prompt's length inside its bucket (mamba.py's Mamba-1 loop)
    prefill_computed_tokens: int = 0

    def admission_error(self, prompt_len: int, max_new: int) -> Optional[str]:
        raise NotImplementedError

    def can_admit(self, rid: int, prompt_len: int) -> bool:
        raise NotImplementedError

    def prefill(self, rid: int, slot: int, prompt):
        raise NotImplementedError

    def grow(self, rid: int, n_tokens: int) -> bool:
        raise NotImplementedError

    def release(self, rid: int, slot: int) -> None:
        raise NotImplementedError

    def decode(self, slot_rids, lens, tokens, key):
        raise NotImplementedError

    # -- speculative decode (ServeConfig.speculator_path) ------------------

    def decode_spec(self, slot_rids, lens, tokens):
        """One draft-then-verify step over all slots: propose
        ``spec_draft_tokens`` tokens per row, score them in one jitted
        verify forward, commit the longest greedy-matching prefix.
        Returns (emit (B, n+1) np.int32, counts (B,) np.int32, logits
        (B, V) of each row's committed position) — row b's new tokens
        are ``emit[b, :counts[b]]``."""
        raise NotImplementedError

    # -- chunked prefill (ServeConfig.prefill_chunk_tokens) ----------------

    def prefill_start(self, rid: int, slot: int, prompt) -> None:
        """Allocate the stream's state and stage ``prompt`` for
        incremental prefill; no forward runs yet."""
        raise NotImplementedError

    def prefill_chunk(self, rid: int):
        """Advance a staged prefill by one chunk. Returns None while
        incomplete; on the final chunk, commits the state and returns
        the (V,) logits row of the last real prompt position —
        bit-identical to what whole-prompt ``prefill`` returns."""
        raise NotImplementedError

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
        if getattr(self, "mesh", None) is None:
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
        if getattr(self, "_repl", None) is not None:
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
    def state_bytes_per_stream(self) -> int:
        """Constant per-stream recurrent-state bytes (0 for families
        whose only decode state is paged KV — that grows, and is
        reported through kv pages instead)."""
        return 0

    def _upload_table(self, slot_rids) -> None:
        """The device copy of the page table (``self._table_dev``), made
        again only when the allocator or the slots' membership changed:
        steady-state decode re-uploads nothing. Under the
        ``decode.table`` span; counts ``page_table_uploads``."""
        tkey = (self.cache.table_version, tuple(slot_rids))
        stale = tkey != self._table_key
        with span("decode.table", uploaded=int(stale)):
            if stale:
                self._table_key = tkey
                self._table_dev = self._dev(
                    self.cache.page_table(list(slot_rids), self.max_pages)
                )
                self.page_table_uploads += 1

    def _padded_len(self, n: int, bucket: int) -> int:
        b = max(1, bucket)
        return -(-n // b) * b


__all__ = [
    "FAMILY_CODES",
    "FAMILY_NAMES",
    "FamilyAdapter",
    "check_params_family",
    "family_of",
    "init_params_for",
    "load_model_config",
    "resolve_adapter",
]
