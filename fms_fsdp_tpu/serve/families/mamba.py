"""Mamba family adapter: constant-memory recurrent decode.

A stream's decode state is a fixed-size slab (models/mamba.py::
init_mamba_decode_state): per mamba layer the conv window plus the fp32
carried state, shaped by the mixer kind (``slab_shapes``): Mamba-2
``(d_conv-1, d_inner + 2*G*N)`` and ``(H, headdim, d_state)``, Mamba-1
(the Jamba hybrids) ``(d_conv-1, d_inner)`` and ``(d_state, d_inner)``.
No paging, no growth — ``grow`` is always True and the slab
bytes a stream holds (``state_bytes_per_stream``) are constant in
generated length, which is the family's headline property
(tests/test_serving_families.py pins it against llama's growing
``kv_pages_in_use``).

Prefill follows the mixer too (models/mamba.py::mamba_prefill): a
Mamba-1 prompt goes through the stack as a sequence, a chunk of
positions at a time in a loop inside its bucket's program that stops at
the prompt's length (the positions it ran are counted in
``prefill_computed_tokens``), and hands over the last chunk's state; a
Mamba-2 prompt scans the decode step over its positions.
Both programs are functions of the two configs alone
(``decode_program``, ``prefill_program``), so anyone can build them
again and read their compiled HLO.

Hybrid configs (attn_layer_idx non-empty) ride the existing PagedKVCache
for their attention layers — page accounting, LIFO eviction and
recompute-on-resume behave exactly like llama, just over n_attn layers
instead of all of them.

Slab lifecycle: ``release`` zeroes the slot's slab slice (eviction,
expiry and completion all land there), and the jitted decode step masks
its state writes to live rows, so an idle slot's slab stays exactly
zero between streams — recompute-on-resume then re-prefills the full
resumed prompt into a clean slice.

Handoff: a stream's slab slice travels through the mamba slab codec
(serve/disagg/slab.py) — per mamba layer the conv window (compute
dtype) and the fp32 SSD state, plus the hybrid attention layers' KV
pages via the shared paged pool — in the same FMSH-framed versioned
wire format llama/mixtral use for pages. That enables disaggregated
prefill/decode for mamba and, more importantly, drain-and-migrate: a
SIGTERM'd replica packs its live mamba streams and ships them to
siblings at zero recompute cost (docs/serving.md "Streaming transport
& drain").
"""

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fms_fsdp_tpu.models.generation import sample_token
from fms_fsdp_tpu.models.mamba import (
    init_mamba_decode_state,
    mamba_decode_step,
    mamba_prefill,
    mamba_state_bytes_per_stream,
    prefill_positions,
    slab_shapes,
)
from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops.selective_scan import scan_step_form
from fms_fsdp_tpu.serve.disagg.slab import (
    SLAB_CODEC_VERSION,
    check_slab_header,
    pack_slab_leaves,
    split_slab_leaves,
)
from fms_fsdp_tpu.serve.families import (
    FamilyAdapter,
    jit_prefill,
    paged_geometry,
    slot_writer,
)


def page_geometry(model_cfg, scfg):
    """``(page_size, max_pages, num_pages)`` of the paged cache a hybrid
    engine builds for its attention layers (untuned: 16 tokens a page
    unless ``scfg.page_size`` pins it)."""
    a = model_cfg.attn_cfg
    page_size, _, _, max_pages, num_pages = paged_geometry(
        scfg, a.num_heads, a.num_heads_kv, a.head_dim, tuned=False
    )
    return page_size, max_pages, num_pages


def ssm_form(model_cfg, slots: int) -> str:
    """How a decode step of ``slots`` slots steps a Mamba-1 layer's scan
    state (``ops/selective_scan.py::scan_step_form``: ``"kernel"``, in
    place, or ``"jnp"``); ``"jnp"`` for a Mamba-2 stack, whose step is
    plain jax."""
    if not model_cfg.mamba1:
        return "jnp"
    return scan_step_form(slots, model_cfg.d_state, model_cfg.d_inner)


@scoped("ssm_scan")
def _mask_state(new, old, live, in_place: bool = False):
    """The slab after a step: the new rows where ``live``, the old rows
    elsewhere (the end of the state's update, so under its scope).
    ``in_place``: the scan's state was stepped where it lay and its dead
    rows were never moved, so only the conv windows are selected."""

    def select(n, o):
        return jnp.where(
            live.reshape((o.shape[0],) + (1,) * (n.ndim - 1)), n, o
        )

    if in_place:
        return [
            dict(n, conv=select(n["conv"], o["conv"])) if n else n
            for n, o in zip(new, old)
        ]
    return jax.tree.map(select, new, old)


def decode_program(model_cfg, scfg, page_size: int, compute_dtype):
    """The jitted decode step of a mamba engine: one recurrent step over
    ``scfg.max_batch`` slots and the sampler, slab (and pools) donated.
    A function of the two configs alone (families/mixtral.py::
    decode_program says why); the traced function keeps the name
    ``_step``, so the profiler shows the program as ``jit__step``.

    Hybrid: ``(params, state, pools, page_table, seq_lens, tokens, key)
    -> (tokens (B,) int32, logits (B, V), state, pools)``; without
    attention layers the pools and the table drop out of both."""
    cfg = model_cfg
    in_place = ssm_form(cfg, scfg.max_batch) == "kernel"

    def step(params, state, pools, page_table, seq_lens, tokens):
        """The model's step and the state with its dead rows (lens 0: a
        prompt is never empty) as they were: idle rows must not smear
        garbage into released, zeroed slab slices."""
        logits, new_state, pools = mamba_decode_step(
            params, state, pools, page_table, seq_lens, tokens, cfg,
            page_size=page_size, compute_dtype=compute_dtype,
            live=seq_lens > 0 if in_place else None,
        )
        state = _mask_state(new_state, state, seq_lens > 0, in_place)
        return logits, state, pools

    def sample(logits, key):
        tok = sample_token(
            logits, key, scfg.temperature, scfg.top_k, scfg.do_sample
        )
        return tok.astype(jnp.int32)

    if cfg.attn_layer_idx:

        def _step(params, state, pools, page_table, seq_lens, tokens, key):
            logits, state, pools = step(
                params, state, pools, page_table, seq_lens, tokens
            )
            return sample(logits, key), logits, state, pools

        return jax.jit(_step, donate_argnums=(1, 2))

    def _step(params, state, seq_lens, tokens, key):
        logits, state, _ = step(params, state, None, None, seq_lens, tokens)
        return sample(logits, key), logits, state

    return jax.jit(_step, donate_argnums=(1,))


def prefill_program(model_cfg, scfg, p_pad: int, kv_len: int, compute_dtype):
    """The jitted prefill of one padded prompt length: ``(params, tokens
    (1, p_pad), lengths (1,)) -> (logits (1, V), slab rows, kv)``. The
    traced function is named by the length, so the profiler shows each
    shape's program under its own name, ``jit__prefill_<p_pad>``."""
    return jit_prefill(
        p_pad, mamba_prefill, model_cfg, compute_dtype=compute_dtype,
        kv_len=kv_len,
        attn_impl="auto" if scfg.attn_impl == "auto" else "xla",
    )


class MambaAdapter(FamilyAdapter):
    family = "mamba"
    supports_handoff = True  # via the slab codec, not the page codec
    _pages_noun = "attn pages"

    def _setup(self) -> None:
        cfg, scfg = self.model_cfg, self.scfg
        self._hybrid = bool(cfg.attn_layer_idx)

        if scfg.serve_layout:
            raise ValueError(
                "mamba serving has no sharded layout yet: the recurrent "
                "slab (conv window + SSD state) has no sharding rulebook"
                " — run mamba replicas single-chip (serve_layout=\"\") "
                "and scale them out data-parallel through the fleet "
                "router"
            )
        if scfg.attn_impl == "kernel":
            raise ValueError(
                "mamba serving has no paged-attention kernel path yet: "
                "set attn_impl to 'auto' or 'reference' (the recurrent "
                "mixer is not attention; hybrid attn layers decode "
                "through the reference gqa_attend)"
            )
        if scfg.kv_quant != "none":
            raise ValueError(
                "mamba serving stores its recurrent slab unquantized and "
                "hybrid attn pages full-width: set kv_quant='none'"
            )
        if scfg.speculator_path:
            raise ValueError(
                "mamba serving has no speculative decode path yet: the "
                "MLPSpeculator draft/verify loop is llama-only (the "
                "verify step replays positions through paged KV, which "
                "the recurrent slab cannot roll back) — unset "
                "speculator_path"
            )
        self.attn_impl = "reference" if self._hybrid else "none"
        # how the decode program steps a Mamba-1 layer's scan state
        self.ssm_form = ssm_form(cfg, scfg.max_batch)
        self._dispatch_fields = {"ssm_form": self.ssm_form}

        if self._hybrid:
            a = cfg.attn_cfg
            self._init_pages(
                len(cfg.attn_layer_idx),
                a.num_heads,
                a.num_heads_kv,
                a.head_dim,
                tuned=False,
            )

        # the whole fleet of slots steps as one fixed-shape batch: one
        # slab covering max_batch streams, donated through the jit so
        # the update is in-place
        self._state = init_mamba_decode_state(
            cfg, scfg.max_batch, self.compute_dtype
        )
        self._decode_fn = decode_program(
            cfg, scfg, self.page_size, self.compute_dtype
        )
        self.ssm_layers = cfg.n_layer - len(cfg.attn_layer_idx)

        # one stream's rows into its slot of the slab (and zeros, on
        # release)
        self._write_slot = slot_writer(0)
        self._zero_rows = jax.tree.map(
            lambda s: jnp.zeros((1,) + s.shape[1:], s.dtype), self._state
        )

    def _release_state(self, rid: int, slot: int) -> None:
        # zero the slab slice: an idle slot must hold no residue of the
        # evicted stream (and the decode step's live-mask keeps it zero)
        self._state = self._write_slot(
            self._state, self._zero_rows, np.int32(slot)
        )

    # -- prefill: one program a padded length, told the prompt's length ----

    def _prefill_key(self, p: int, p_pad: int, kv_len: int):
        return (p_pad, kv_len)

    def _build_prefill(self, key):
        return prefill_program(
            self.model_cfg, self.scfg, *key, self.compute_dtype
        )

    def _call_prefill(self, fn, toks, p: int):
        logits, rows, kv = fn(
            self.params, jnp.asarray(toks), jnp.asarray([p], np.int32)
        )
        # prefill already selects each row's last real position
        return (
            logits[0],
            kv if self._hybrid else None,
            rows,
            prefill_positions(self.model_cfg, p, toks.shape[1]),
        )

    # -- disaggregation: the slab codec (serve/disagg/slab.py) -------------

    def _slab_geometry(self) -> Dict:
        """The geometry fields the slab header carries and
        check_handoff_header compares — JSON-native types only (the
        header round-trips through canonical JSON)."""
        cfg = self.model_cfg
        geo = {
            "family": self.family,
            "compute_dtype": jnp.dtype(self.compute_dtype).name,
            "n_layer": int(cfg.n_layer),
            "attn_layers": sorted(int(i) for i in cfg.attn_layer_idx),
            "ssm_layer": cfg.ssm_layer,
            "conv_shape": [int(d) for d in slab_shapes(cfg)[0]],
            "ssd_shape": [int(d) for d in slab_shapes(cfg)[1]],
        }
        if self._hybrid:
            geo.update(
                quant=self.cache.quant,
                page_size=self.cache.page_size,
                n_kv_heads=self.cache.n_kv_heads,
                head_dim=self.cache.head_dim,
                n_attn_layers=self.cache.n_layers,
            )
        return geo

    def export_handoff(self, rid: int, slot: Optional[int] = None):
        assert slot is not None, "mamba slab export needs the stream's slot"
        layer_states = {
            i: {
                "conv": np.asarray(layer["conv"][slot]),
                "ssd": np.asarray(layer["ssd"][slot]),
            }
            for i, layer in enumerate(self._state)
            if layer
        }
        kv = self.cache.gather_pages(rid) if self._hybrid else None
        header = dict(self._slab_geometry())
        header.update(
            codec="mamba_slab",
            codec_version=SLAB_CODEC_VERSION,
            alloc_tokens=self.cache.tokens_of(rid) if self._hybrid else 0,
        )
        return header, pack_slab_leaves(layer_states, kv)

    def check_handoff_header(self, header) -> None:
        check_slab_header(header, self._slab_geometry())

    def import_handoff(self, rid: int, slot: int, header, arrays) -> bool:
        from fms_fsdp_tpu.serve.disagg.handoff import HandoffError

        self.check_handoff_header(header)
        layer_states, kv = split_slab_leaves(arrays)
        # validate everything validatable BEFORE any allocation: a
        # frame rejected after pages/slab were touched must not leak
        expected_layers = {
            i for i, layer in enumerate(self._state) if layer
        }
        if set(layer_states) != expected_layers:
            raise HandoffError(
                f"slab frame covers layers {sorted(layer_states)}; "
                f"this replica's mamba layers are "
                f"{sorted(expected_layers)}"
            )
        for i in expected_layers:
            for part in ("conv", "ssd"):
                want = tuple(
                    int(d) for d in self._state[i][part].shape[1:]
                )
                got = tuple(layer_states[i][part].shape)
                if got != want:
                    raise HandoffError(
                        f"slab leaf layer {i} {part!r} has shape "
                        f"{got}, this replica expects {want}"
                    )
        if self._hybrid:
            if not kv:
                raise HandoffError(
                    "hybrid mamba handoff is missing its attention-"
                    "layer 'kv.*' page leaves"
                )
            if not self.cache.scatter_pages(
                rid, kv, int(header["alloc_tokens"])
            ):
                return False  # pool full right now: engine defers
        elif kv:
            raise HandoffError(
                "non-hybrid mamba handoff carries attention page "
                "leaves this replica has no pool for"
            )
        try:
            new_state = list(self._state)
            for i in expected_layers:
                layer = new_state[i]
                new_state[i] = {
                    "conv": layer["conv"].at[slot].set(
                        jnp.asarray(
                            layer_states[i]["conv"], layer["conv"].dtype
                        )
                    ),
                    "ssd": layer["ssd"].at[slot].set(
                        jnp.asarray(layer_states[i]["ssd"], jnp.float32)
                    ),
                }
            self._state = new_state
        except Exception as e:
            # free the decode-side pages and re-zero the slab slice
            # this import touched — pool accounting must return to its
            # pre-import value
            if self._hybrid:
                self.cache.free(rid)
            self._state = self._write_slot(
                self._state, self._zero_rows, np.int32(slot)
            )
            raise HandoffError(
                f"slab import failed after allocation (pages freed, "
                f"slab slice zeroed): {e}"
            ) from e
        return True

    # -- obs ---------------------------------------------------------------

    @property
    def state_bytes_per_stream(self) -> int:
        return mamba_state_bytes_per_stream(
            self.model_cfg, self.compute_dtype
        )

    @property
    def slab(self):
        """The whole slab: list over layers of {"conv", "ssd"} with a
        leading slot axis ({} for hybrid attn layers). Donated into every
        decode step, so a reference kept across one goes stale."""
        return self._state

    def slab_slice(self, slot: int):
        """The slot's slab (debug/tests): list over layers of {"conv",
        "ssd"} rows ({} for hybrid attn layers)."""
        return jax.tree.map(lambda s: s[slot], self._state)
