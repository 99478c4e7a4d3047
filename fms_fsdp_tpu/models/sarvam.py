"""Sarvam (``model_type: sarvam_mla``): multi-head latent attention over
a mixture of many small experts. The serving family's model file:
forward, sequence prefill and the paged decode step.

**Attention, every layer** (DeepSeek-V2's MLA with no query
compression). For the normed input ``x_t``: ``q_t = W_q x_t``, ``nheads``
heads of ``qk_nope_head_dim + qk_rope_head_dim``; ``[c_t ; kr_t] = W_kva
x_t`` with ``c_t`` of ``kv_lora_rank`` and one ``kr_t`` of
``qk_rope_head_dim`` shared by all heads; ``ĉ_t = RMSNorm(c_t)``;
``[k_nope_{t,h} ; v_{t,h}] = W_kvb,h ĉ_t``; rotary (``deepseek_yarn``
frequencies, ops/rope.py) on ``q_rope`` and ``kr``; ``score_h(t, s) =
(q_nope·k_nope + q_rope·kr) · m² / sqrt(q_head_dim)`` with ``m`` YaRN's
temperature; causal softmax; ``W_o`` over the heads' ``Σ p v``. A position
leaves ``[ĉ_s ; rope(kr_s)]`` behind, ``latent_dim`` values a layer
whatever the head count: the **latent cache** (its pool keeps them in
whole rows of 128 lanes: ``pool_width``).

Two forms, equal in exact arithmetic. *Expanded* (``sarvam_forward``,
the prefill): keys and values are made from the latent by ``W_kvb`` and
attention is plain multi-head attention with 192-wide keys and 128-wide
values. *Absorbed* (the decode step): ``q̃_h = W_kvb,h^K^T q_nope_h``
attends over the latent itself, ``score = q̃·ĉ_s + q_rope·kr_s``, ``u_h =
Σ p ĉ_s``, and the head's output is ``W_kvb,h^V u_h``: ``nheads`` query
heads on one ``latent_dim``-wide key whose first ``kv_lora_rank`` values
are also the value; ``W_kvb`` is never applied to the cache.

**Feed-forward.** The first ``first_k_dense`` layers: a dense SwiGLU.
The others: ``s = sigmoid(W_g h)`` in float32 over all ``num_experts``;
the ``top_k`` largest of ``s + b`` are chosen (the bias chooses and does
not weigh); ``w_i = routed_scaling_factor · s_i / Σ_chosen s_j``; ``y =
Σ_chosen w_i E_i(h) + S(h)`` with ``S`` the shared expert(s). **The layer
is told which experts it holds** (``cfg.held``): it routes over all of
them, computes the part of ``y`` that its own experts give plus the
shared expert, and adds nothing for the others (a chip's share under
expert parallelism, without the exchange; models/moe_held.py, which the
other family that routes this way imports too). Three forms of the held
part: over a decode step's rows the two of models/mixtral.py
(``routed_moe_form``: every held expert streamed once, or one product a
routed pair); over a prompt's chunk the (token, choice) pairs that land
on held experts are sorted by expert and multiplied group by group
(``_moe_grouped``, through ops/grouped_matmul.py), none dropped, the work
following the pairs that land here.

Read by the family's convention where ``config.json`` has no key:
sigmoid scoring with the chosen weights normalised; one routing group;
``use_qk_norm`` as the RMSNorm on the latent before ``W_kvb``; rotary
pairs interleaved in the checkpoint (``ops/rope.py::deinterleave``).

Parameter tree: ``embedding (V, D)``, ``dense_layers`` and ``layers``
(dicts of leaves stacked over the leading dense and the MoE layers),
``norm``, ``lm_head (D, V)``. ``V`` is the vocabulary rows held.
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from fms_fsdp_tpu.models import sequence_prefill as seq
from fms_fsdp_tpu.models.configs import SarvamConfig
from fms_fsdp_tpu.models.moe_held import (
    _moe_dense_held,
    _moe_grouped,
    _moe_token,
    _shared,
    _swiglu,
)
from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops import flash_attention as _fa
from fms_fsdp_tpu.ops.norms import rms_norm
from fms_fsdp_tpu.ops.pallas_mode import interpret_default
from fms_fsdp_tpu.ops.ring_attention import merge_partial
from fms_fsdp_tpu.ops.rope import (
    apply_rotary,
    deinterleave,
    yarn_mscale,
    yarn_rope_table,
)

__all__ = [
    "SarvamConfig",
    "init_sarvam_params",
    "sarvam_forward",
    "sarvam_paged_decode_step",
    "sarvam_prefill",
]

Params = Dict[str, Any]

# positions one trip of the prefill's loop takes through the stack. Every
# chunk reads every held expert once, 1.6 GB a layer at the published
# widths, and gives each about c * top_k / num_experts rows: at 2048 the
# grouped product does 128 operations a weight byte, under the v5e's
# ridge of 240, so a shorter chunk costs the experts' bytes again for
# fewer rows. A constant of the program: no option selects it.
PREFILL_CHUNK = 2048

# cache positions one trip of the decode step's attention loop gathers.
# The loop stops at the longest live stream, so a step pays for the
# positions that exist and not for ``max_seq_len``.
DECODE_BLOCK_TOKENS = 2048

LANES = 128


def pool_width(cfg: SarvamConfig) -> int:
    """The width of a position's entry in the latent pool and in the
    prefill's latent buffer: ``latent_dim`` rounded up to whole rows of
    128 lanes (576 -> 640), zeros behind the latent. The chip's (8, 128)
    tiling pads a 576-wide minor axis to 640 in memory anyway; asked for
    576 its compiler instead lays the pool out with the page index
    minor-most and relays the whole pool out, in and back, inside every
    decode step (deviceless compile, PERF.md PR 31: 2.67 GB of
    temporaries and two copies of the pool a step)."""
    return -(-cfg.latent_dim // LANES) * LANES


def init_sarvam_params(key, cfg: SarvamConfig, dtype=jnp.float32) -> Params:
    d, n, r = cfg.emb_dim, cfg.nheads, cfg.kv_lora_rank
    held = cfg.held[1]
    std = 0.02
    out_std = std / (2 * cfg.nlayers) ** 0.5
    keys = iter(jax.random.split(key, 32))

    def tn(shape, s=std):
        return (
            jax.random.truncated_normal(next(keys), -3, 3, shape, jnp.float32)
            * s
        ).astype(dtype)

    def attn(L):
        return {
            "attn_norm": jnp.ones((L, d), dtype),
            "wq": tn((L, d, n * cfg.q_head_dim)),
            "wkv_a": tn((L, d, cfg.latent_dim)),
            "kv_norm": jnp.ones((L, r), dtype),
            "wkv_b": tn((L, r, n * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
            "wo": tn((L, n * cfg.v_head_dim, d), out_std),
            "ffn_norm": jnp.ones((L, d), dtype),
        }

    Ld, Lm, f, h = (
        cfg.first_k_dense, cfg.n_moe_layers, cfg.hidden_dim,
        cfg.moe_hidden_dim,
    )
    dense = dict(
        attn(Ld),
        w1=tn((Ld, d, f)),
        w3=tn((Ld, d, f)),
        w2=tn((Ld, f, d), out_std),
    )
    moe = dict(
        attn(Lm),
        gate=tn((Lm, d, cfg.num_experts)),
        gate_bias=jnp.zeros((Lm, cfg.num_experts), dtype),
        w1=tn((Lm, held, d, h)),
        w3=tn((Lm, held, d, h)),
        w2=tn((Lm, held, h, d), out_std),
    )
    if cfg.num_shared_experts:
        hs = cfg.num_shared_experts * h
        moe.update(
            shared_w1=tn((Lm, d, hs)),
            shared_w3=tn((Lm, d, hs)),
            shared_w2=tn((Lm, hs, d), out_std),
        )
    return {
        "embedding": tn((cfg.src_vocab_size, d)),
        "dense_layers": dense,
        "layers": moe,
        "norm": jnp.ones((d,), dtype),
        "lm_head": tn((d, cfg.src_vocab_size)),
    }


# ---------------------------------------------------------------------------
# attention: what both forms share
# ---------------------------------------------------------------------------


def softmax_scale(cfg: SarvamConfig) -> float:
    """``m² / sqrt(q_head_dim)``, ``m`` YaRN's temperature over all
    dimensions."""
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return cfg.q_head_dim**-0.5 * m * m


def rope_tables(cfg: SarvamConfig, seq_len: int):
    with jax.named_scope("mla_kv_down"):
        return yarn_rope_table(
            seq_len,
            cfg.qk_rope_head_dim,
            cfg.rope_theta,
            factor=cfg.rope_factor,
            original_max_position=cfg.rope_original_max_position,
            beta_fast=cfg.rope_beta_fast,
            beta_slow=cfg.rope_beta_slow,
            mscale=cfg.rope_mscale,
            mscale_all_dim=cfg.rope_mscale_all_dim,
        )


@scoped("norm")
def _norm(x, w, cfg):
    return rms_norm(x, w, cfg.norm_eps)


@scoped("mla_q")
def _mla_q(h, layer, cfg: SarvamConfig, cos, sin, positions):
    """h (B, S, D) -> (q_nope (B, S, N, nope), q_rope (B, S, N, rope)
    rotated at ``positions`` (B, S))."""
    B, S, _ = h.shape
    q = (h @ layer["wq"]).reshape(B, S, cfg.nheads, cfg.q_head_dim)
    nope = cfg.qk_nope_head_dim
    q_rope = apply_rotary(deinterleave(q[..., nope:]), cos, sin, positions)
    return q[..., :nope], q_rope


@scoped("mla_kv_down")
def _mla_latent(h, layer, cfg: SarvamConfig, cos, sin, positions):
    """h (B, S, D) -> what the positions leave in the cache, (B, S,
    latent_dim): the normed latent, then the rotated shared key."""
    r = cfg.kv_lora_rank
    ckr = h @ layer["wkv_a"]
    c = rms_norm(ckr[..., :r], layer["kv_norm"], cfg.norm_eps)
    kr = apply_rotary(
        deinterleave(ckr[..., r:])[:, :, None, :], cos, sin, positions
    )[:, :, 0]
    return jnp.concatenate([c, kr], axis=-1)


@scoped("mla_expand")
def _mla_expand(lat, layer, cfg: SarvamConfig):
    """Keys and values of the expanded form from the latent: lat (B, S,
    latent_dim or wider, padding ignored) -> k (B, S, N, q_head_dim), v
    (B, S, N, v_head_dim)."""
    B, S, _ = lat.shape
    r, n, nope = cfg.kv_lora_rank, cfg.nheads, cfg.qk_nope_head_dim
    kv = (lat[..., :r] @ layer["wkv_b"]).reshape(
        B, S, n, nope + cfg.v_head_dim
    )
    kr = jnp.broadcast_to(
        lat[:, :, None, r : cfg.latent_dim], (B, S, n, cfg.qk_rope_head_dim)
    )
    return jnp.concatenate([kv[..., :nope], kr], axis=-1), kv[..., nope:]


def _to_pool_width(x, cfg: SarvamConfig):
    """Zeros behind the last axis up to ``pool_width``."""
    pad = pool_width(cfg) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _wkv_b_heads(layer, cfg: SarvamConfig):
    """``W_kvb`` by head: (W^K (r, N, nope), W^V (r, N, v))."""
    w = layer["wkv_b"].reshape(
        cfg.kv_lora_rank, cfg.nheads, cfg.qk_nope_head_dim + cfg.v_head_dim
    )
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _attn_partial(q, k, v, causal: bool, scale: float, flash: bool):
    """Attention of q (B, c, N, dq) over k (B, s, N, dq), v (B, s, N, dv)
    -> (normalised output (B, c, N, dv), log-sum-exp (B, c, N, 1) fp32):
    a partial that ``merge_partial`` joins with others over disjoint
    keys. Where ``flash`` the flash kernel at the widths given, an earlier
    block (not causal) in one step over its keys with no running rescale
    (PERF.md PR 32: 2.85 -> 2.58 ms a block); else an einsum."""
    if flash:
        return _fa.flash_attention(
            q, k, v, causal=causal, scale=scale, return_lse=True,
            block_k=None if causal else k.shape[1],
            interpret=interpret_default(),
        )
    s = jnp.einsum(
        "bqnd,bsnd->bnqs", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        mask = jnp.tril(jnp.ones(s.shape[-2:], dtype=bool))
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bnqs,bsnd->bqnd", p.astype(v.dtype), v)
    o = o.astype(jnp.float32) / jnp.moveaxis(l, 1, 2)
    return o, jnp.moveaxis(m + jnp.log(l), 1, 2)


def _use_flash(attn_impl: str, c: int) -> bool:
    return c % 256 == 0 and seq.kernel_wanted(attn_impl)


def prefill_attn_form(cfg: SarvamConfig, attn_impl: str, p_pad: int) -> str:
    """What ``_attn_partial`` runs in the prefill program of ``p_pad``
    positions (``attn_form`` on ``serve/prefill.dispatch``): the kernel
    with values narrower than keys (the published 192 and 128) or of one
    width, or the einsum (off a TPU, and odd chunks)."""
    if not _use_flash(attn_impl, prefill_chunk(p_pad)):
        return "einsum"
    return "flash" if cfg.q_head_dim == cfg.v_head_dim else "flash_two_width"


# ---------------------------------------------------------------------------
# feed-forward: the held experts' layer is models/moe_held.py's
# ---------------------------------------------------------------------------


@scoped("mlp")
def _mlp(h, layer):
    return _swiglu(h, layer["w1"], layer["w3"], layer["w2"])


def _layer_at(stacked, i: int):
    return jax.tree.map(lambda a: a[i], stacked)


# ---------------------------------------------------------------------------
# forward (whole sequences, no cache): the expanded form
# ---------------------------------------------------------------------------


def sarvam_forward(
    params: Params, tokens, cfg: SarvamConfig, *,
    compute_dtype=jnp.bfloat16, **_unused,
):
    """tokens (B, S) -> logits (B, S, V): expanded attention over the
    whole sequence, the held experts' dense mixture."""
    params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B, S = tokens.shape
    cos, sin = rope_tables(cfg, S)
    scale = softmax_scale(cfg)
    x = params["embedding"][tokens]

    def attend(x, layer):
        h = _norm(x, layer["attn_norm"], cfg)
        q = jnp.concatenate(_mla_q(h, layer, cfg, cos, sin, None), axis=-1)
        k, v = _mla_expand(
            _mla_latent(h, layer, cfg, cos, sin, None), layer, cfg
        )
        o, _ = _attn_partial(q, k, v, True, scale, False)
        x = x + o.astype(x.dtype).reshape(B, S, -1) @ layer["wo"]
        return x, _norm(x, layer["ffn_norm"], cfg)

    for i in range(cfg.first_k_dense):
        layer = _layer_at(params["dense_layers"], i)
        x, h2 = attend(x, layer)
        x = x + _mlp(h2, layer)

    def body(x, layer):
        x, h2 = attend(x, layer)
        return x + _moe_dense_held(h2, layer, cfg) + _shared(h2, layer), None

    x, _ = lax.scan(body, x, params["layers"])
    return _norm(x, params["norm"], cfg) @ params["lm_head"]


# ---------------------------------------------------------------------------
# prefill: the prompt as a sequence, a chunk at a time, expanded form
# ---------------------------------------------------------------------------


def prefill_chunk(p_pad: int) -> int:
    """The chunk of a prompt padded to ``p_pad``: the largest divisor of
    ``p_pad`` up to ``PREFILL_CHUNK``, so that chunks tile the bucket."""
    return seq.chunk_of(p_pad, PREFILL_CHUNK)


def prefill_positions(p: int, p_pad: int) -> int:
    """Positions ``sarvam_prefill`` computes for a prompt of ``p`` tokens
    padded to ``p_pad``: whole chunks up to the prompt's end."""
    return seq.positions_computed(p, prefill_chunk(p_pad))


def _chunk_attention(q, lat, l, layer, cfg, start, c: int, flash: bool):
    """Causal attention of the chunk's queries q (B, c, N, q_head_dim) at
    positions ``start`` to ``start + c`` over layer ``l`` of the latent
    buffer lat (L, B, kv_len, pool_width), written up to there: the
    chunk's own block under the causal mask, each earlier block whole,
    keys and values of a block made from its latent when it is met (they
    are never kept), partials merged through their log-sum-exp.
    Returns (B, c, N, v_head_dim)."""
    scale = softmax_scale(cfg)
    B = q.shape[0]

    def partial_at(at, diag):
        block = lax.dynamic_slice(
            lat, (l, 0, at, 0), (1, B, c, lat.shape[-1])
        )[0]
        k, v = _mla_expand(block, layer, cfg)
        with jax.named_scope("attn"):
            return _attn_partial(q, k, v, diag, scale, flash)

    o, lse = partial_at(start, True)

    def merge(i, carry):
        o_i, lse_i = partial_at(i * c, False)
        with jax.named_scope("attn"):
            return merge_partial(carry, o_i, lse_i)

    o, _ = lax.fori_loop(0, start // c, merge, (o.astype(jnp.float32), lse))
    return o.astype(q.dtype)


def sarvam_prefill(
    params: Params,
    tokens,
    lengths,
    cfg: SarvamConfig,
    *,
    compute_dtype=jnp.bfloat16,
    kv_len: int = 0,
    attn_impl: str = "auto",
    moe_impl: str = "routed",
):
    """Prompt prefill. tokens (B, S_pad) int32, lengths (B,) int32 the
    prompts' lengths (<= S_pad). ``prefill_chunk(S_pad)`` positions at a
    time go through every layer, in one loop whose trip count is read
    from ``lengths`` on the device; from chunk to chunk go the latent
    written so far and each row's residual at its last real position.
    ``moe_impl="routed"`` groups each chunk's pairs by held expert
    (``_moe_grouped``); ``"dense"`` runs every held expert over every
    row (the parity form).

    Returns (logits (B, V) of each row's last real position; the latent
    (L, B, kv_len, pool_width), zero past each row's length, for the
    pages; the number of (token, choice) pairs of the positions computed
    that landed on held experts, summed over the MoE layers; the trips
    the grouped product's loop took for them, one a layer and chunk
    unless its pairs overran a slab; and the row tiles a product of
    those trips met)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B, S = tokens.shape
    c = prefill_chunk(S)
    kv_len = kv_len or S
    assert kv_len >= S, (kv_len, S)
    flash = _use_flash(attn_impl, c)
    cos, sin = rope_tables(cfg, S)
    Ld = cfg.first_k_dense
    # the scan slices the MoE layers' leaves but the routed experts': the
    # grouped matmul reads those where they lie in the stack
    experts = {name: params["layers"][name] for name in ("w1", "w3", "w2")}
    rest = {
        name: leaf for name, leaf in params["layers"].items()
        if name not in experts
    }

    def body(chunk, carry):
        lat, last, pairs, slabs, tiles = carry
        start, positions = chunk.start, chunk.positions
        with jax.named_scope("embed"):
            toks = lax.dynamic_slice_in_dim(tokens, start, c, axis=1)
            x = params["embedding"][toks]

        def attend(x, lat, layer, l):
            h = _norm(x, layer["attn_norm"], cfg)
            q = jnp.concatenate(
                _mla_q(h, layer, cfg, cos, sin, positions), axis=-1
            )
            new = _mla_latent(h, layer, cfg, cos, sin, positions)
            with jax.named_scope("latent_write"):
                lat = seq.write_live(lat, new, chunk.live, start, layer=l)
            o = _chunk_attention(q, lat, l, layer, cfg, start, c, flash)
            with jax.named_scope("attn_out"):
                x = x + o.reshape(B, c, -1) @ layer["wo"]
            return x, lat, _norm(x, layer["ffn_norm"], cfg)

        for i in range(Ld):
            layer = _layer_at(params["dense_layers"], i)
            x, lat, h2 = attend(x, lat, layer, i)
            x = x + _mlp(h2, layer)

        def moe_layer(carry, inp):
            x, lat, pairs, slabs, tiles = carry
            layer, i = inp
            x, lat, h2 = attend(x, lat, layer, Ld + i)
            if moe_impl == "routed":
                y, n, trips, met = _moe_grouped(
                    h2.reshape(B * c, -1), layer, cfg, experts, i
                )
                y, pairs, slabs = y.reshape(B, c, -1), pairs + n, slabs + trips
                tiles = tiles + met
            else:
                mine = {name: stack[i] for name, stack in experts.items()}
                y = _moe_dense_held(h2, dict(layer, **mine), cfg)
            with jax.named_scope("moe_combine"):
                return (
                    x + y + _shared(h2, layer), lat, pairs, slabs, tiles
                ), None

        with jax.named_scope("layers"):
            (x, lat, pairs, slabs, tiles), _ = lax.scan(
                moe_layer,
                (x, lat, pairs, slabs, tiles),
                (rest, jnp.arange(cfg.n_moe_layers)),
            )
        return x, (lat, last, pairs, slabs, tiles)

    lat, last, pairs, slabs, tiles = seq.chunk_loop(
        lengths, c, body,
        lambda: (
            jnp.zeros((cfg.nlayers, B, kv_len, pool_width(cfg)), compute_dtype),
            jnp.zeros((B, cfg.emb_dim), compute_dtype),
            *(jnp.zeros((), jnp.int32),) * 3,
        ),
        last=1,
    )
    with jax.named_scope("lm_head"):
        logits = _norm(last, params["norm"], cfg) @ params["lm_head"]
    return logits, lat, pairs, slabs, tiles


# ---------------------------------------------------------------------------
# decode: one ragged paged step, absorbed form over the latent pages
# ---------------------------------------------------------------------------


def decode_block_pages(max_pages: int, page_size: int) -> int:
    """Pages one trip of the decode attention's loop gathers: the
    largest divisor of a stream's ``max_pages`` that holds at most
    ``DECODE_BLOCK_TOKENS`` positions."""
    return seq.largest_divisor(
        max_pages, max(1, DECODE_BLOCK_TOKENS // page_size)
    )


def _latent_attend(
    qq, pool, l, page_table, seq_lens, cfg, page_size: int,
    kernel: bool = False,
):
    """Absorbed attention of one query a row over layer ``l`` of the
    latent pool: the ragged paged kernel
    (``ops/paged_attention.py::latent_attention_kernel``: each row's own
    pages, read where they lie) where ``kernel``, else in plain jax as
    follows. qq (B, N, pool_width): each head's absorbed query then
    its rotary part; pool (L, P, page_size, pool_width); row ``b`` sees
    positions <= seq_lens[b]. Blocks of pages are gathered and attended
    with a running softmax, up to the longest row and no further.
    Returns u (B, N, kv_lora_rank) fp32: Σ p ĉ."""
    r = cfg.kv_lora_rank
    if kernel:
        from fms_fsdp_tpu.ops.paged_attention import latent_attention_kernel

        return latent_attention_kernel(
            qq, pool, l, page_table, seq_lens, value_width=r,
            scale=softmax_scale(cfg),
        )
    B, max_pages = page_table.shape
    bp = decode_block_pages(max_pages, page_size)
    blk = bp * page_size
    scale = softmax_scale(cfg)

    def block(j, carry):
        m, den, acc = carry
        with jax.named_scope("latent_gather"):
            ids = lax.dynamic_slice_in_dim(page_table, j * bp, bp, axis=1)
            g = pool[l, ids].reshape(B, blk, -1)
        with jax.named_scope("attn"):
            s = jnp.einsum(
                "bnc,bsc->bns", qq, g, preferred_element_type=jnp.float32
            ) * scale
            pos = j * blk + jnp.arange(blk, dtype=jnp.int32)
            s = jnp.where(
                pos[None, None, :] <= seq_lens[:, None, None], s, -jnp.inf
            )
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            fade = jnp.exp(m - m_new)
            den = den * fade + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * fade + jnp.einsum(
                "bns,bsc->bnc", p.astype(g.dtype), g[..., :r],
                preferred_element_type=jnp.float32,
            )
        return m_new, den, acc

    n = cfg.nheads
    _, den, acc = lax.fori_loop(
        0,
        jnp.max(seq_lens) // blk + 1,
        block,
        (
            jnp.full((B, n, 1), -jnp.inf, jnp.float32),
            jnp.zeros((B, n, 1), jnp.float32),
            jnp.zeros((B, n, r), jnp.float32),
        ),
    )
    with jax.named_scope("attn"):
        return acc / den


def sarvam_paged_decode_step(
    params: Params,
    pools,
    page_table,
    seq_lens,
    tokens,
    cfg: SarvamConfig,
    *,
    page_size: int,
    compute_dtype=jnp.bfloat16,
    moe_impl: str = "routed",
    attn_impl: str = "reference",
):
    """One ragged paged decode step. tokens (B,) int32 at positions
    ``seq_lens``; pools ``{"latent": (L, P, page_size, pool_width)}``,
    the adapter's PagedKVCache.pools. Each layer writes the position's
    latent to its page and attends in the absorbed form over the pages
    (``attn_impl="kernel"``: the ragged paged kernel; ``"reference"``:
    gathered blocks of pages in plain jax). Returns (logits (B, V),
    pools)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B = tokens.shape[0]
    max_seq = page_table.shape[1] * page_size
    cos, sin = rope_tables(cfg, max_seq)
    seq_lens = seq_lens.astype(jnp.int32)
    positions = seq_lens[:, None]
    with jax.named_scope("embed"):
        x = params["embedding"][tokens[:, None]]
    with jax.named_scope("latent_write"):  # each row's write target
        page_ids = page_table[jnp.arange(B), seq_lens // page_size]
        slots = seq_lens % page_size
    Ld = cfg.first_k_dense

    def attend(x, pool, layer, l):
        h = _norm(x, layer["attn_norm"], cfg)
        q_nope, q_rope = _mla_q(h, layer, cfg, cos, sin, positions)
        new = _mla_latent(h, layer, cfg, cos, sin, positions)
        with jax.named_scope("latent_write"):
            pool = pool.at[l, page_ids, slots].set(
                _to_pool_width(new[:, 0], cfg)
            )
        wk, wv = _wkv_b_heads(layer, cfg)
        with jax.named_scope("mla_absorb"):
            # zeros against the pool's padding add nothing to a score
            qq = _to_pool_width(
                jnp.concatenate(
                    [
                        jnp.einsum("bnd,rnd->bnr", q_nope[:, 0], wk),
                        q_rope[:, 0],
                    ],
                    axis=-1,
                ),
                cfg,
            )
        u = _latent_attend(
            qq, pool, l, page_table, seq_lens, cfg, page_size,
            kernel=attn_impl == "kernel",
        )
        with jax.named_scope("mla_absorb"):
            o = jnp.einsum("bnr,rnd->bnd", u.astype(x.dtype), wv)
        with jax.named_scope("attn_out"):
            x = x + o.reshape(B, 1, -1) @ layer["wo"]
        return x, pool, _norm(x, layer["ffn_norm"], cfg)

    pool = pools["latent"]
    for i in range(Ld):
        layer = _layer_at(params["dense_layers"], i)
        x, pool, h2 = attend(x, pool, layer, i)
        x = x + _mlp(h2, layer)

    def body(carry, inp):
        x, pool = carry
        layer, i = inp
        x, pool, h2 = attend(x, pool, layer, Ld + i)
        y = _moe_token(h2, layer, cfg, moe_impl)
        with jax.named_scope("moe_combine"):
            return (x + y + _shared(h2, layer), pool), None

    with jax.named_scope("layers"):
        (x, pool), _ = lax.scan(
            body, (x, pool), (params["layers"], jnp.arange(cfg.n_moe_layers))
        )
    with jax.named_scope("lm_head"):
        logits = _norm(x, params["norm"], cfg) @ params["lm_head"]
    return logits[:, 0], {"latent": pool}
