"""MiniCPM-SALA (``model_type: minicpm_sala``): block-sparse attention
layers that choose their context through compressed keys, beside
lightning linear-attention layers that keep a state a head. The serving
family's model file: forward, sequence prefill and the decode step over
pages, an index cache and a state.

**Whole model.** ``h0 = scale_emb * E[id]``; layer ``i`` of kind
``cfg.kind(i)``: ``a = h + s * Mixer(RMSNorm(h))``, ``h' = a + s *
MLP(RMSNorm(a))``, ``s = scale_depth / sqrt(depth_published)`` whatever
depth is kept; ``MLP`` a SwiGLU of ``hidden_dim``; ``logits = W_head
RMSNorm(h_L) / (emb_dim / dim_model_base)``, head untied, no bias.

**Sparse layer** (``minicpm4``; InfLLM-V2). ``q = W_q u`` (``nheads``
heads), ``k = W_k u``, ``v = W_v u`` (``kvheads`` heads); RMSNorm with a
learned weight over each head's values of q and of k; no rotary. A query
at position ``t`` with ``t + 1 <= dense_len`` attends every position up
to its own; a later one scores the compressed keys of its context (the
mean of ``kernel_size`` keys every ``kernel_stride``), pools the scores
to blocks of ``block_size`` and attends ``topk`` blocks, the first and
the newest always among them (ops/paged_attention.py::block_keys, the
one arithmetic of every form here); the query heads of a kv head choose
together. ``out = W_o (o * sigmoid(W_g u))``.

**Lightning layer** (``lightning-attn``). ``q, k, v`` of
``lightning_nh`` heads; RMSNorm by head on q and k; rotary on q and k
(the whole head, its two halves paired); ``S_t = lam_h S_{t-1} + k_t
v_t^T``, ``o_t = q_t^T S_t / sqrt(H)`` (ops/lightning_attention.py: the
recurrence, the one-position step, the chunked form); ``out = W_o
(RMSNorm(o) * sigmoid(W_g u))``, the norm over the concatenated heads.

**What a position leaves behind follows the kind of layer.** A sparse
layer keeps every position's key and value in pages, one kv head a page
(``serve/kv_cache.py::PagedKVCache`` over ``L_sparse * kvheads`` layers
of one head: a kv head's chosen pages are its own list), and beside
them an **index cache**: the compressed keys, ``block_size /
kernel_stride`` a page, which the choice reads whole and the attention
never. A lightning layer keeps a float32 state ``(heads, H, H)`` a
stream, whatever the context.

Read by the family's convention where ``config.json`` has no key (the
configuration file lists each): the sparse sizes; that a position goes
dense or sparse by its own ``t`` and not by its sequence's length (so a
position reads the same in a prefill chunk, a decode step and a full
forward); the decay's slopes; no activation on q, k, v.

Parameter tree: ``embedding (V, D)``, ``norm``, ``lm_head (D, V)`` and a
dict of stacked leaves for each kind of layer, ``sparse`` and
``lightning``; a layer's index in its stack is its place among the
layers of its kind.
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from fms_fsdp_tpu.models import sequence_prefill as seq
from fms_fsdp_tpu.models.configs import SalaConfig
from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops.attention import chunk_attention
from fms_fsdp_tpu.ops.lightning_attention import (
    lightning_chunked,
    lightning_recurrent,
    lightning_step,
)
from fms_fsdp_tpu.ops.norms import rms_norm
from fms_fsdp_tpu.ops.paged_attention import (
    NEG_INF,
    block_keys,
    chosen_list,
    chosen_mask,
    chosen_pages_attention,
    compress_keys,
    free_list,
    gathered_blocks_attention,
)
from fms_fsdp_tpu.ops.ring_attention import merge_partial
from fms_fsdp_tpu.ops.rope import rotate_halves

__all__ = [
    "SalaConfig",
    "init_sala_params",
    "sala_forward",
    "sala_paged_decode_step",
    "sala_prefill",
]

Params = Dict[str, Any]

# positions one trip of the prefill's loop takes through the stack (in
# whole blocks). A constant of the program: no option selects it.
PREFILL_CHUNK = 2048
# queries whose choice of blocks is made in one piece inside a chunk
SELECT_TILE = 256
# queries that share one product against the band of forced blocks
BAND_TILE = 256


def init_sala_params(key, cfg: SalaConfig, dtype=jnp.float32) -> Params:
    d, f = cfg.emb_dim, cfg.hidden_dim
    std = 0.02
    keys = iter(jax.random.split(key, 64))

    def tn(shape, s=std):
        return (
            jax.random.truncated_normal(next(keys), -3, 3, shape, jnp.float32)
            * s
        ).astype(dtype)

    def stack(kind: str, L: int):
        if kind == "sparse":
            heads, hd = cfg.nheads * cfg.head_dim, cfg.head_dim
            kv = cfg.kvheads * hd
        else:
            hd = cfg.lightning_head_dim
            heads = kv = cfg.lightning_nh * hd
        p = {
            "attn_norm": jnp.ones((L, d), dtype),
            "wq": tn((L, d, heads)),
            "wk": tn((L, d, kv)),
            "wv": tn((L, d, kv)),
            "q_norm": jnp.ones((L, hd), dtype),
            "k_norm": jnp.ones((L, hd), dtype),
        }
        if kind == "lightning":
            p["o_norm"] = jnp.ones((L, heads), dtype)
        p.update(
            wg=tn((L, d, heads)),
            wo=tn((L, heads, d)),
            ffn_norm=jnp.ones((L, d), dtype),
            w1=tn((L, d, f)),
            w3=tn((L, d, f)),
            w2=tn((L, f, d)),
        )
        return p

    params = {"embedding": tn((cfg.src_vocab_size, d))}
    for kind, layers in cfg.stacks.items():
        params[kind] = stack(kind, len(layers))
    params["norm"] = jnp.ones((d,), dtype)
    params["lm_head"] = tn((d, cfg.src_vocab_size))
    return params


def layer_places(cfg: SalaConfig):
    """``[(kind, index in the kind's stack), ...]`` of the layers in
    order."""
    seen, out = {}, []
    for i in range(cfg.nlayers):
        kind = cfg.kind(i)
        seen[kind] = seen.get(kind, -1) + 1
        out.append((kind, seen[kind]))
    return out


def _layer_at(stacked, i: int):
    return {name: a[i] for name, a in stacked.items()}


# ---------------------------------------------------------------------------
# what every form shares
# ---------------------------------------------------------------------------


@scoped("norm")
def _norm(x, w, cfg):
    return rms_norm(x, w, cfg.norm_eps)


@scoped("embed")
def _embed(params, tokens, cfg):
    x = params["embedding"][tokens]
    return (x * cfg.scale_emb).astype(x.dtype)


@scoped("mlp")
def _mlp(x, layer, cfg):
    h = _norm(x, layer["ffn_norm"], cfg)
    y = (jax.nn.silu(h @ layer["w1"]) * (h @ layer["w3"])) @ layer["w2"]
    return x + (cfg.residual_gain * y).astype(x.dtype)


@scoped("lm_head")
def _head(x, params, cfg):
    logits = _norm(x, params["norm"], cfg) @ params["lm_head"]
    return logits / cfg.logit_divisor


def _qkv(u, layer, cfg: SalaConfig, kind: str, positions):
    """u (B, S, D) -> q, k, v by head: projected, q and k normed by head
    and, on a lightning layer, turned at ``positions`` (B, S)."""
    B, S, _ = u.shape
    if kind == "sparse":
        n, nkv, hd = cfg.nheads, cfg.kvheads, cfg.head_dim
    else:
        n = nkv = cfg.lightning_nh
        hd = cfg.lightning_head_dim
    with jax.named_scope("qkv"):
        # the products end here, before the reshape by head
        # (ops/attention.py::qkv_by_head says what the compiler does otherwise)
        q, k, v = lax.optimization_barrier(
            (u @ layer["wq"], u @ layer["wk"], u @ layer["wv"])
        )
        q = q.reshape(B, S, n, hd)
        k = k.reshape(B, S, nkv, hd)
        v = v.reshape(B, S, nkv, hd)
    with jax.named_scope("qk_norm"):
        q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.norm_eps)
    if kind == "lightning":
        with jax.named_scope("rope"):
            q = rotate_halves(q, positions, cfg.rope_theta)
            k = rotate_halves(k, positions, cfg.rope_theta)
    return q, k, v


def _sparse_out(x, o, u, layer, cfg):
    """The sparse mixer's end: the output gate, ``W_o``, the residual."""
    with jax.named_scope("attn_out"):
        o = o.reshape(u.shape[:-1] + (-1,)).astype(u.dtype)
        y = (o * jax.nn.sigmoid(u @ layer["wg"])) @ layer["wo"]
        return x + (cfg.residual_gain * y).astype(x.dtype)


def _lightning_out(x, o, u, layer, cfg):
    """The lightning mixer's end: the norm over the concatenated heads,
    the output gate, ``W_o``, the residual. o float32 by head."""
    with jax.named_scope("lin_gate"):
        o = rms_norm(
            o.reshape(u.shape[:-1] + (-1,)), layer["o_norm"], cfg.norm_eps
        ).astype(u.dtype)
        o = o * jax.nn.sigmoid(u @ layer["wg"])
    with jax.named_scope("attn_out"):
        return x + (cfg.residual_gain * (o @ layer["wo"])).astype(x.dtype)


def _masked_attention(q, k, v, mask):
    """q (B, Sq, Nkv, g, H) over k, v (B, Sk, Nkv, H) where ``mask`` (B,
    Nkv, Sq, Sk) -> (normalised output (B, Sq, N, H) fp32, log-sum-exp (B,
    Sq, N, 1) fp32): a partial that ``merge_partial`` joins with others.
    A row that sees nothing gives a log-sum-exp near ``NEG_INF``."""
    B, Sq, nkv, g, H = q.shape
    s = jnp.einsum(
        "bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32
    ) * (H**-0.5)
    s = jnp.where(mask[:, :, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask[:, :, None], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v)
    o = o.astype(jnp.float32) / jnp.maximum(jnp.moveaxis(l, 3, 1), 1e-30)
    lse = jnp.moveaxis(m + jnp.log(jnp.maximum(l, 1e-30)), 3, 1)
    return o.reshape(B, Sq, nkv * g, H), lse.reshape(B, Sq, nkv * g, 1)


def _choose(q, kc, t, sp):
    """The blocks each query of q (B, T, Nkv, g, H) at positions t (B, T)
    attends, through compressed keys kc (B, Nkv, nb * r, H): (B, Nkv, T,
    nb) bool."""
    return chosen_mask(*block_keys(q, kc, t, sp), sp)


def _pad_rows(kc, rows):
    """kc (B, Nkv, n, H) with rows of zeros up to ``rows``."""
    return jnp.pad(kc, ((0, 0), (0, 0), (0, rows - kc.shape[2]), (0, 0)))


# ---------------------------------------------------------------------------
# forward (whole sequences, no cache): the parity form
# ---------------------------------------------------------------------------


def sala_forward(
    params: Params, tokens, cfg: SalaConfig, *,
    compute_dtype=jnp.bfloat16, lightning: str = "recurrent",
):
    """tokens (B, S) -> logits (B, S, V), S in whole blocks: every
    position's blocks chosen at once and attended under a mask; the
    lightning layers by the recurrence (``lightning="recurrent"``) or the
    chunked form (``"chunked"``)."""
    params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B, S = tokens.shape
    sp = cfg.sparse
    assert S % sp.block_size == 0, (S, sp.block_size)
    pos = jnp.arange(S, dtype=jnp.int32)
    positions = jnp.broadcast_to(pos, (B, S))
    block_of = pos // sp.block_size
    x = _embed(params, tokens, cfg)
    for kind, at in layer_places(cfg):
        layer = _layer_at(params[kind], at)
        u = _norm(x, layer["attn_norm"], cfg)
        q, k, v = _qkv(u, layer, cfg, kind, positions)
        if kind == "sparse":
            q = q.reshape(B, S, cfg.kvheads, -1, cfg.head_dim)
            kc = _pad_rows(compress_keys(k, sp), S // sp.kernel_stride)
            chosen = _choose(q, kc, positions, sp)
            mask = chosen[..., block_of] & (pos[None, :] <= pos[:, None])
            o, _ = _masked_attention(q, k, v, mask)
            x = _sparse_out(x, o, u, layer, cfg)
        else:
            scale = cfg.lightning_head_dim**-0.5
            form = (
                lightning_recurrent if lightning == "recurrent"
                else lightning_chunked
            )
            o, _ = form(q, k, v, scale)
            x = _lightning_out(x, o, u, layer, cfg)
        x = _mlp(x, layer, cfg)
    return _head(x, params, cfg)


# ---------------------------------------------------------------------------
# prefill: the prompt as a sequence, a chunk at a time
# ---------------------------------------------------------------------------


def prefill_chunk(p_pad: int, cfg: SalaConfig) -> int:
    """The chunk of a prompt padded to ``p_pad`` (whole blocks): the most
    blocks up to ``PREFILL_CHUNK`` positions that tile it."""
    return seq.chunk_of(p_pad, PREFILL_CHUNK, unit=cfg.sparse.block_size)


def prefill_positions(p: int, p_pad: int, cfg: SalaConfig) -> int:
    """Positions ``sala_prefill`` computes for a prompt of ``p`` tokens
    padded to ``p_pad``: whole chunks up to the prompt's end."""
    return seq.positions_computed(p, prefill_chunk(p_pad, cfg))


def prefill_choices(p: int, cfg: SalaConfig):
    """(positions of a prompt of ``p`` tokens that choose their blocks,
    the blocks they choose together, one kv head's, the blocks they
    choose from): what ``sala_prefill`` counts, from the sizes alone."""
    sp = cfg.sparse
    exist = [t // sp.block_size + 1 for t in range(min(p, sp.dense_len), p)]
    return len(exist), sum(min(n, sp.topk) for n in exist), sum(exist)


def prefill_multiplied(p: int, p_pad: int, cfg: SalaConfig) -> int:
    """The blocks a kv head's products touch for the choosing positions
    of a prompt of ``p`` tokens in the program of ``p_pad``
    (``multiplied_blocks`` over its chunks): ``sala_prefill``'s fourth
    count, from the sizes alone."""
    sp = cfg.sparse
    c = prefill_chunk(p_pad, cfg)
    t = jnp.arange(min(p, sp.dense_len), p, dtype=jnp.int32)
    chosen = jnp.asarray(
        [f == "chosen" for f in chunk_forms(p_pad, cfg)]
    )[t // c]
    return int(jnp.sum(multiplied_blocks(
        t // sp.block_size + 1, t // c * c, c, chosen, sp
    )))


def _use_flash(cfg: SalaConfig, attn_impl: str, c: int) -> bool:
    fits = c % 256 == 0 and cfg.head_dim % 128 == 0
    return fits and seq.kernel_wanted(attn_impl)


def chunk_forms(p_pad: int, cfg: SalaConfig):
    """What the sparse layers' attention is in each chunk of the prefill
    program of ``p_pad`` positions, in the chunks' order: ``"dense"``
    where every position of the chunk attends everything before it
    (``start + c <= dense_len``), ``"chosen"`` where every position
    chooses and its forced blocks stand apart (``start >= dense_len``
    and the first block lies before the band), ``"masked"`` for a chunk
    that holds both kinds (only where the chunk does not divide
    ``dense_len``)."""
    sp = cfg.sparse
    c = prefill_chunk(p_pad, cfg)
    apart = (sp.init_blocks + sp.window_blocks - 1) * sp.block_size
    free = min(sp.topk, p_pad // sp.block_size) > (
        sp.init_blocks + sp.window_blocks
    )
    return [
        "dense" if start + c <= sp.dense_len
        else "chosen" if free and start >= max(sp.dense_len, apart)
        else "masked"
        for start in range(0, p_pad, c)
    ]


def prefill_attn_form(cfg: SalaConfig, attn_impl: str, p_pad: int) -> str:
    """What the sparse layers' attention runs in the prefill program of
    ``p_pad`` positions (``attn_form`` on ``serve/prefill.dispatch``):
    chunks whose every position is dense take the causal flash walk or
    its einsum form; chunks past ``dense_len`` the band of forced blocks
    and the kernel over each query's list of free blocks
    (``+chosen_blocks``); a chunk that holds both kinds the masked walk
    over every block (``+masked_blocks``)."""
    flash = _use_flash(cfg, attn_impl, prefill_chunk(p_pad, cfg))
    forms = chunk_forms(p_pad, cfg)
    return ("flash" if flash else "einsum") + "".join(
        f"+{form}_blocks" for form in ("masked", "chosen") if form in forms
    )


@scoped("sparse_compress")
def _compress_chunk(kc, k, before, start, ahead, sp):
    """The compressed keys whose window ends inside this chunk, written
    into kc (B, Nkv, 1 + kv_len / stride, H) (key ``j`` at row ``j + 1``:
    the window that would start before position 0 lands in row 0 and is
    never read). k (B, c, Nkv, H) the chunk's keys, ``before`` (B, stride,
    Nkv, H) the ``kernel_stride`` keys before it: the windows that
    straddle the chunk's start. A window that holds a position past a
    row's end (``ahead`` (B,) positions left from the chunk's start) is
    written as zeros."""
    new = compress_keys(jnp.concatenate([before, k], axis=1), sp)
    first = jnp.arange(new.shape[2], dtype=jnp.int32) * sp.kernel_stride
    whole = first[None, :] + sp.kernel_stride <= ahead[:, None]
    new = jnp.where(whole[:, None, :, None], new, jnp.zeros_like(new))
    return lax.dynamic_update_slice(
        kc, new, (0, 0, start // sp.kernel_stride, 0)
    )


@scoped("sparse_select")
def _select_chunk(q, kc, positions, sp, lists: bool):
    """The choice of a chunk's queries q (B, c, Nkv, g, H), ``SELECT_TILE``
    at a time: ``_choose``'s mask (B, Nkv, c, nb) bool, or with ``lists``
    (a ``"chosen"`` chunk) each query's free blocks and their count
    (``free_list``: (B, Nkv, c, W) and (B, Nkv, c) int32)."""
    B, c = q.shape[:2]
    tile = seq.largest_divisor(c, SELECT_TILE)

    def choose(q, t):
        if lists:
            return free_list(block_keys(q, kc, t, sp)[0], sp)
        return (_choose(q, kc, t, sp),)

    if tile == c:
        out = choose(q, positions)
    else:

        def tiles(a):
            return jnp.moveaxis(
                a.reshape((B, c // tile, tile) + a.shape[2:]), 1, 0
            )

        out = lax.map(lambda a: choose(*a), (tiles(q), tiles(positions)))
        # (c / tile, B, Nkv, tile, ...) -> (B, Nkv, c, ...)
        out = tuple(
            jnp.moveaxis(a, 0, 2).reshape(a.shape[1:3] + (c,) + a.shape[4:])
            for a in out
        )
    return out if lists else out[0]


@scoped("sparse_attn")
def _masked_chunk_attention(q, kb, vb, chosen, start, sp):
    """A chunk's queries q (B, c, Nkv, g, H) at positions ``start`` on
    over the positions up to their own of the blocks ``chosen`` (B, Nkv,
    c, nb), out of the buffers kb, vb (B, kv_len, Nkv, H): the buffer is
    walked ``c`` positions at a time up to the chunk's own, each piece
    under the mask of its blocks, the partials merged through their
    log-sum-exp. Every block is multiplied and the unchosen masked: the
    cost is the dense walk's, which only a chunk that holds dense
    positions and choosing ones pays. -> (B, c, N, H)."""
    B, c, nkv, g, H = q.shape
    bs = sp.block_size
    q_pos = start + jnp.arange(c, dtype=jnp.int32)

    def partial_at(i):
        at = i * c
        k = lax.dynamic_slice_in_dim(kb, at, c, axis=1)
        v = lax.dynamic_slice_in_dim(vb, at, c, axis=1)
        blocks = lax.dynamic_slice_in_dim(chosen, at // bs, c // bs, axis=3)
        k_pos = at + jnp.arange(c, dtype=jnp.int32)
        mask = jnp.repeat(blocks, bs, axis=3) & (
            k_pos[None, :] <= q_pos[:, None]
        )
        return _masked_attention(q, k, v, mask)

    o, lse = partial_at(start // c)
    o, _ = lax.fori_loop(
        0, start // c,
        lambda i, carry: merge_partial(carry, *partial_at(i)),
        (o, lse),
    )
    return o.astype(kb.dtype)


def _band_attention(q, kb, vb, start, sp):
    """The forced half of a ``"chosen"`` chunk's attention: queries q (B,
    c, Nkv, g, H) at positions ``start`` on over the first
    ``init_blocks`` blocks and the ``window_blocks`` that end at their
    own, up to their own position. The queries of a block share those
    blocks and the band slides a block a block, so ``BAND_TILE`` queries
    at a time take one masked product against their own positions, the
    ``window_size - block_size`` before them and the first blocks.
    -> the partial (B, c, N, H), (B, c, N, 1) float32."""
    B, c, nkv, g, H = q.shape
    bs = sp.block_size
    tile = bs * seq.largest_divisor(c // bs, max(1, BAND_TILE // bs))
    first, back = sp.init_blocks * bs, (sp.window_blocks - 1) * bs
    # a band key's position and a query's, both from the tile's start
    k_pos = jnp.arange(-back, tile, dtype=jnp.int32)
    t = jnp.arange(tile, dtype=jnp.int32)
    seen = jnp.concatenate([
        jnp.ones((tile, first), bool),
        (k_pos[None, :] // bs > (t // bs)[:, None] - sp.window_blocks)
        & (k_pos[None, :] <= t[:, None]),
    ], axis=1)

    def band(i):
        at = start + i * tile

        def keys(buf):
            return jnp.concatenate([
                buf[:, :first],
                lax.dynamic_slice_in_dim(buf, at - back, back + tile, axis=1),
            ], axis=1)

        return _masked_attention(
            lax.dynamic_slice_in_dim(q, i * tile, tile, axis=1),
            keys(kb), keys(vb), seen[None, None],
        )

    if tile == c:
        return band(0)
    o, lse = lax.map(band, jnp.arange(c // tile, dtype=jnp.int32))
    # (c / tile, B, tile, N, ...) -> (B, c, N, ...)
    return tuple(
        jnp.moveaxis(a, 0, 1).reshape((B, c) + a.shape[3:]) for a in (o, lse)
    )


@scoped("sparse_attn")
def _chosen_chunk_attention(q, kb, vb, free, n, start, sp):
    """A ``"chosen"`` chunk's queries q (B, c, Nkv, g, H) at positions
    ``start`` on over the blocks each chose, out of the buffers kb, vb (B,
    kv_len, Nkv, H): the forced half as a band (``_band_attention``), the
    free half (``free`` (B, Nkv, c, W), ``n`` (B, Nkv, c) of
    ``_select_chunk``) by the kernel that takes each query's blocks out
    of a context resident in vector memory; the two partials merged
    through their log-sum-exp. Only the chosen blocks and the band's
    masked corners are multiplied. -> (B, c, N, H)."""
    c = q.shape[1]
    o, _ = merge_partial(
        _band_attention(q, kb, vb, start, sp),
        *gathered_blocks_attention(
            q, kb, vb, free, n, start + c, block_size=sp.block_size
        ),
    )
    return o.astype(kb.dtype)


def multiplied_blocks(exist, start, c: int, chosen, sp):
    """The blocks a kv head's products touch for a choosing position with
    ``exist`` blocks up to its own, in the chunk at ``start``: where the
    chunk is ``chosen``, the band (the forced blocks and the masked
    corners of ``BAND_TILE`` queries) and the position's free blocks;
    else every block up to the chunk's end, which the masked walk
    multiplies."""
    bs = sp.block_size
    tile = seq.largest_divisor(c // bs, max(1, BAND_TILE // bs))
    forced = sp.init_blocks + sp.window_blocks
    listed = jnp.clip(exist - forced, 0, sp.topk - forced)
    return jnp.where(chosen, forced - 1 + tile + listed, (start + c) // bs)


def sala_prefill(
    params: Params,
    tokens,
    lengths,
    cfg: SalaConfig,
    *,
    compute_dtype=jnp.bfloat16,
    kv_len: int = 0,
    attn_impl: str = "auto",
):
    """Prompt prefill. tokens (B, S_pad) int32, lengths (B,) int32 the
    prompts' lengths (<= S_pad). ``prefill_chunk(S_pad)`` positions at a
    time go through every layer, in one loop whose trip count is read
    from ``lengths`` on the device. From chunk to chunk go: each sparse
    layer's keys and values written so far and its compressed keys (the
    keys not yet compressed are the buffer's last, read back for the
    windows that straddle a chunk's start), each lightning layer's
    state, and each row's residual at its last real position. A chunk
    whose every position is dense (``start + c <= dense_len``) attends
    as models/kexaone.py's full layers do; a chunk past ``dense_len``
    chooses each position's free blocks as a list and multiplies the
    chosen blocks alone (``_chosen_chunk_attention``); a chunk that holds
    both kinds of position chooses under a mask and walks every block
    (``_masked_chunk_attention``). The program decides by the chunk's
    start, its length and ``dense_len`` (``chunk_forms``), and holds the
    forms its chunks take and no others.

    Returns (logits (B, V) of each row's last real position; the sparse
    layers' pages ``{"k", "v"}`` (L_sparse * Nkv, B, kv_len, 1, H), a kv
    head a layer of the cache, zero past each row's length, and ``"kc"``
    (L_sparse * Nkv, B, kv_len / stride, H), the index cache's rows; the
    lightning layers' state ``{"S"}`` (L_lightning, B, heads, H, H)
    float32; and the counts (positions that chose their blocks, blocks
    chosen by them a kv head, blocks they chose from, blocks the
    attention's products touched for them: ``multiplied_blocks``) of the
    first sparse layer: every sparse layer's are the same)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B, S = tokens.shape
    sp = cfg.sparse
    c = prefill_chunk(S, cfg)
    kv_len = kv_len or S
    assert kv_len >= S and kv_len % sp.block_size == 0, (kv_len, S)
    flash = _use_flash(cfg, attn_impl, c)
    places = layer_places(cfg)
    n_sparse, n_lin = len(cfg.sparse_layers), len(cfg.lightning_layers)
    nkv, hd = cfg.kvheads, cfg.head_dim
    rows = kv_len // sp.kernel_stride
    kv_shape = (B, kv_len, nkv, hd)
    kc_shape = (B, nkv, 1 + rows, hd)
    s_shape = (B, cfg.lightning_nh) + (cfg.lightning_head_dim,) * 2
    lin_scale = cfg.lightning_head_dim**-0.5

    forms = chunk_forms(S, cfg)
    present = [f for f in ("dense", "masked", "chosen") if f in forms]
    # where each form after the first begins: the forms come in that
    # order (``chunk_forms``)
    edges = jnp.asarray([forms.index(f) * c for f in present[1:]], jnp.int32)
    first_chosen = forms.index("chosen") * c if "chosen" in forms else S

    def by_form(start, **branches):
        """The branch of the chunk at ``start``: chosen by ``start``, the
        chunk and ``dense_len`` alone, among the forms this program's
        chunks take (one form: no conditional)."""
        return lambda x: lax.switch(
            jnp.sum(start >= edges), [branches[f] for f in present], x
        )

    def body(chunk, carry):
        pages, states, last, counts = carry
        pages, states = list(pages), list(states)
        start, ahead, live = chunk.start, chunk.ahead, chunk.live
        positions = chunk.positions
        toks = lax.dynamic_slice_in_dim(tokens, start, c, axis=1)
        x = _embed(params, toks, cfg)
        si = li = 0
        with jax.named_scope("layers"):
            for kind, at in places:
                layer = _layer_at(params[kind], at)
                u = _norm(x, layer["attn_norm"], cfg)
                q, k, v = _qkv(u, layer, cfg, kind, positions)
                if kind == "lightning":
                    with jax.named_scope("lin_scan"):
                        o, states[li] = lightning_chunked(
                            q, k, v, lin_scale, states[li], live
                        )
                    li += 1
                    x = _mlp(_lightning_out(x, o, u, layer, cfg), layer, cfg)
                    continue
                kb, vb, kc = pages[si]
                with jax.named_scope("kv_write"):
                    keep = live[:, :, None, None]
                    k = jnp.where(keep, k, jnp.zeros_like(k))
                    before = lax.dynamic_slice_in_dim(
                        kb, jnp.maximum(start - sp.kernel_stride, 0),
                        sp.kernel_stride, axis=1,
                    )
                    kb = lax.dynamic_update_slice(kb, k, (0, start, 0, 0))
                    vb = lax.dynamic_update_slice(
                        vb, jnp.where(keep, v, jnp.zeros_like(v)),
                        (0, start, 0, 0),
                    )
                kc = _compress_chunk(kc, k, before, start, ahead, sp)
                pages[si] = (kb, vb, kc)

                def dense(q):
                    with jax.named_scope("attn"):
                        return chunk_attention(
                            q, kb, vb, start,
                            impl="pallas" if flash else "xla",
                        )

                def masked(q):
                    qg = q.reshape(B, c, nkv, -1, hd)
                    mask = _select_chunk(
                        qg, kc[:, :, 1:], positions, sp, lists=False
                    )
                    return _masked_chunk_attention(qg, kb, vb, mask, start, sp)

                def chosen(q):
                    qg = q.reshape(B, c, nkv, -1, hd)
                    free, n = _select_chunk(
                        qg, kc[:, :, 1:], positions, sp, lists=True
                    )
                    return _chosen_chunk_attention(
                        qg, kb, vb, free, n, start, sp
                    )

                o = by_form(start, dense=dense, masked=masked, chosen=chosen)(q)
                if si == 0:
                    chose = live & (positions + 1 > sp.dense_len)
                    exist = positions // sp.block_size + 1
                    touched = multiplied_blocks(
                        exist, start, c, start >= first_chosen, sp
                    )
                    counts = counts + jnp.stack([
                        jnp.sum(chose),
                        jnp.sum(
                            jnp.where(chose, jnp.minimum(exist, sp.topk), 0)
                        ),
                        jnp.sum(jnp.where(chose, exist, 0)),
                        jnp.sum(jnp.where(chose, touched, 0)),
                    ]).astype(jnp.int32)
                si += 1
                x = _mlp(_sparse_out(x, o, u, layer, cfg), layer, cfg)
        return x, (tuple(pages), tuple(states), last, counts)

    def zeros(shape, dtype=compute_dtype):
        return jnp.zeros(shape, dtype)

    pages, states, last, counts = seq.chunk_loop(
        lengths, c, body,
        lambda: (
            tuple(
                (zeros(kv_shape), zeros(kv_shape), zeros(kc_shape))
                for _ in range(n_sparse)
            ),
            tuple(zeros(s_shape, jnp.float32) for _ in range(n_lin)),
            zeros((B, cfg.emb_dim)),
            jnp.zeros((4,), jnp.int32),
        ),
        last=2,
    )
    logits = _head(last, params, cfg)

    def by_head(buf):  # (B, kv_len, Nkv, H) -> (Nkv, B, kv_len, 1, H)
        return jnp.moveaxis(buf, 2, 0)[..., None, :]

    def cat(parts, shape):
        return jnp.concatenate(parts) if parts else zeros((0,) + shape)

    kv = {
        "k": cat([by_head(p[0]) for p in pages], (B, kv_len, 1, hd)),
        "v": cat([by_head(p[1]) for p in pages], (B, kv_len, 1, hd)),
        "kc": cat(
            [jnp.moveaxis(p[2][:, :, 1:], 1, 0) for p in pages],
            (B, rows, hd),
        ),
    }
    state = {"S": seq.stack_or_empty(list(states), s_shape, jnp.float32)}
    return logits, kv, state, counts


# ---------------------------------------------------------------------------
# decode: one ragged step over chosen pages, the index cache and the state
# ---------------------------------------------------------------------------


@scoped("index_write")
def _index_write(kc_pool, k_pool, heads, page_table, seq_lens, sp):
    """The compressed key whose window ends at a row's position, if one
    does: the mean of the row's last ``kernel_size`` keys, read back from
    its pages (the position's own key is written), into the index cache
    at the page and row of the window's first position. k_pool (L', P,
    page_size, 1, H), kc_pool (L', P, r, H); ``heads`` (Nkv,) the
    layer's kv heads among the cache's layers."""
    B = seq_lens.shape[0]
    ps = sp.block_size
    first = seq_lens + 1 - sp.kernel_size  # the window's first position
    due = (first >= 0) & (first % sp.kernel_stride == 0)
    at = jnp.maximum(first, 0)[:, None] + jnp.arange(
        sp.kernel_size, dtype=jnp.int32
    )  # (B, kernel)
    ids = jnp.take_along_axis(page_table, at // ps, axis=1)
    keys = k_pool[heads[:, None, None], ids[None], (at % ps)[None], 0]
    # (Nkv, B, kernel, H) -> as a sequence of one window a row and head
    new = compress_keys(
        jnp.moveaxis(keys, 0, 2).reshape(B, sp.kernel_size, -1, keys.shape[-1]),
        sp,
    )[:, :, 0]  # (B, Nkv, H)
    page = jnp.take_along_axis(
        page_table, (jnp.maximum(first, 0) // ps)[:, None], axis=1
    )[:, 0]
    row = (jnp.maximum(first, 0) // sp.kernel_stride) % sp.per_block
    old = kc_pool[heads[:, None], page[None], row[None]]  # (Nkv, B, H)
    new = jnp.where(due[None, :, None], jnp.moveaxis(new, 1, 0), old)
    return kc_pool.at[heads[:, None], page[None], row[None]].set(new)


@scoped("sparse_select")
def _select_step(q, kc_pool, heads, page_table, seq_lens, sp):
    """One query a row: its list of chosen blocks. q (B, Nkv, g, H); the
    row's compressed keys are gathered whole through its table."""
    B, maxp = page_table.shape
    kc = kc_pool[heads[:, None, None], page_table[None]]  # (Nkv, B, maxp, r, H)
    kc = jnp.moveaxis(kc, 0, 1).reshape(B, heads.shape[0], -1, kc.shape[-1])
    key, _, dense = block_keys(q[:, None], kc, seq_lens[:, None], sp)
    return chosen_list(key, dense, sp)


def sala_paged_decode_step(
    params: Params,
    state,
    pools,
    page_table,
    seq_lens,
    tokens,
    cfg: SalaConfig,
    *,
    page_size: int,
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "reference",
    block_kv=None,
):
    """One ragged decode step. tokens (B,) int32 at positions
    ``seq_lens``; state ``{"S"}`` (L_lightning, B, heads, H, H) float32;
    pools ``{"k", "v"}`` (L_sparse * Nkv, P, page_size, 1, H) and
    ``{"kc"}`` (L_sparse * Nkv, P, r, H), the adapter's
    PagedKVCache.pools. A sparse layer writes the position's key and
    value to its page, the compressed key that the position completes
    (if one) to the index cache, chooses the row's blocks through the
    index cache and attends those pages alone (``attn_impl="kernel"``:
    the ragged paged kernel over each (row, kv head)'s list;
    ``"reference"``: gathered); a lightning layer steps its state.
    Returns (logits (B, V), state, pools)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    sp = cfg.sparse
    assert page_size == sp.block_size, (page_size, sp.block_size)
    B = tokens.shape[0]
    nkv, hd = cfg.kvheads, cfg.head_dim
    seq_lens = seq_lens.astype(jnp.int32)
    positions = seq_lens[:, None]
    rows = jnp.arange(B)
    x = _embed(params, tokens[:, None], cfg)
    with jax.named_scope("kv_write"):  # each row's write target
        page_ids = page_table[rows, seq_lens // page_size]
        slots = seq_lens % page_size
    S = state["S"]
    pools = dict(pools)
    P = pools["k"].shape[1]
    lin_scale = cfg.lightning_head_dim**-0.5
    si = li = 0
    with jax.named_scope("layers"):
        for kind, at in layer_places(cfg):
            layer = _layer_at(params[kind], at)
            u = _norm(x, layer["attn_norm"], cfg)
            q, k, v = _qkv(u, layer, cfg, kind, positions)
            if kind == "lightning":
                with jax.named_scope("lin_step"):
                    o, new = lightning_step(
                        q[:, 0], k[:, 0], v[:, 0], S[li], lin_scale
                    )
                    S = S.at[li].set(new)
                li += 1
                x = _mlp(
                    _lightning_out(x, o[:, None], u, layer, cfg), layer, cfg
                )
                continue
            heads = si * nkv + jnp.arange(nkv, dtype=jnp.int32)
            with jax.named_scope("kv_write"):
                at_ = (heads[:, None], page_ids[None], slots[None], 0)
                pools["k"] = pools["k"].at[at_].set(jnp.moveaxis(k[:, 0], 1, 0))
                pools["v"] = pools["v"].at[at_].set(jnp.moveaxis(v[:, 0], 1, 0))
            pools["kc"] = _index_write(
                pools["kc"], pools["k"], heads, page_table, seq_lens, sp
            )
            qg = q[:, 0].reshape(B, nkv, -1, hd)
            blocks, n = _select_step(
                qg, pools["kc"], heads, page_table, seq_lens, sp
            )
            with jax.named_scope("sparse_attn"):
                flat = (-1,) + pools["k"].shape[2:]
                o = chosen_pages_attention(
                    qg, pools["k"].reshape(flat), pools["v"].reshape(flat),
                    page_table, seq_lens, blocks, n, first_page=heads * P,
                    kernel=attn_impl == "kernel", block_kv=block_kv,
                )
            si += 1
            x = _mlp(_sparse_out(x, o[:, None], u, layer, cfg), layer, cfg)
    logits = _head(x, params, cfg)
    return logits[:, 0], {"S": S}, pools
