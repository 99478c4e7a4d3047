"""LFM2-MoE (``model_type: lfm2_moe``): gated short convolutions beside a
few grouped-query attention layers, over a mixture of many small experts.
The serving family's model file: forward, sequence prefill and the decode
step over a window of each convolution layer and pages of each attention
layer.

**A layer** ``i``: ``a = x + Op_i(RMSNorm(x))``, ``y = a +
FF_i(RMSNorm(a))`` (``operator_norm``, ``ffn_norm``); after the last layer
``RMSNorm`` (``norm_f``, the family's ``embedding_norm``) and the head,
which is the embedding.

**Short convolution** (``layer_types[i] == "conv"``). ``[B, C, x] = W_in
u`` (``D -> 3 D``, split in that order); ``z_t = B_t * x_t``; ``c_t = sum_j
w[:, j] * z_{t - K + 1 + j}`` by channel (depthwise, causal, ``K`` =
``conv_kernel`` taps, ``z`` zero before the sequence starts); ``out_t =
W_out (C_t * c_t)``. No bias, no activation. All a later position can
read of a stream is its last ``K - 1`` values of ``z``: the **window** a
slot keeps, ``(K - 1, D)`` a convolution layer whatever the context.

**Attention** (``"full_attention"``). ``q = W_q u`` (``nheads`` heads of
``head_dim = emb_dim / nheads``), ``k = W_k u``, ``v = W_v u``
(``kvheads`` heads); RMSNorm with a learned weight over each head's values
of ``q`` and of ``k``, then rotary embedding over the whole head (the two
halves paired, ``rope_theta``): ops/attention.py's ``qkv_by_head``, which
kexaone's window layers run too; causal softmax of ``q_t . k_u /
sqrt(head_dim)`` in float32; ``W_o``. Keys and values live in pages
(``serve/kv_cache.py::PagedKVCache`` over the attention layers alone)
as rows of 128 lanes, a position's ``kvheads * head_dim`` values side by
side in ``tile_rows`` of them: two heads of 64 a row, nothing padded,
what ``ops/paged_attention.py::packed_pages_attention_kernel`` reads.

**Feed-forward.** A dense SwiGLU of ``hidden_dim`` in the first
``num_dense_layers`` layers; after them ``num_experts`` sigmoid-routed
experts, ``top_k`` a token, no shared one, the chosen scores normalised
over ``sum + router_sum_eps``: models/moe_held.py, the code sarvam and
kexaone run.

Read by the family's convention where ``config.json`` has no key: the
tied head; heads of ``emb_dim / nheads``; the QK-norm before the rotary
and its pairing by halves; the ``1e-6`` under the router's sum; no
activation in the convolution.

Parameter tree: ``embedding (V, D)``, ``norm_f (D,)`` and ``layers``, a
list of one dict a layer: ``operator_norm``, ``ffn_norm``; a convolution
layer's ``in_proj (D, 3 D)``, ``conv_w (D, K)``, ``out_proj (D, D)`` or an
attention layer's ``wq``, ``wk``, ``wv``, ``q_norm``, ``k_norm``, ``wo``;
a dense layer's ``w1``, ``w3``, ``w2`` or an expert layer's ``gate``,
``gate_bias`` and ``w1``/``w3``/``w2`` with the held experts leading.
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from fms_fsdp_tpu.models import sequence_prefill as seq
from fms_fsdp_tpu.models.configs import Lfm2MoeConfig
from fms_fsdp_tpu.models.moe_held import (
    _moe_grouped,
    _moe_token,
    _router,
    _swiglu,
)
from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops import flash_attention as _fa
from fms_fsdp_tpu.ops.attention import (
    band_mask,
    chunk_attention,
    masked_attention,
    qkv_by_head,
)
from fms_fsdp_tpu.ops.norms import rms_norm
from fms_fsdp_tpu.ops.paged_attention import (
    gqa_attend,
    packed_pages_attention_kernel,
    packed_row_width,
    tile_rows,
)

__all__ = [
    "Lfm2MoeConfig",
    "init_lfm2_params",
    "lfm2_forward",
    "lfm2_paged_decode_step",
    "lfm2_prefill",
]

Params = Dict[str, Any]

# positions one trip of the prefill's loop takes through the stack: as
# models/kexaone.py::PREFILL_CHUNK, for its reason (every chunk reads every
# expert once). A constant of the program: no option selects it.
PREFILL_CHUNK = 2048


def init_lfm2_params(key, cfg: Lfm2MoeConfig, dtype=jnp.float32) -> Params:
    d, hd = cfg.emb_dim, cfg.head_dim
    held = cfg.held[1]
    std = 0.02
    out_std = std / (2 * cfg.nlayers) ** 0.5
    keys = iter(jax.random.split(key, 16 * cfg.nlayers + 4))

    def tn(shape, s=std):
        return (
            jax.random.truncated_normal(next(keys), -3, 3, shape, jnp.float32)
            * s
        ).astype(dtype)

    def layer(i: int):
        p = {"operator_norm": jnp.ones((d,), dtype)}
        if cfg.layer_types[i] == "conv":
            p.update(
                in_proj=tn((d, 3 * d)),
                # taps of unit sum of squares: ``c`` keeps ``z``'s scale
                conv_w=tn((d, cfg.conv_kernel), cfg.conv_kernel**-0.5),
                out_proj=tn((d, d), out_std),
            )
        else:
            p.update(
                wq=tn((d, cfg.nheads * hd)),
                wk=tn((d, cfg.kvheads * hd)),
                wv=tn((d, cfg.kvheads * hd)),
                q_norm=jnp.ones((hd,), dtype),
                k_norm=jnp.ones((hd,), dtype),
                wo=tn((cfg.nheads * hd, d), out_std),
            )
        p["ffn_norm"] = jnp.ones((d,), dtype)
        if not cfg.sparse(i):
            f = cfg.hidden_dim
            p.update(w1=tn((d, f)), w3=tn((d, f)), w2=tn((f, d), out_std))
            return p
        h = cfg.moe_hidden_dim
        p.update(
            gate=tn((d, cfg.num_experts)),
            gate_bias=jnp.zeros((cfg.num_experts,), dtype),
            w1=tn((held, d, h)),
            w3=tn((held, d, h)),
            w2=tn((held, h, d), out_std),
        )
        return p

    return {
        "embedding": tn((cfg.src_vocab_size, d)),
        "layers": [layer(i) for i in range(cfg.nlayers)],
        "norm_f": jnp.ones((d,), dtype),
    }


# ---------------------------------------------------------------------------
# what every form shares
# ---------------------------------------------------------------------------


@scoped("norm")
def _norm(x, w, cfg):
    return rms_norm(x, w, cfg.norm_eps)


@scoped("dense_mlp")
def _mlp(h, layer):
    return _swiglu(h, layer["w1"], layer["w3"], layer["w2"])


@scoped("head")
def _head(x, params, cfg):
    """The final norm and the tied head over rows x (..., D)."""
    x = rms_norm(x, params["norm_f"], cfg.norm_eps)
    return jnp.einsum("...d,vd->...v", x, params["embedding"])


@scoped("conv_in")
def _conv_in(h, layer):
    """h (B, S, D) -> (z = B * x, C), each (B, S, D)."""
    b, c, x = jnp.split(h @ layer["in_proj"], 3, axis=-1)
    return b * x, c


@scoped("short_conv")
def _short_conv(tail, z, w):
    """The causal depthwise convolution of z (B, S, D) behind the ``K -
    1`` positions before it, tail (B, K - 1, D) (zeros where the sequence
    starts): ``c_t = sum_j w[:, j] * z_{t - K + 1 + j}``, summed in
    float32. w (D, K). Returns (B, S, D)."""
    S, K = z.shape[1], w.shape[1]
    ext = jnp.concatenate([tail, z], axis=1).astype(jnp.float32)
    taps = w.astype(jnp.float32)
    c = sum(ext[:, j:j + S] * taps[:, j] for j in range(K))
    return c.astype(z.dtype)


@scoped("conv_out")
def _conv_out(c, gate, layer):
    return (gate * c) @ layer["out_proj"]


def _ffn_forward(h2, layer, cfg, sparse: bool):
    """The parity form of a layer's feed-forward: every held expert over
    every row."""
    if not sparse:
        return _mlp(h2, layer)
    return _moe_token(h2, layer, cfg, "dense")


# ---------------------------------------------------------------------------
# forward (whole sequences, no cache): the parity form
# ---------------------------------------------------------------------------


def lfm2_forward(
    params: Params, tokens, cfg: Lfm2MoeConfig, *,
    compute_dtype=jnp.bfloat16, **_unused,
):
    """tokens (B, S) -> logits (B, S, V): the convolution behind a window
    of zeros, masked attention over the whole sequence, the held experts'
    dense mixture."""
    params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B, S = tokens.shape
    pos = jnp.arange(S, dtype=jnp.int32)
    positions = jnp.broadcast_to(pos, (B, S))
    x = params["embedding"][tokens]
    for i, layer in enumerate(params["layers"]):
        h = _norm(x, layer["operator_norm"], cfg)
        if cfg.layer_types[i] == "conv":
            z, gate = _conv_in(h, layer)
            tail = jnp.zeros((B, cfg.conv_kernel - 1, cfg.emb_dim), z.dtype)
            x = x + _conv_out(_short_conv(tail, z, layer["conv_w"]), gate, layer)
        else:
            q, k, v = qkv_by_head(h, layer, cfg, positions, True)
            o, _ = masked_attention(q, k, v, band_mask(pos, pos, 0)[None])
            x = x + o.astype(x.dtype).reshape(B, S, -1) @ layer["wo"]
        h2 = _norm(x, layer["ffn_norm"], cfg)
        x = x + _ffn_forward(h2, layer, cfg, cfg.sparse(i))
    return _head(x, params, cfg)


# ---------------------------------------------------------------------------
# prefill: the prompt as a sequence, a chunk at a time
# ---------------------------------------------------------------------------


def prefill_chunk(p_pad: int) -> int:
    """The chunk of a prompt padded to ``p_pad``: the largest divisor of
    ``p_pad`` up to ``PREFILL_CHUNK``, so that chunks tile the program."""
    return seq.chunk_of(p_pad, PREFILL_CHUNK)


def prefill_positions(p: int, p_pad: int) -> int:
    """Positions ``lfm2_prefill`` computes for a prompt of ``p`` tokens
    in a program of ``p_pad``: whole chunks up to the prompt's end."""
    return seq.positions_computed(p, prefill_chunk(p_pad))


def _use_flash(cfg: Lfm2MoeConfig, attn_impl: str, c: int) -> bool:
    return _fa.supports(
        (1, c, cfg.nheads, cfg.head_dim), (1, c, cfg.kvheads, cfg.head_dim),
        forward_only=True,
    ) and seq.kernel_wanted(attn_impl)


def prefill_attn_form(cfg: Lfm2MoeConfig, attn_impl: str, p_pad: int) -> str:
    """What the attention layers run in the prefill program of ``p_pad``
    positions (``attn_form`` on ``serve/prefill.dispatch``): the causal
    flash kernel over blocks of heads ``head_dim`` wide, or einsums (off a
    TPU, and odd chunks)."""
    flash = _use_flash(cfg, attn_impl, prefill_chunk(p_pad))
    return f"flash_head{cfg.head_dim}" if flash else "einsum"


def lfm2_prefill(
    params: Params,
    tokens,
    lengths,
    cfg: Lfm2MoeConfig,
    *,
    compute_dtype=jnp.bfloat16,
    kv_len: int = 0,
    attn_impl: str = "auto",
    moe_impl: str = "routed",
):
    """Prompt prefill. tokens (B, S_pad) int32, lengths (B,) int32 the
    prompts' lengths (<= S_pad). ``prefill_chunk(S_pad)`` positions at a
    time go through every layer, in one loop whose trip count is read
    from ``lengths`` on the device. From chunk to chunk go: each
    convolution layer's last ``conv_kernel - 1`` values of ``z`` (all a
    later chunk reads of the past there), each attention layer's keys and
    values written so far (a later chunk walks them block by block,
    ``ops/attention.py::chunk_attention``), and each row's residual at
    its last real position. ``moe_impl="routed"`` groups each chunk's
    pairs by expert (``models/moe_held.py::_moe_grouped``); ``"dense"``
    runs every held expert over every row (the parity form).

    Returns (logits (B, V) of each row's last real position; the
    attention layers' ``{"k", "v"}`` (L_attn, B, kv_len * tile_rows,
    128), zero past each row's length, as the pages hold them; the convolution layers'
    windows ``{"z"}`` (L_conv, B, conv_kernel - 1, D), the last values of
    ``z`` of each row's prompt, oldest first, zeros before its start; the
    number of (token, choice) pairs of the positions computed that landed
    on held experts, summed over the expert layers; the trips the
    grouped product's loop took for them; and the row tiles a product of
    those trips met)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B, S = tokens.shape
    c = prefill_chunk(S)
    kv_len = kv_len or S
    assert kv_len >= S, (kv_len, S)
    W = cfg.conv_kernel - 1
    flash = _use_flash(cfg, attn_impl, c)
    n_attn, n_conv = len(cfg.attn_layers), len(cfg.conv_layers)
    kv_shape = (B, kv_len, cfg.kvheads, cfg.head_dim)
    tail_shape = (B, W, cfg.emb_dim)

    def body(chunk, carry):
        kvs, tails, last, pairs, slabs, tiles = carry
        kvs, tails = list(kvs), list(tails)
        start, ahead, positions = chunk.start, chunk.ahead, chunk.positions
        with jax.named_scope("embed"):
            toks = lax.dynamic_slice_in_dim(tokens, start, c, axis=1)
            x = params["embedding"][toks]
        ai = ci = 0
        with jax.named_scope("layers"):
            for i, layer in enumerate(params["layers"]):
                routed = cfg.sparse(i) and moe_impl == "routed"
                h = _norm(x, layer["operator_norm"], cfg)
                if cfg.layer_types[i] == "conv":
                    z, gate = _conv_in(h, layer)
                    conv = _short_conv(tails[ci], z, layer["conv_w"])
                    with jax.named_scope("short_conv"):
                        tails[ci] = seq.next_tail(tails[ci], z, ahead, W)
                    x = x + _conv_out(conv, gate, layer)
                    ci += 1
                else:
                    q, k, v = qkv_by_head(h, layer, cfg, positions, True)
                    with jax.named_scope("kv_write"):
                        kb, vb = seq.write_live(
                            kvs[ai], (k, v), chunk.live, start
                        )
                    with jax.named_scope("attn_full"):
                        o = chunk_attention(
                            q, kb, vb, start,
                            impl="pallas" if flash else "xla",
                        )
                    kvs[ai] = (kb, vb)
                    ai += 1
                    with jax.named_scope("attn_out"):
                        x = x + o.reshape(B, c, -1) @ layer["wo"]
                h2 = _norm(x, layer["ffn_norm"], cfg)
                if not routed:
                    x = x + _ffn_forward(h2, layer, cfg, cfg.sparse(i))
                    continue
                y, n, trips, met = _moe_grouped(
                    h2.reshape(B * c, -1), layer, cfg
                )
                pairs, slabs, tiles = pairs + n, slabs + trips, tiles + met
                with jax.named_scope("moe_combine"):
                    x = x + y.reshape(B, c, -1)
        return x, (tuple(kvs), tuple(tails), last, pairs, slabs, tiles)

    def zeros(shape):
        return jnp.zeros(shape, compute_dtype)

    kvs, tails, last, pairs, slabs, tiles = seq.chunk_loop(
        lengths, c, body,
        lambda: (
            tuple((zeros(kv_shape), zeros(kv_shape)) for _ in range(n_attn)),
            tuple(zeros(tail_shape) for _ in range(n_conv)),
            zeros((B, cfg.emb_dim)),
            *(jnp.zeros((), jnp.int32),) * 3,
        ),
        last=2,
    )
    logits = _head(last, params, cfg)
    kv = {
        name: seq.stack_or_empty(
            [p[i] for p in kvs], kv_shape, compute_dtype
        ).reshape(n_attn, B, -1, packed_row_width(cfg.kvheads, cfg.head_dim))
        for i, name in enumerate(("k", "v"))
    }
    z = seq.stack_or_empty(list(tails), tail_shape, compute_dtype)
    return logits, kv, {"z": z}, pairs, slabs, tiles


# ---------------------------------------------------------------------------
# decode: one ragged step over windows (convolution layers) and pages
# ---------------------------------------------------------------------------


def _pages_attend(q, pools, la, page_table, seq_lens, cfg, kernel, block_kv):
    """One query a row over layer ``la`` of the attention layers' pools
    ``{"k", "v"}`` (L_attn, P, page_size * tile_rows, 128 lanes), row
    ``b`` seeing cache positions <= seq_lens[b]: models/kexaone.py's
    ``_pages_attend`` over pages of rows of 128 lanes. ``kernel``: the
    ragged paged kernel with two heads of 64 a tile
    (``ops/paged_attention.py::packed_pages_attention_kernel``); else
    gather and attend in plain jax. q (B, N, H) -> (B, N * H)."""
    L, P = pools["k"].shape[:2]
    k_pages = pools["k"].reshape((L * P,) + pools["k"].shape[2:])
    v_pages = pools["v"].reshape((L * P,) + pools["v"].shape[2:])
    table = page_table + la * P
    if kernel:
        with jax.named_scope("attn_full"):
            return packed_pages_attention_kernel(
                q, k_pages, v_pages, table, seq_lens, nkv=cfg.kvheads,
                block_kv=block_kv,
            )
    with jax.named_scope("kv_read"):
        # (B, max_pages, rows a page, 128) -> (B, positions, Nkv, H)
        k, v = (
            pages[table].reshape(
                table.shape[0], -1, cfg.kvheads, cfg.head_dim
            )
            for pages in (k_pages, v_pages)
        )
    with jax.named_scope("attn_full"):
        return gqa_attend(q[:, None], k, v, seq_lens[:, None])[:, 0]


@scoped("moe_router")
def _experts_touched(idx, live, cfg):
    """The experts some live row chose: idx (B, 1, K) ids over all
    ``num_experts``, live (B,) bool -> () int32."""
    chose = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.bool_)
    chose = chose & live[:, None, None, None]
    return jnp.sum(jnp.any(chose, axis=(0, 1, 2)), dtype=jnp.int32)


def lfm2_paged_decode_step(
    params: Params,
    windows,
    pools,
    page_table,
    seq_lens,
    tokens,
    cfg: Lfm2MoeConfig,
    *,
    page_size: int,
    compute_dtype=jnp.bfloat16,
    moe_impl: str = "routed",
    attn_impl: str = "reference",
    block_kv=None,
):
    """One ragged decode step. tokens (B,) int32 at positions
    ``seq_lens`` (0: a slot that holds no stream); windows ``{"z"}``
    (L_conv, B, conv_kernel - 1, D), the convolution layers' per-slot
    windows, oldest first; pools ``{"k", "v"}`` (L_attn, P, page_size *
    tile_rows, 128), the adapter's PagedKVCache.pools. A convolution layer reads
    its window, computes the position and shifts ``z`` in (a dead slot's
    window stays as it was); an attention layer writes the position's key
    and value to its page and attends the stream's pages
    (``attn_impl="kernel"``: the ragged paged kernel; ``"reference"``:
    gathered). Returns (logits (B, V), windows, pools, counts (2,) int32:
    the (layer, expert) pairs some live stream chose, and the (row,
    choice) pairs the live streams routed, over the expert layers)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B = tokens.shape[0]
    seq_lens = seq_lens.astype(jnp.int32)
    positions = seq_lens[:, None]
    live = seq_lens > 0  # a prompt is never empty
    rows = jnp.arange(B)
    with jax.named_scope("embed"):
        x = params["embedding"][tokens[:, None]]
    tr = tile_rows(cfg.kvheads, cfg.head_dim)
    with jax.named_scope("kv_write"):  # each row's write target
        page_ids = page_table[rows, seq_lens // page_size][:, None]
        slots = (seq_lens % page_size)[:, None] * tr + jnp.arange(tr)
    wins = windows["z"]
    pools = dict(pools)
    touched = jnp.zeros((), jnp.int32)
    ci = ai = 0
    with jax.named_scope("layers"):
        for i, layer in enumerate(params["layers"]):
            h = _norm(x, layer["operator_norm"], cfg)
            if cfg.layer_types[i] == "conv":
                z, gate = _conv_in(h, layer)
                conv = _short_conv(wins[ci], z, layer["conv_w"])
                with jax.named_scope("short_conv"):
                    new = jnp.concatenate([wins[ci][:, 1:], z], axis=1)
                    wins = wins.at[ci].set(
                        jnp.where(live[:, None, None], new, wins[ci])
                    )
                x = x + _conv_out(conv, gate, layer)
                ci += 1
            else:
                q, k, v = qkv_by_head(h, layer, cfg, positions, True)
                with jax.named_scope("kv_write"):
                    pools["k"] = pools["k"].at[ai, page_ids, slots].set(
                        k.reshape(B, tr, -1)
                    )
                    pools["v"] = pools["v"].at[ai, page_ids, slots].set(
                        v.reshape(B, tr, -1)
                    )
                o = _pages_attend(
                    q[:, 0], pools, ai, page_table, seq_lens, cfg,
                    attn_impl == "kernel", block_kv,
                )
                ai += 1
                with jax.named_scope("attn_out"):
                    x = x + o.reshape(B, 1, -1) @ layer["wo"]
            h2 = _norm(x, layer["ffn_norm"], cfg)
            if not cfg.sparse(i):
                x = x + _mlp(h2, layer)
                continue
            idx, w = _router(h2, layer, cfg)
            touched = touched + _experts_touched(idx, live, cfg)
            y = _moe_token(h2, layer, cfg, moe_impl, routed=(idx, w))
            with jax.named_scope("moe_combine"):
                x = x + y
    logits = _head(x, params, cfg)
    with jax.named_scope("moe_router"):
        pairs = (
            jnp.sum(live, dtype=jnp.int32) * cfg.top_k * cfg.n_moe_layers
        )
    return (
        logits[:, 0], {"z": wins}, pools, jnp.stack([touched, pairs]),
    )
