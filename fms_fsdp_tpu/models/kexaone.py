"""K-EXAONE (``model_type: exaone_moe``): grouped-query attention whose
layers are of two kinds, window and full, over a mixture of many small
experts. The serving family's model file: forward, sequence prefill and
the decode step over a cache of each kind.

**A layer** ``i``, of attention kind ``layer_types[i]`` and feed-forward
kind ``mlp_layer_types[i]``: ``a = x + Attn(RMSNorm(x))``, ``y = a +
FFN(RMSNorm(a))`` (the norms stand before each sub-block); after the last
layer ``RMSNorm`` and the untied head over the vocabulary rows held.

**Attention.** ``q = W_q h`` (``nheads`` heads of ``head_dim``), ``k = W_k
h``, ``v = W_v h`` (``kvheads`` heads), no biases; RMSNorm with a learned
weight over each head's values of ``q`` and of ``k``; on a
``sliding_attention`` layer rotary embedding (the two halves of a head
paired, ``rope_theta``) on ``q`` and ``k``, on a ``full_attention`` layer
none; scores ``q_t . k_u / sqrt(head_dim)`` for ``u <= t`` and, on a
sliding layer, ``t - u < sliding_window`` (a position sees itself and the
``sliding_window - 1`` before it); softmax in float32; ``nheads /
kvheads`` query heads share a kv head; ``W_o`` over the heads.

**What a position leaves behind follows the kind of layer.** A full
layer keeps every position's key and value: pages of a pool that grows
with the context (``serve/kv_cache.py::PagedKVCache`` over the full
layers alone). A window layer can only ever read its last
``sliding_window`` positions, so a stream keeps a **ring** of that many
keys and values a window layer, whatever its context: position ``t`` is
written at ``t mod sliding_window``, keys are rotated before they are
stored and softmax does not care for the order of what it sums, so the
ring needs a validity mask while ``t < sliding_window - 1`` and nothing
else.

**Feed-forward.** ``dense``: a SwiGLU of ``hidden_dim``. ``sparse``: the
held share of ``num_experts`` sigmoid-routed experts beside a shared one,
models/moe_held.py, the code models/sarvam.py runs.

Read by the family's convention where ``config.json`` has no key: the
QK-norm; rotary on the window layers only; norms before each sub-block;
the router's choosing bias.
Left out: the multi-token-prediction module (``models/configs.py::
kexaone_config`` refuses a config that asks for it).

Parameter tree: ``embedding (V, D)``, ``norm``, ``lm_head (D, V)`` and
one dict of stacked leaves for each kind of layer the config has, named
by ``cfg.kind(i)``: ``sliding_dense``, ``sliding_sparse``, ``full_dense``,
``full_sparse``; a layer's index in its stack is its place among the
layers of its kind. Weights have one shape whatever the attention kind.
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from fms_fsdp_tpu.models import sequence_prefill as seq
from fms_fsdp_tpu.models.configs import KExaoneConfig
from fms_fsdp_tpu.models.moe_held import (
    _moe_dense_held,
    _moe_grouped,
    _moe_token,
    _shared,
    _swiglu,
)
from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops.attention import (
    as_ring as _as_ring,
    band_mask,
    chunk_attention,
    masked_attention,
    qkv_by_head,
    window_chunk_attention as _window_chunk_attention,
)
from fms_fsdp_tpu.ops.norms import rms_norm
from fms_fsdp_tpu.ops.paged_attention import (
    gather_pages,
    gqa_attend,
    paged_attention_kernel,
)

__all__ = [
    "KExaoneConfig",
    "init_kexaone_params",
    "kexaone_forward",
    "kexaone_paged_decode_step",
    "kexaone_prefill",
]

Params = Dict[str, Any]

# positions one trip of the prefill's loop takes through the stack: as
# models/sarvam.py::PREFILL_CHUNK, for its reason (every chunk reads every
# held expert once). A constant of the program: no option selects it.
PREFILL_CHUNK = 2048

EXPERT_LEAVES = ("w1", "w3", "w2")


def init_kexaone_params(key, cfg: KExaoneConfig, dtype=jnp.float32) -> Params:
    d, hd = cfg.emb_dim, cfg.head_dim
    held = cfg.held[1]
    std = 0.02
    out_std = std / (2 * cfg.nlayers) ** 0.5
    keys = iter(jax.random.split(key, 64))

    def tn(shape, s=std):
        return (
            jax.random.truncated_normal(next(keys), -3, 3, shape, jnp.float32)
            * s
        ).astype(dtype)

    def stack(kind: str, L: int):
        p = {
            "attn_norm": jnp.ones((L, d), dtype),
            "wq": tn((L, d, cfg.nheads * hd)),
            "wk": tn((L, d, cfg.kvheads * hd)),
            "wv": tn((L, d, cfg.kvheads * hd)),
            "q_norm": jnp.ones((L, hd), dtype),
            "k_norm": jnp.ones((L, hd), dtype),
            "wo": tn((L, cfg.nheads * hd, d), out_std),
            "ffn_norm": jnp.ones((L, d), dtype),
        }
        if kind.endswith("_dense"):
            f = cfg.hidden_dim
            p.update(w1=tn((L, d, f)), w3=tn((L, d, f)),
                     w2=tn((L, f, d), out_std))
            return p
        h = cfg.moe_hidden_dim
        p.update(
            gate=tn((L, d, cfg.num_experts)),
            gate_bias=jnp.zeros((L, cfg.num_experts), dtype),
            w1=tn((L, held, d, h)),
            w3=tn((L, held, d, h)),
            w2=tn((L, held, h, d), out_std),
        )
        if cfg.num_shared_experts:
            hs = cfg.num_shared_experts * h
            p.update(
                shared_w1=tn((L, d, hs)),
                shared_w3=tn((L, d, hs)),
                shared_w2=tn((L, hs, d), out_std),
            )
        return p

    params = {"embedding": tn((cfg.src_vocab_size, d))}
    for kind, layers in cfg.stacks.items():
        params[kind] = stack(kind, len(layers))
    params["norm"] = jnp.ones((d,), dtype)
    params["lm_head"] = tn((d, cfg.src_vocab_size))
    return params


def layer_places(cfg: KExaoneConfig):
    """``[(kind, index in the kind's stack, sliding?, sparse?), ...]`` of
    the layers in order."""
    seen = {}
    out = []
    for i in range(cfg.nlayers):
        kind = cfg.kind(i)
        at = seen[kind] = seen.get(kind, -1) + 1
        out.append((
            kind, at, cfg.layer_types[i] == "sliding_attention",
            cfg.mlp_layer_types[i] == "sparse",
        ))
    return out


def _layer_at(stacked, i: int, without=()):
    return {
        name: a[i] for name, a in stacked.items() if name not in without
    }


# ---------------------------------------------------------------------------
# what every form shares
# ---------------------------------------------------------------------------


@scoped("norm")
def _norm(x, w, cfg):
    return rms_norm(x, w, cfg.norm_eps)


@scoped("mlp")
def _mlp(h, layer):
    return _swiglu(h, layer["w1"], layer["w3"], layer["w2"])


# ---------------------------------------------------------------------------
# forward (whole sequences, no cache): the parity form
# ---------------------------------------------------------------------------


def kexaone_forward(
    params: Params, tokens, cfg: KExaoneConfig, *,
    compute_dtype=jnp.bfloat16, **_unused,
):
    """tokens (B, S) -> logits (B, S, V): masked attention over the whole
    sequence, the held experts' dense mixture."""
    params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B, S = tokens.shape
    pos = jnp.arange(S, dtype=jnp.int32)
    positions = jnp.broadcast_to(pos, (B, S))
    x = params["embedding"][tokens]
    for kind, at, sliding, sparse in layer_places(cfg):
        layer = _layer_at(params[kind], at)
        h = _norm(x, layer["attn_norm"], cfg)
        q, k, v = qkv_by_head(h, layer, cfg, positions, sliding)
        mask = band_mask(pos, pos, cfg.sliding_window if sliding else 0)[None]
        o, _ = masked_attention(q, k, v, mask)
        x = x + o.astype(x.dtype).reshape(B, S, -1) @ layer["wo"]
        h2 = _norm(x, layer["ffn_norm"], cfg)
        if sparse:
            x = x + _moe_dense_held(h2, layer, cfg) + _shared(h2, layer)
        else:
            x = x + _mlp(h2, layer)
    return _norm(x, params["norm"], cfg) @ params["lm_head"]


# ---------------------------------------------------------------------------
# prefill: the prompt as a sequence, a chunk at a time
# ---------------------------------------------------------------------------


def prefill_chunk(p_pad: int) -> int:
    """The chunk of a prompt padded to ``p_pad``: the largest divisor of
    ``p_pad`` up to ``PREFILL_CHUNK``, so that chunks tile the bucket."""
    return seq.chunk_of(p_pad, PREFILL_CHUNK)


def prefill_positions(p: int, p_pad: int) -> int:
    """Positions ``kexaone_prefill`` computes for a prompt of ``p`` tokens
    padded to ``p_pad``: whole chunks up to the prompt's end."""
    return seq.positions_computed(p, prefill_chunk(p_pad))


def _use_flash(cfg: KExaoneConfig, attn_impl: str, c: int) -> bool:
    fits = c % 256 == 0 and cfg.head_dim % 128 == 0
    return fits and seq.kernel_wanted(attn_impl)


def prefill_attn_form(cfg: KExaoneConfig, attn_impl: str, p_pad: int) -> str:
    """What the window and the full layers' attention run in the prefill
    program of ``p_pad`` positions (``attn_form`` on
    ``serve/prefill.dispatch``): the flash kernels (the windowed one over
    the band, the causal one over the blocks) or einsums (off a TPU, and
    odd chunks)."""
    flash = _use_flash(cfg, attn_impl, prefill_chunk(p_pad))
    return "flash_window+flash" if flash else "einsum"


def kexaone_prefill(
    params: Params,
    tokens,
    lengths,
    cfg: KExaoneConfig,
    *,
    compute_dtype=jnp.bfloat16,
    kv_len: int = 0,
    attn_impl: str = "auto",
    moe_impl: str = "routed",
):
    """Prompt prefill. tokens (B, S_pad) int32, lengths (B,) int32 the
    prompts' lengths (<= S_pad). ``prefill_chunk(S_pad)`` positions at a
    time go through every layer, in one loop whose trip count is read
    from ``lengths`` on the device. From chunk to chunk go: each full
    layer's keys and values written so far (a later chunk walks them
    block by block, ``ops/attention.py::chunk_attention``), each window
    layer's last ``sliding_window`` positions (a later chunk walks
    nothing else of the past), and each row's residual at its last real
    position. ``moe_impl="routed"`` groups each chunk's pairs by held
    expert (``models/moe_held.py::_moe_grouped``); ``"dense"`` runs every
    held expert over every row (the parity form).

    Returns (logits (B, V) of each row's last real position; the full
    layers' ``{"k", "v"}`` (L_full, B, kv_len, Nkv, H), zero past each
    row's length, for the pages; the window layers' rings ``{"k", "v"}``
    (L_window, B, sliding_window, Nkv, H), position ``t`` at ``t mod
    sliding_window``; the number of (token, choice) pairs of the
    positions computed that landed on held experts, summed over the
    sparse layers; the trips the grouped product's loop took for them,
    one a layer and chunk unless its pairs overran a slab; and the row
    tiles a product of those trips met)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B, S = tokens.shape
    c = prefill_chunk(S)
    kv_len = kv_len or S
    assert kv_len >= S, (kv_len, S)
    W = cfg.sliding_window
    flash = _use_flash(cfg, attn_impl, c)
    places = layer_places(cfg)
    n_full, n_win = len(cfg.full_layers), len(cfg.window_layers)
    kv_shape = (B, kv_len, cfg.kvheads, cfg.head_dim)
    tail_shape = (B, W, cfg.kvheads, cfg.head_dim)
    # a sparse layer is sliced out of its stack but for the routed
    # experts: the grouped matmul reads those where they lie
    experts = {
        kind: {name: params[kind][name] for name in EXPERT_LEAVES}
        for kind in cfg.stacks if kind.endswith("_sparse")
    }

    def body(chunk, carry):
        full, tails, last, pairs, slabs, tiles = carry
        full, tails = list(full), list(tails)
        start, ahead, positions = chunk.start, chunk.ahead, chunk.positions
        with jax.named_scope("embed"):
            toks = lax.dynamic_slice_in_dim(tokens, start, c, axis=1)
            x = params["embedding"][toks]
        fi = wi = 0
        with jax.named_scope("layers"):
            for kind, at, sliding, sparse in places:
                routed = sparse and moe_impl == "routed"
                layer = _layer_at(
                    params[kind], at, EXPERT_LEAVES if routed else ()
                )
                h = _norm(x, layer["attn_norm"], cfg)
                q, k, v = qkv_by_head(h, layer, cfg, positions, sliding)
                if sliding:
                    tk, tv = tails[wi]
                    o = _window_chunk_attention(
                        q, k, v, tk, tv, start, W, flash
                    )
                    tails[wi] = (
                        seq.next_tail(tk, k, ahead, W),
                        seq.next_tail(tv, v, ahead, W),
                    )
                    wi += 1
                else:
                    with jax.named_scope("kv_write"):
                        kb, vb = seq.write_live(
                            full[fi], (k, v), chunk.live, start
                        )
                    with jax.named_scope("attn_full"):
                        o = chunk_attention(
                            q, kb, vb, start,
                            impl="pallas" if flash else "xla",
                        )
                    full[fi] = (kb, vb)
                    fi += 1
                with jax.named_scope("attn_out"):
                    x = x + o.reshape(B, c, -1) @ layer["wo"]
                h2 = _norm(x, layer["ffn_norm"], cfg)
                if not sparse:
                    x = x + _mlp(h2, layer)
                    continue
                if routed:
                    y, n, trips, met = _moe_grouped(
                        h2.reshape(B * c, -1), layer, cfg, experts[kind], at
                    )
                    y = y.reshape(B, c, -1)
                    pairs, slabs, tiles = pairs + n, slabs + trips, tiles + met
                else:
                    y = _moe_dense_held(h2, layer, cfg)
                with jax.named_scope("moe_combine"):
                    x = x + y + _shared(h2, layer)
        return x, (tuple(full), tuple(tails), last, pairs, slabs, tiles)

    def zeros(shape):
        return jnp.zeros(shape, compute_dtype)

    full, tails, last, pairs, slabs, tiles = seq.chunk_loop(
        lengths, c, body,
        lambda: (
            tuple((zeros(kv_shape), zeros(kv_shape)) for _ in range(n_full)),
            tuple(
                (zeros(tail_shape), zeros(tail_shape)) for _ in range(n_win)
            ),
            zeros((B, cfg.emb_dim)),
            *(jnp.zeros((), jnp.int32),) * 3,
        ),
        last=2,
    )
    with jax.named_scope("lm_head"):
        logits = _norm(last, params["norm"], cfg) @ params["lm_head"]
    kv = {
        name: seq.stack_or_empty(
            [p[i] for p in full], kv_shape, compute_dtype
        )
        for i, name in enumerate(("k", "v"))
    }
    ring = {
        name: seq.stack_or_empty(
            [_as_ring(p[i], lengths, W) for p in tails], tail_shape,
            compute_dtype,
        )
        for i, name in enumerate(("k", "v"))
    }
    return logits, kv, ring, pairs, slabs, tiles


# ---------------------------------------------------------------------------
# decode: one ragged step over rings (window layers) and pages (full layers)
# ---------------------------------------------------------------------------


@scoped("attn_window")
def _ring_attend(q, ring_k, ring_v, seq_lens):
    """One query a row over its ring: q (B, N, H), ring_k/ring_v (B, W,
    Nkv, H) with the row's position ``seq_lens[b]`` already written; entry
    ``r`` holds a position of this stream iff ``r <= seq_lens[b]`` (every
    entry once the ring has wrapped). Plain jax. Returns (B, N * H)."""
    W = ring_k.shape[1]
    mask = jnp.arange(W, dtype=jnp.int32)[None, :] <= seq_lens[:, None]
    o, _ = masked_attention(q[:, None], ring_k, ring_v, mask[:, None, :])
    return o.astype(q.dtype).reshape(q.shape[0], -1)


def _pages_attend(q, pools, lf, page_table, seq_lens, kernel, block_kv):
    """One query a row over layer ``lf`` of the full layers' pools
    ``{"k", "v"}`` (L_full, P, page_size, Nkv, H), row ``b`` seeing cache
    positions <= seq_lens[b]. The pools are seen as one run of ``L_full *
    P`` pages and the table's ids moved into the layer's part of it, so no
    layer's slice of a pool is ever made. ``kernel``: the ragged paged
    kernel (``ops/paged_attention.py::paged_attention_kernel``), each
    row's own pages read where they lie; else gather and attend in plain
    jax. q (B, N, H) -> (B, N * H)."""
    L, P = pools["k"].shape[:2]
    k_pages = pools["k"].reshape((L * P,) + pools["k"].shape[2:])
    v_pages = pools["v"].reshape((L * P,) + pools["v"].shape[2:])
    table = page_table + lf * P
    if kernel:
        with jax.named_scope("attn_full"):
            return paged_attention_kernel(
                q, k_pages, v_pages, table, seq_lens, block_kv=block_kv
            )
    with jax.named_scope("kv_read"):
        k = gather_pages(k_pages, table)
        v = gather_pages(v_pages, table)
    with jax.named_scope("attn_full"):
        return gqa_attend(q[:, None], k, v, seq_lens[:, None])[:, 0]


def kexaone_paged_decode_step(
    params: Params,
    ring,
    pools,
    page_table,
    seq_lens,
    tokens,
    cfg: KExaoneConfig,
    *,
    page_size: int,
    compute_dtype=jnp.bfloat16,
    moe_impl: str = "routed",
    attn_impl: str = "reference",
    block_kv=None,
):
    """One ragged decode step. tokens (B,) int32 at positions
    ``seq_lens``; ring ``{"k", "v"}`` (L_window, B, sliding_window, Nkv,
    H), the window layers' per-slot rings; pools ``{"k", "v"}`` (L_full,
    P, page_size, Nkv, H), the adapter's PagedKVCache.pools. A window
    layer writes the position's key and value at ``seq_lens mod
    sliding_window`` of its ring and attends the ring; a full layer writes
    them to its page and attends the stream's pages
    (``attn_impl="kernel"``: the ragged paged kernel; ``"reference"``:
    gathered). Returns (logits (B, V), ring, pools)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B = tokens.shape[0]
    W = cfg.sliding_window
    seq_lens = seq_lens.astype(jnp.int32)
    positions = seq_lens[:, None]
    rows = jnp.arange(B)
    with jax.named_scope("embed"):
        x = params["embedding"][tokens[:, None]]
    with jax.named_scope("kv_write"):  # each row's write target
        page_ids = page_table[rows, seq_lens // page_size]
        slots = seq_lens % page_size
    with jax.named_scope("win_write"):
        ring_at = seq_lens % W
    ring_k, ring_v = ring["k"], ring["v"]
    pools = dict(pools)
    wi = fi = 0
    with jax.named_scope("layers"):
        for kind, at, sliding, sparse in layer_places(cfg):
            layer = _layer_at(params[kind], at)
            h = _norm(x, layer["attn_norm"], cfg)
            q, k, v = qkv_by_head(h, layer, cfg, positions, sliding)
            if sliding:
                with jax.named_scope("win_write"):
                    ring_k = ring_k.at[wi, rows, ring_at].set(k[:, 0])
                    ring_v = ring_v.at[wi, rows, ring_at].set(v[:, 0])
                o = _ring_attend(q[:, 0], ring_k[wi], ring_v[wi], seq_lens)
                wi += 1
            else:
                with jax.named_scope("kv_write"):
                    pools["k"] = pools["k"].at[fi, page_ids, slots].set(k[:, 0])
                    pools["v"] = pools["v"].at[fi, page_ids, slots].set(v[:, 0])
                o = _pages_attend(
                    q[:, 0], pools, fi, page_table, seq_lens,
                    attn_impl == "kernel", block_kv,
                )
                fi += 1
            with jax.named_scope("attn_out"):
                x = x + o.reshape(B, 1, -1) @ layer["wo"]
            h2 = _norm(x, layer["ffn_norm"], cfg)
            if sparse:
                y = _moe_token(h2, layer, cfg, moe_impl)
                with jax.named_scope("moe_combine"):
                    x = x + y + _shared(h2, layer)
            else:
                x = x + _mlp(h2, layer)
    with jax.named_scope("lm_head"):
        logits = _norm(x, params["norm"], cfg) @ params["lm_head"]
    return logits[:, 0], {"k": ring_k, "v": ring_v}, pools
