"""Phi-4-mini-flash (``model_type: phi4flash``; the "SambaY"
decoder-hybrid-decoder of arXiv:2507.06607 with the Differential
Attention of arXiv:2410.05258). The serving family's model file: forward,
the sequence prefill that stops half way, and the decode step over a ring,
a slab and one layer's pages.

**A layer** ``l``: ``h = x + Mix_l(LN(x))``, ``y = h + MLP_l(LN(h))``
(LayerNorm with weight and bias; ``MLP(h) = W2 (silu(W1 h) * (W3 h))``, no
bias); after the last layer a LayerNorm and the head, which is the
embedding. No positional embedding anywhere: the state-space layers carry
the order. ``cfg.kind(l)`` (models/configs.py::Phi4FlashConfig) says what
``Mix_l`` is, with ``half = nlayers / 2``:

- ``mamba`` (even ``l <= half``): the Mamba-1 mixer of models/mamba1.py
  without Jamba's three norms. Layer ``half`` also hands out its scan
  output ``M`` (``d_inner`` wide, with the ``D`` skip, before the gate).
  A stream keeps its slab: the conv's last ``d_conv - 1`` inputs and the
  float32 scan state.
- ``window`` (odd ``l < half``): differential attention over the last
  ``sliding_window`` positions, the query's own among them. A stream
  keeps a **ring** of that many keys and values, position ``t`` at ``t
  mod sliding_window``.
- ``full`` (``l = half + 1``): differential attention over every earlier
  position. Its keys and values are **the only cache of the second
  half**: pages, ``2 kvheads head_dim`` values a position once.
- ``gmu`` (even ``l > half``): ``W_out (silu(W_in h) * M)`` with ``M`` of
  the same position. Keeps nothing.
- ``cross`` (odd ``l > half + 1``): differential attention with a query
  and an output projection alone, over the full layer's keys and values
  of positions ``<= t``. Keeps nothing.

**Differential attention** runs as rows of two heads (ops/attention.py,
"differential attention"): a pair of key heads side by side is a row of
``2 head_dim`` lanes (128 at the published heads of 64), a pair's two
value heads the same row of the value cache; ring and pages hold
``kvheads / 2`` such rows a position. Query heads stand in their half of
a row (``diff_rows``), the two softmaxes are one grouped-query attention
of ``nheads`` heads over ``kvheads / 2`` heads of ``2 head_dim``
(scores' factor ``head_dim ** -0.5``), and ``diff_combine`` takes the
difference in float32, norms it by head and scales it. Three settings:
a prompt's chunk of a window layer (the windowed flash kernel over the
band), and the ring and the pages of a decode step (the ragged paged
kernel where ``attn_impl="kernel"``, a slot's ring read as its own few
pages, the float32 accumulator handed out; else gathered).

**A prefill stops half way.** Nothing behind the full layer leaves state,
so a prompt's positions other than its last go through layers ``0`` to
``half`` and the full layer's key and value projection alone
(``chunk_loop``'s body); then the full layer's attention and MLP and the
layers behind it run **for each prompt's last position alone**
(``_cross_decoder``, the code a decode step runs), with ``M`` of that
position. This is what the architecture defines a prefill to be: no
option chooses it.

Read by the family's convention where ``config.json`` has no key: the
Mamba sizes; the layer rule; ``M``; the pairing by neighbours, ``lambda``
and its init by layer index, the norm by head and the ``1 - lambda_init``
factor; biases on the attention projections; no rotary; a window that
counts the query's own position; a float32 scan state
(benchmark/configs/phi-4-mini-flash.1chip.json lists them under
``assumed``).

Parameter tree: ``embedding (V, D)``, ``norm_f {weight, bias}`` and
``layers``, a list of one dict a layer: ``norm``, ``norm2`` (``{weight,
bias}``), ``mlp {w1, w3, w2}`` and ``mixer``: a Mamba layer's
models/mamba1.py leaves (no ``dt_norm``, ``B_norm``, ``C_norm``); a gated
memory unit's ``in_proj (D, d_inner)``, ``out_proj``; an attention
layer's ``wq, bq, wo, bo``, ``lambda_q1, lambda_k1, lambda_q2, lambda_k2
(head_dim,)``, ``subln (2 head_dim,)`` and, unless it is a cross layer,
``wk, bk, wv, bv``.
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from fms_fsdp_tpu.models import sequence_prefill as seq
from fms_fsdp_tpu.models.configs import Phi4FlashConfig
from fms_fsdp_tpu.models.mamba1 import mamba1_mixer, mamba1_mixer_step
from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops.attention import (
    as_ring,
    band_mask,
    diff_combine,
    diff_rows,
    masked_attention,
    window_chunk_attention,
)
from fms_fsdp_tpu.ops.norms import layer_norm
from fms_fsdp_tpu.ops.paged_attention import paged_attention_kernel
from fms_fsdp_tpu.ops.selective_scan import selective_scan

__all__ = [
    "Phi4FlashConfig",
    "init_phi4flash_params",
    "phi4flash_decode_step",
    "phi4flash_forward",
    "phi4flash_prefill",
]

Params = Dict[str, Any]

# positions one trip of the prefill's loop takes through the first half
# of the stack: models/mamba.py::PREFILL_CHUNK, whose mixer and widths
# these are (512 rows do the operations a weight byte that the chip's
# ridge asks for). A constant of the program: no option selects it.
PREFILL_CHUNK = 512


def init_phi4flash_params(key, cfg: Phi4FlashConfig, dtype=jnp.float32) -> Params:
    d, hd, di = cfg.emb_dim, cfg.head_dim, cfg.d_inner
    N, R = cfg.d_state, cfg.dt_rank_
    std = 0.02
    out_std = std / (2 * cfg.nlayers) ** 0.5
    keys = iter(jax.random.split(key, 16 * cfg.nlayers + 4))

    def tn(shape, s=std):
        return (
            jax.random.truncated_normal(next(keys), -3, 3, shape, jnp.float32)
            * s
        ).astype(dtype)

    def norm():
        return {"weight": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}

    def mamba():
        # dt ~ LogUniform[1e-3, 1e-1] through softplus, A[c, n] = n + 1:
        # mamba_ssm's Mamba-1 init, as models/mamba.py draws it
        u = jax.random.uniform(next(keys), (di,), jnp.float32)
        dt = jnp.clip(
            jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3)), 1e-4
        )
        A = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (di, N))
        return {
            "in_proj": tn((2, d, di)),
            "conv_w": tn((di, cfg.d_conv), std * 10),
            "conv_b": jnp.zeros((di,), dtype),
            "x_proj": tn((di, R + 2 * N)),
            "dt_proj": tn((R, di), R**-0.5),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(A).astype(dtype),
            "D": jnp.ones((di,), dtype),
            "out_proj": tn((di, d), out_std),
        }

    def attention(cross: bool):
        p = {
            "wq": tn((d, cfg.nheads * hd)),
            "bq": jnp.zeros((cfg.nheads * hd,), dtype),
            "wo": tn((cfg.nheads * hd, d), out_std),
            "bo": jnp.zeros((d,), dtype),
            "subln": jnp.ones((2 * hd,), dtype),
            **{f"lambda_{n}": tn((hd,), 0.1) for n in ("q1", "k1", "q2", "k2")},
        }
        if not cross:
            p.update(
                wk=tn((d, cfg.kvheads * hd)),
                bk=jnp.zeros((cfg.kvheads * hd,), dtype),
                wv=tn((d, cfg.kvheads * hd)),
                bv=jnp.zeros((cfg.kvheads * hd,), dtype),
            )
        return p

    def layer(i: int):
        kind = cfg.kind(i)
        if kind == "mamba":
            mixer = mamba()
        elif kind == "gmu":
            mixer = {"in_proj": tn((d, di)), "out_proj": tn((di, d), out_std)}
        else:
            mixer = attention(kind == "cross")
        f = cfg.hidden_dim
        return {
            "norm": norm(),
            "mixer": mixer,
            "norm2": norm(),
            "mlp": {"w1": tn((d, f)), "w3": tn((d, f)), "w2": tn((f, d), out_std)},
        }

    return {
        "embedding": tn((cfg.src_vocab_size, d)),
        "layers": [layer(i) for i in range(cfg.nlayers)],
        "norm_f": norm(),
    }


# ---------------------------------------------------------------------------
# what every form shares
# ---------------------------------------------------------------------------


def pair_rows(cfg: Phi4FlashConfig):
    """(rows a position takes in a cache, lanes of a row): a pair of key
    (or value) heads side by side is a row."""
    return cfg.kvheads // 2, 2 * cfg.head_dim


@scoped("norm")
def _norm(x, p, cfg):
    return layer_norm(x, p["weight"], p["bias"], cfg.norm_eps)


@scoped("mlp")
def _mlp(h, p):
    return (jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])) @ p["w2"]


@scoped("lm_head")
def _head(x, params, cfg):
    """The final norm and the tied head over rows x (..., D)."""
    x = layer_norm(
        x, params["norm_f"]["weight"], params["norm_f"]["bias"], cfg.norm_eps
    )
    return jnp.einsum("...d,vd->...v", x, params["embedding"])


@scoped("gmu")
def _gmu(h, p, memory):
    """A gated memory unit: ``W_out (silu(W_in h) * M)``."""
    return (jax.nn.silu(h @ p["in_proj"]) * memory.astype(h.dtype)) @ p[
        "out_proj"
    ]


@scoped("qkv")
def _q_rows(h, p, cfg):
    """h (..., D) -> the queries as rows of two heads (..., N, 2 H)."""
    q = h @ p["wq"] + p["bq"]
    return diff_rows(q.reshape(q.shape[:-1] + (cfg.nheads, cfg.head_dim)))


@scoped("qkv")
def _kv_rows(h, p, cfg):
    """h (..., D) -> keys and values as a cache holds them, (..., Nkv / 2,
    2 H) each: a pair of heads a row."""
    shape = h.shape[:-1] + pair_rows(cfg)
    return (
        (h @ p["wk"] + p["bk"]).reshape(shape),
        (h @ p["wv"] + p["bv"]).reshape(shape),
    )


def _combine(o, p, cfg, i: int, dtype):
    """``diff_combine`` of layer ``i`` -> (..., N * H) in ``dtype``."""
    return diff_combine(o, p, cfg.lambda_init(i), cfg.subln_eps).astype(dtype)


@scoped("attn_out")
def _attn_out(o, p):
    return o @ p["wo"] + p["bo"]


def _attend(q, k, v, mask, cfg):
    """Rows of queries q (B, Sq, N, 2 H) over rows k, v (B, Sk, Nkv / 2,
    2 H) where ``mask`` (B or 1, Sq, Sk) -> (B, Sq, N, 2 H) float32."""
    return masked_attention(q, k, v, mask, scale=cfg.head_dim**-0.5)[0]


def _cross_decoder(params, x, memory, attend, cfg, write_kv=None):
    """The full layer and the layers behind it on one position a row: x
    (B, D) the residual before the full layer, ``memory`` (B, d_inner)
    the hand-out layer's scan output at that position. ``attend(q) ->
    (B, N, 2 H)``: the rows of queries q (B, N, 2 H) over the full
    layer's keys and values of the row's positions, the row's own among
    them, through whatever holds them (a decode step's pages, a prefill's
    buffer). ``write_kv(k, v)`` puts the position's own there first (a
    decode step; a prefill's loop wrote every position's). What a decode
    step and the end of a prefill both run. -> x (B, D)."""
    for i in range(cfg.full_layer, cfg.nlayers):
        layer = params["layers"][i]
        kind, p = cfg.kind(i), layer["mixer"]
        h = _norm(x, layer["norm"], cfg)
        if kind == "gmu":
            out = _gmu(h, p, memory)
        else:
            if kind == "full" and write_kv is not None:
                write_kv(*_kv_rows(h, p, cfg))
            q = _q_rows(h, p, cfg)
            with jax.named_scope("attn_full" if kind == "full" else "attn_cross"):
                o = _combine(attend(q), p, cfg, i, x.dtype)
            out = _attn_out(o, p)
        x = x + out
        x = x + _mlp(_norm(x, layer["norm2"], cfg), layer["mlp"])
    return x


# ---------------------------------------------------------------------------
# forward (whole sequences, no cache): the parity form
# ---------------------------------------------------------------------------


def phi4flash_forward(
    params: Params, tokens, cfg: Phi4FlashConfig, *,
    compute_dtype=jnp.bfloat16, **_unused,
):
    """tokens (B, S) -> logits (B, S, V): every layer over every position,
    the scan from a zero state, masked attention over the whole sequence
    (banded on a window layer; over the full layer's keys and values on a
    cross layer)."""
    params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B, S = tokens.shape
    pos = jnp.arange(S, dtype=jnp.int32)
    x = params["embedding"][tokens]
    memory = kv = None
    for i, layer in enumerate(params["layers"]):
        kind, p = cfg.kind(i), layer["mixer"]
        h = _norm(x, layer["norm"], cfg)
        if kind == "mamba":
            out, _, y = mamba1_mixer(h, p, cfg, norms=False, hand_out=True)
            if i == cfg.hand_out_layer:
                memory = y
        elif kind == "gmu":
            out = _gmu(h, p, memory)
        else:
            if kind != "cross":
                mine = _kv_rows(h, p, cfg)
                if kind == "full":
                    kv = mine
            window = cfg.sliding_window if kind == "window" else 0
            o = _attend(
                _q_rows(h, p, cfg), *(kv if kind == "cross" else mine),
                band_mask(pos, pos, window)[None], cfg,
            )
            out = _attn_out(_combine(o, p, cfg, i, x.dtype), p)
        x = x + out
        x = x + _mlp(_norm(x, layer["norm2"], cfg), layer["mlp"])
    return _head(x, params, cfg)


# ---------------------------------------------------------------------------
# prefill: the first half a chunk at a time, the second for one position
# ---------------------------------------------------------------------------


def prefill_chunk(p_pad: int) -> int:
    """The chunk of a prompt padded to ``p_pad``: the largest divisor of
    ``p_pad`` up to ``PREFILL_CHUNK``, so that chunks tile the program."""
    return seq.chunk_of(p_pad, PREFILL_CHUNK)


def prefill_positions(p: int, p_pad: int) -> int:
    """Positions the first half of the stack computes for a prompt of
    ``p`` tokens in a program of ``p_pad``: whole chunks up to the
    prompt's end. The second half computes one."""
    return seq.positions_computed(p, prefill_chunk(p_pad))


def _use_flash(cfg: Phi4FlashConfig, attn_impl: str, c: int) -> bool:
    fits = c % 256 == 0 and (2 * cfg.head_dim) % 128 == 0
    return fits and seq.kernel_wanted(attn_impl)


def prefill_attn_form(cfg: Phi4FlashConfig, attn_impl: str, p_pad: int) -> str:
    """What the window layers run in the prefill program of ``p_pad``
    positions (``attn_form`` on ``serve/prefill.dispatch``): the windowed
    flash kernel over rows of two heads, or einsums (off a TPU, and odd
    chunks). The full layer's attention is one position's, an einsum."""
    flash = _use_flash(cfg, attn_impl, prefill_chunk(p_pad))
    return "flash_window_rows" if flash else "einsum"


def phi4flash_prefill(
    params: Params,
    tokens,
    lengths,
    cfg: Phi4FlashConfig,
    *,
    compute_dtype=jnp.bfloat16,
    kv_len: int = 0,
    attn_impl: str = "auto",
):
    """Prompt prefill. tokens (B, S_pad) int32, lengths (B,) int32 the
    prompts' lengths (<= S_pad). ``prefill_chunk(S_pad)`` positions at a
    time go through layers 0 to ``cfg.hand_out_layer`` and the full
    layer's key and value projection, in one loop whose trip count is
    read from ``lengths`` on the device. From chunk to chunk go: each
    Mamba layer's slab (the scan's state and the conv's last inputs, each
    row's frozen at its length), each window layer's last
    ``sliding_window`` keys and values, the full layer's keys and values
    written so far, and each row's residual and scan output ``M`` at its
    last real position. Behind the loop the full layer's attention and
    MLP and the layers behind it run for that one position a row
    (``_cross_decoder``).

    Returns (logits (B, V) of each row's last real position; the full
    layer's ``{"k", "v"}`` (1, B, kv_len * kvheads / 2, 2 head_dim), zero
    past each row's length, as the pages hold them; the state a slot
    keeps: ``{"ring_k", "ring_v"}`` (L_window, B, sliding_window * kvheads
    / 2, 2 head_dim), position ``t`` in rows ``(t mod sliding_window) *
    kvheads / 2`` on,
    ``{"conv"}`` (L_mamba, B, d_conv - 1, d_inner) and ``{"ssd"}``
    (L_mamba, B, d_state, d_inner) float32)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B, S = tokens.shape
    c = prefill_chunk(S)
    kv_len = kv_len or S
    assert kv_len >= S, (kv_len, S)
    W, D = cfg.sliding_window, cfg.emb_dim
    flash = _use_flash(cfg, attn_impl, c)
    scale = cfg.head_dim**-0.5
    n_mamba, n_win = len(cfg.layers_of("mamba")), len(cfg.layers_of("window"))
    rows = pair_rows(cfg)
    kv_shape, tail_shape = (B, kv_len) + rows, (B, W) + rows
    full = params["layers"][cfg.full_layer]

    def body(chunk, carry):
        slabs, tails, kv, last = carry
        slabs, tails = list(slabs), list(tails)
        start, ahead = chunk.start, chunk.ahead
        with jax.named_scope("embed"):
            toks = lax.dynamic_slice_in_dim(tokens, start, c, axis=1)
            x = params["embedding"][toks]
        mi = wi = 0
        with jax.named_scope("layers"):
            for i in range(cfg.full_layer):
                layer = params["layers"][i]
                p = layer["mixer"]
                h = _norm(x, layer["norm"], cfg)
                if cfg.kind(i) == "mamba":
                    out, slabs[mi], memory = mamba1_mixer(
                        h, p, cfg, lengths=jnp.clip(ahead, 0, c),
                        scan=selective_scan, carry=slabs[mi], norms=False,
                        hand_out=True,
                    )
                    mi += 1
                else:
                    k, v = _kv_rows(h, p, cfg)
                    tk, tv = tails[wi]
                    o = window_chunk_attention(
                        _q_rows(h, p, cfg), k, v, tk, tv, start, W, flash,
                        scale=scale,
                    )
                    with jax.named_scope("attn_window"):
                        o = _combine(o, p, cfg, i, x.dtype)
                    tails[wi] = (
                        seq.next_tail(tk, k, ahead, W),
                        seq.next_tail(tv, v, ahead, W),
                    )
                    wi += 1
                    out = _attn_out(o, p)
                x = x + out
                x = x + _mlp(_norm(x, layer["norm2"], cfg), layer["mlp"])
            # all the second half keeps of these positions
            h = _norm(x, full["norm"], cfg)
            with jax.named_scope("kv_write"):
                kv = seq.write_live(
                    kv, _kv_rows(h, full["mixer"], cfg), chunk.live, start
                )
        # the loop keeps each row's last real position of what it is
        # handed: the residual and, beside it, the hand-out layer's M
        x = jnp.concatenate([x, memory.astype(x.dtype)], axis=-1)
        return x, (tuple(slabs), tuple(tails), kv, last)

    def zeros(shape, dtype=compute_dtype):
        return jnp.zeros(shape, dtype)

    def slab():
        return {
            "conv": zeros((B, cfg.d_conv - 1, cfg.d_inner)),
            "ssd": zeros((B, cfg.d_state, cfg.d_inner), jnp.float32),
        }

    slabs, tails, (kb, vb), last = seq.chunk_loop(
        lengths, c, body,
        lambda: (
            tuple(slab() for _ in range(n_mamba)),
            tuple((zeros(tail_shape), zeros(tail_shape)) for _ in range(n_win)),
            (zeros(kv_shape), zeros(kv_shape)),
            zeros((B, D + cfg.d_inner)),
        ),
        last=3,
    )
    x, memory = last[:, :D], last[:, D:]
    seen = (jnp.arange(kv_len, dtype=jnp.int32)[None] < lengths[:, None])[:, None]

    def attend(q):  # one position a row over the buffer, as a decode step
        return _attend(q[:, None], kb, vb, seen, cfg)[:, 0]

    with jax.named_scope("layers"):
        x = _cross_decoder(params, x, memory, attend, cfg)
    logits = _head(x, params, cfg)
    kv = {
        "k": kb.reshape(1, B, -1, rows[1]), "v": vb.reshape(1, B, -1, rows[1])
    }
    def ring(i):  # (L_window, B, W * kvheads / 2, 2 H): rows, as pages are
        return jnp.stack(
            [as_ring(t[i], lengths, W).reshape(B, -1, rows[1]) for t in tails]
        )

    state = {
        "ring_k": ring(0),
        "ring_v": ring(1),
        "conv": jnp.stack([s["conv"] for s in slabs]),
        "ssd": jnp.stack([s["ssd"] for s in slabs]),
    }
    return logits, kv, state


# ---------------------------------------------------------------------------
# decode: one ragged step over ring, slab and the full layer's pages
# ---------------------------------------------------------------------------


@scoped("attn_window")
def _ring_attend(q, ring_k, ring_v, wi, seq_lens, cfg, kernel, page_size):
    """Rows of queries q (B, N, 2 H), one position a row, over window layer
    ``wi`` of the rings (L_window, B, W * kvheads / 2, 2 H) with the row's
    position ``seq_lens[b]`` already written: entry ``r`` holds a position
    of this stream iff ``r <= seq_lens[b]`` (every entry once the ring has
    wrapped). ``kernel``: a slot's ring is ``W / page_size`` pages of the
    ragged paged kernel, under a table that is the slots' own order, read
    where they lie at a row of 128 lanes a pair of heads (gathered by head
    the chip pads a position's ``kvheads / 2`` rows to 16 and copies the
    rings whole: 4 GB of temporaries at the published widths, deviceless
    v5e compile); else a masked einsum. -> (B, N, 2 H) float32."""
    L, B = ring_k.shape[:2]
    W = cfg.sliding_window
    pairs, width = pair_rows(cfg)
    if kernel and W % page_size == 0:
        n = W // page_size
        table = (wi * B + jnp.arange(B, dtype=jnp.int32))[:, None] * n + (
            jnp.arange(n, dtype=jnp.int32)
        )
        return paged_attention_kernel(
            q,
            ring_k.reshape(L * B * n, page_size * pairs, width),
            ring_v.reshape(L * B * n, page_size * pairs, width),
            table, jnp.minimum(seq_lens, W - 1), block_kv=W,
            scale=cfg.head_dim**-0.5, nkv=pairs, out_dtype=jnp.float32,
        ).reshape(q.shape)
    in_ring = jnp.arange(W, dtype=jnp.int32)[None] <= seq_lens[:, None]
    return _attend(
        q[:, None], ring_k[wi].reshape(B, W, pairs, width),
        ring_v[wi].reshape(B, W, pairs, width), in_ring[:, None], cfg,
    )[:, 0]


def _pages_attend(q, pools, page_table, seq_lens, cfg, kernel, block_kv):
    """Rows of queries q (B, N, 2 H), one position a row, over the full
    layer's pools ``{"k", "v"}`` (1, P, page_size * kvheads / 2, 2 H), row
    ``b`` seeing cache positions <= seq_lens[b]. ``kernel``: the ragged
    paged kernel, told that a page's rows are ``kvheads / 2`` heads of ``2
    H`` and handing its float32 accumulator out; else gather and attend in
    plain jax. -> (B, N, 2 H) float32."""
    k_pages, v_pages = pools["k"][0], pools["v"][0]
    pairs, width = pair_rows(cfg)
    if kernel:
        return paged_attention_kernel(
            q, k_pages, v_pages, page_table, seq_lens, block_kv=block_kv,
            scale=cfg.head_dim**-0.5, nkv=pairs, out_dtype=jnp.float32,
        ).reshape(q.shape)
    with jax.named_scope("kv_read"):
        k, v = (
            pages[page_table].reshape(q.shape[0], -1, pairs, width)
            for pages in (k_pages, v_pages)
        )
    seen = jnp.arange(k.shape[1], dtype=jnp.int32)[None] <= seq_lens[:, None]
    return _attend(q[:, None], k, v, seen[:, None], cfg)[:, 0]


def phi4flash_decode_step(
    params: Params,
    state,
    pools,
    page_table,
    seq_lens,
    tokens,
    cfg: Phi4FlashConfig,
    *,
    page_size: int,
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "reference",
    block_kv=None,
):
    """One ragged decode step. tokens (B,) int32 at positions
    ``seq_lens`` (0: a slot that holds no stream); ``state`` the slots'
    ``{"ring_k", "ring_v", "conv", "ssd"}`` as ``phi4flash_prefill``
    returns them with B the slots; pools ``{"k", "v"}`` (1, P, page_size *
    kvheads / 2, 2 head_dim), the adapter's PagedKVCache.pools. A Mamba
    layer steps its slab; a dead slot's stays as it was: the scan's
    stacked state goes to the mixer whole with the layer's index and the
    live rows, and is stepped where it lies (on a TPU one kernel pass
    that writes a dead row back as read, ops/selective_scan.py; else
    slice, step, select, write back), and the conv window's 3.9 MB are
    selected here. A window layer
    writes the position's key and value at ``seq_lens mod
    sliding_window`` of its ring and attends the ring (``"kernel"``: as
    pages of the ragged paged kernel); the full layer
    writes them to its page; it and every cross layer attend the stream's
    pages (``attn_impl="kernel"``: the ragged paged kernel;
    ``"reference"``: gathered), eight reads of the same pages; the gated
    memory units gate the step's own ``M``. Returns (logits (B, V), state,
    pools)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B = tokens.shape[0]
    W = cfg.sliding_window
    seq_lens = seq_lens.astype(jnp.int32)
    live = seq_lens > 0  # a prompt is never empty
    slots = jnp.arange(B)
    pairs, _ = pair_rows(cfg)
    with jax.named_scope("embed"):
        x = params["embedding"][tokens]
    with jax.named_scope("kv_write"):  # each row's write target
        page_ids = page_table[slots, seq_lens // page_size][:, None]
        page_rows_at = (seq_lens % page_size)[:, None] * pairs + jnp.arange(pairs)
    with jax.named_scope("win_write"):
        ring_at = (seq_lens % W)[:, None] * pairs + jnp.arange(pairs)
    ring_k, ring_v = state["ring_k"], state["ring_v"]
    conv, ssd = state["conv"], state["ssd"]
    pools = dict(pools)
    memory = None
    mi = wi = 0
    with jax.named_scope("layers"):
        for i in range(cfg.full_layer):
            layer = params["layers"][i]
            p = layer["mixer"]
            h = _norm(x, layer["norm"], cfg)
            if cfg.kind(i) == "mamba":
                out, st, y = mamba1_mixer_step(
                    h, {"conv": conv[mi], "ssd": ssd}, p, cfg,
                    norms=False, hand_out=True, layer=mi, live=live,
                )
                ssd = st["ssd"]
                with jax.named_scope("ssm_scan"):
                    conv = conv.at[mi].set(
                        jnp.where(live[:, None, None], st["conv"], conv[mi])
                    )
                if i == cfg.hand_out_layer:
                    memory = y
                mi += 1
            else:
                k, v = _kv_rows(h, p, cfg)
                with jax.named_scope("win_write"):
                    ring_k = ring_k.at[wi, slots[:, None], ring_at].set(k)
                    ring_v = ring_v.at[wi, slots[:, None], ring_at].set(v)
                o = _ring_attend(
                    _q_rows(h, p, cfg), ring_k, ring_v, wi, seq_lens, cfg,
                    attn_impl == "kernel", page_size,
                )
                with jax.named_scope("attn_window"):
                    o = _combine(o, p, cfg, i, x.dtype)
                wi += 1
                out = _attn_out(o, p)
            x = x + out
            x = x + _mlp(_norm(x, layer["norm2"], cfg), layer["mlp"])

        def write_kv(k, v):
            with jax.named_scope("kv_write"):
                pools["k"] = pools["k"].at[0, page_ids, page_rows_at].set(k)
                pools["v"] = pools["v"].at[0, page_ids, page_rows_at].set(v)

        def attend(q):
            return _pages_attend(
                q, pools, page_table, seq_lens, cfg, attn_impl == "kernel",
                block_kv,
            )

        x = _cross_decoder(params, x, memory, attend, cfg, write_kv)
    logits = _head(x, params, cfg)
    state = {"ring_k": ring_k, "ring_v": ring_v, "conv": conv, "ssd": ssd}
    return logits, state, pools
