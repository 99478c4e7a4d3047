"""Mixtral (sparse mixture of SwiGLU experts over Llama attention) in
plain float32 ``jax.numpy``: the forward pass that serving is held to.

Follows the published description (mistralai/Mixtral-8x7B-v0.1
``config.json`` and the paper, Jiang et al. 2024): pre-norm blocks, causal
GQA attention with rotary embedding over the whole head (half-split
pairs, ``rope_theta``), then a router that takes the softmax of the
``num_experts_per_tok`` largest of its logits (the same as renormalising
the top of the full softmax) and mixes those experts' SwiGLU outputs;
RMSNorm; untied embedding and head.

Every expert is computed for every token and weighted by its (mostly
zero) mixing weight: no dispatch, no buffers, no cache. Departures, of
memory only: experts one at a time, attention in blocks of query rows.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 1024


def param_spec(c):
    d, f = c["hidden_size"], c["intermediate_size"]
    L, E, v = c["num_hidden_layers"], c["num_local_experts"], c["vocab_size"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nq
    std, out_std = 0.02, 0.02 / (2 * L) ** 0.5

    def layer(shape, kind="normal", scale=std):
        return dict(shape=(L,) + shape, kind=kind, scale=scale, stacked=True)

    return {
        "embedding": dict(shape=(v, d), kind="normal", scale=std),
        "layers/attn_norm": layer((d,), "ones"),
        "layers/wq": layer((d, nq * hd)),
        "layers/wk": layer((d, nkv * hd)),
        "layers/wv": layer((d, nkv * hd)),
        "layers/wo": layer((nq * hd, d), scale=out_std),
        "layers/ffn_norm": layer((d,), "ones"),
        "layers/gate": layer((d, E)),
        "layers/w1": layer((E, d, f)),
        "layers/w3": layer((E, d, f)),
        "layers/w2": layer((E, f, d), scale=out_std),
        "norm": dict(shape=(d,), kind="ones"),
        "lm_head": dict(shape=(d, v), kind="normal", scale=std),
    }


def layer_paths(spec):
    return [p for p, s in spec.items() if s.get("stacked")]


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, theta):
    """Half-split pairs over the whole head. x (B,S,N,hd)."""
    S, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_gqa(q, k, v, block=QUERY_BLOCK):
    """softmax(q k^T / sqrt(hd)) v, causal, one block of query rows at a
    time. q (B,S,Nq,hd); k, v (B,S,Nkv,hd)."""
    Bsz, S, nq, hd = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    blk = math.gcd(S, block)  # the largest block that divides the rows
    qb = q.reshape(Bsz, S // blk, blk, nkv, g, hd)
    cols = jnp.arange(S)

    def one(args):
        qi, start = args  # (B, blk, nkv, g, hd)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k) / jnp.sqrt(float(hd))
        rows = start + jnp.arange(blk)
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(one, (jnp.moveaxis(qb, 1, 0), jnp.arange(S // blk) * blk))
    return jnp.moveaxis(out, 0, 1).reshape(Bsz, S, nq, hd)


def route(h, gate, c):
    """-> mixing weights (B,S,E): the softmax of the top-k router logits
    at the chosen experts, 0 elsewhere."""
    probs = jax.nn.softmax(h @ gate, axis=-1)
    top_p, top_i = lax.top_k(probs, c["num_experts_per_tok"])
    top_w = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    choice = jax.nn.one_hot(top_i, c["num_local_experts"], dtype=jnp.float32)
    return jnp.sum(choice * top_w[..., None], axis=2)


def experts(h, mix, w1, w3, w2):
    def one(y, e):
        w1e, w3e, w2e, me = e
        out = (jax.nn.silu(h @ w1e) * (h @ w3e)) @ w2e
        return y + me[..., None] * out, None

    y, _ = lax.scan(
        one, jnp.zeros_like(h), (w1, w3, w2, jnp.moveaxis(mix, -1, 0)))
    return y


def block(x, layer, c):
    B, S, d = x.shape
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nq
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = rms_norm(x, layer["attn_norm"], eps)
    q = rotary((h @ layer["wq"]).reshape(B, S, nq, hd), theta)
    k = rotary((h @ layer["wk"]).reshape(B, S, nkv, hd), theta)
    v = (h @ layer["wv"]).reshape(B, S, nkv, hd)
    x = x + causal_gqa(q, k, v).reshape(B, S, nq * hd) @ layer["wo"]
    h = rms_norm(x, layer["ffn_norm"], eps)
    mix = route(h, layer["gate"], c)
    return x + experts(h, mix, layer["w1"], layer["w3"], layer["w2"])
