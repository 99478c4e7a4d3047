"""Sarvam-105B (``model_type: sarvam_mla``: multi-head latent attention
over many small sigmoid-routed experts beside a shared one) in plain
float32 ``jax.numpy``: the forward pass that serving is held to.

Follows the published ``config.json`` (sarvamai/sarvam-105b) and the
DeepSeek-V2/V3 papers and code its keys come from. ``d`` = hidden_size,
``N`` = num_attention_heads, ``r`` = kv_lora_rank, ``nope``/``rope``/``v``
= qk_nope_head_dim / qk_rope_head_dim / v_head_dim; no bias but the
router's.

- Every layer: ``x = x + attn(rms(x, w_attn))``, then ``x = x +
  ffn(rms(x, w_ffn))``. The first ``first_k_dense_replace`` layers' ``ffn``
  is a dense SwiGLU ``(silu(h W1) * (h W3)) W2`` of intermediate_size
  (``layer_kind``), the others' the mixture below.
- Attention, **expanded**: ``q = h W_q`` (N heads of nope + rope);
  ``[c ; kr] = h W_kva`` (r, then one rope-wide key for all heads); ``ĉ =
  rms(c, w_kv)``; ``[k_nope_h ; v_h] = ĉ W_kvb`` by head (nope + v);
  rotary on ``q_rope`` and ``kr``; ``k_h = [k_nope_h ; kr]``; ``score =
  q_h·k_h · m² / sqrt(nope + rope)``, ``m = 0.1 · mscale_all_dim ·
  ln(factor) + 1``; causal softmax; ``(Σ p v_h over heads) W_o``. Keys and
  values are made for every position: no latent cache, no absorbed
  product (the program's decode step never applies ``W_kvb`` to its
  cache; this is what it has to agree with).
- Rotary: ``deepseek_yarn`` frequencies from ``rope_scaling`` (theta,
  factor, original_max_position_embeddings, beta_fast, beta_slow);
  neighbouring pairs ``(x[2i], x[2i+1])`` are turned, in place. cos and
  sin carry ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
  mscale_all_dim)``, 1 as published.
- Mixture: ``s = sigmoid(h W_g)`` over all ``published.num_experts``
  (the router's width); the ``num_experts_per_tok`` largest of ``s + b``
  are chosen; ``w_i = routed_scaling_factor · s_i / Σ_chosen s_j``; ``y =
  Σ_chosen w_i E_i(h) + S(h)``, every ``E_i`` and the shared ``S`` (width
  ``num_shared_experts · moe_intermediate_size``) a SwiGLU. A loop over
  the experts held, one at a time: each adds its SwiGLU of every row,
  weighted by the row's weight for it, which is exactly zero where the
  row did not choose it (the same sum as a loop over each row's chosen
  experts, without a gather of weights).
- Final ``rms(x, w_f)``, logits ``x W_head`` (untied).

**The share** (guide section 4): the tree holds experts ``first_expert_held``
to ``first_expert_held + num_experts`` of ``published.num_experts`` and
``vocab_size`` rows of the vocabulary. The router keeps its published
width and its experts per token; a chosen expert that is not held adds
nothing, here as in the program, and that partial result goes on to the
next layer. ``share(c, first)`` gives the config of another share;
tests/test_sarvam.py adds four up to the uncut layer.

**Assumed** (the config has no key; the configuration file lists them):
sigmoid scoring with the chosen weights normalised; one routing group;
``use_qk_norm`` is the RMSNorm on the r-wide latent (a norm on the
expanded keys could not be absorbed, and ``head_dim: 576`` says the cache
is the latent); rotary pairs interleaved.

Departures, of memory only: attention in blocks of query rows.

**Seeded weights** (``weights.py`` draws ``normal``, ``ones``, ``zeros``).
Embedding std 0.02. A matrix that reads the block's input has std 1 /
sqrt(rows), so pre-activations have unit scale at any width, the test
sizes too; ``W_kvb`` reads the unit-RMS latent, std 1 / sqrt(r). The
matrices that write to the residual stream (``W_o``, every ``W2``) have a
further 1 / sqrt(2 L): the stack's gain stays near one (PR 27: with
unit-scale draws 28 layers amplified bfloat16's rounding until it read
like float8's). The router's ``W_g`` has std 1 / sqrt(d): scores are
sigmoids of unit-scale logits, spread over 0.1-0.9, and the bias is
normal with std 0.1: it moves about one choice in ten (read on the CPU
at the test size) and weighs nothing. An expert's output enters with
weight 2.5 / 8 on average.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256


def layer_kind(i, c):
    return "dense" if i < c.get("first_k_dense_replace", 0) else "moe"


def router_width(c):
    return (c.get("published") or {}).get("num_experts", c["num_experts"])


def share(c, first):
    """The config of the share that holds experts ``first`` to ``first +
    num_experts``."""
    return {**c, "first_expert_held": first}


def param_spec(c):
    d, v = c["hidden_size"], c["vocab_size"]
    L, Ld = c["num_hidden_layers"], c.get("first_k_dense_replace", 0)
    N, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    f, h = c["intermediate_size"], c["moe_intermediate_size"]
    held, hs = c["num_experts"], c.get("num_shared_experts", 0) * h
    out_gain = (2 * L) ** -0.5

    def stack(n):
        def leaf(shape, kind="normal", scale=1.0):
            return dict(shape=(n,) + tuple(shape), kind=kind, scale=scale,
                        stacked=True)

        def into(*shape):  # reads unit-scale rows
            return leaf(shape, scale=shape[-2] ** -0.5)

        def out(*shape):  # writes to the residual stream
            return leaf(shape, scale=shape[-2] ** -0.5 * out_gain)

        attn = {
            "attn_norm": leaf((d,), "ones"),
            "wq": into(d, N * (nope + rope)),
            "wkv_a": into(d, r + rope),
            "kv_norm": leaf((r,), "ones"),
            "wkv_b": into(r, N * (nope + vd)),
            "wo": out(N * vd, d),
            "ffn_norm": leaf((d,), "ones"),
        }
        return attn, leaf, into, out

    spec = {"embedding": dict(shape=(v, d), kind="normal", scale=0.02)}
    attn, leaf, into, out = stack(Ld)
    dense = dict(attn, w1=into(d, f), w3=into(d, f), w2=out(f, d))
    spec.update({"dense_layers/" + k: s for k, s in dense.items()})
    attn, leaf, into, out = stack(L - Ld)
    moe = dict(
        attn,
        gate=into(d, router_width(c)),
        gate_bias=leaf((router_width(c),), scale=0.1),
        w1=into(held, d, h), w3=into(held, d, h), w2=out(held, h, d),
    )
    if hs:
        moe.update(shared_w1=into(d, hs), shared_w3=into(d, hs),
                   shared_w2=out(hs, d))
    spec.update({"layers/" + k: s for k, s in moe.items()})
    spec["norm"] = dict(shape=(d,), kind="ones")
    spec["lm_head"] = dict(shape=(d, v), kind="normal", scale=d**-0.5)
    return spec


def layer_paths(spec, kind):
    """The stacked leaves of the layers of ``kind``; a layer's index in
    its stack is its index among the layers of its kind."""
    at = "dense_layers/" if kind == "dense" else "layers/"
    return [p for p in spec if p.startswith(at)]


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(c):
    rs, dim = c["rope_scaling"], c["qk_rope_head_dim"]
    base = float(c["rope_theta"])
    factor = rs["factor"]
    orig = rs["original_max_position_embeddings"]
    pairs = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / base**pairs
    inter = 1.0 / (factor * base**pairs)

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def rotary(x, c):
    """Neighbouring pairs of the last axis turned by the yarn angles of
    each position. x (B, S, ..., rope) with S on axis 1."""
    rs = c["rope_scaling"]
    S = x.shape[1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * yarn_inv_freq(c)[None, :]
    scale = (yarn_mscale(rs["factor"], rs["mscale"])
             / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    shape = (1, S) + (1,) * (x.ndim - 3) + (ang.shape[-1],)
    cos = (jnp.cos(ang) * scale).reshape(shape)
    sin = (jnp.sin(ang) * scale).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).reshape(x.shape)


def causal_attention(q, k, v, scale, block=QUERY_BLOCK):
    """softmax(q k^T scale) v, causal, one block of query rows at a time.
    q, k (B, S, N, dq); v (B, S, N, dv)."""
    B, S, N, dq = q.shape
    blk = math.gcd(S, block)
    qb = jnp.moveaxis(q.reshape(B, S // blk, blk, N, dq), 1, 0)
    cols = jnp.arange(S)

    def one(args):
        qi, start = args
        s = jnp.einsum("bqnd,bsnd->bnqs", qi, k) * scale
        rows = start + jnp.arange(blk)
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("bnqs,bsnd->bqnd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(one, (qb, jnp.arange(S // blk) * blk))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, N * v.shape[-1])


def attention(h, p, c):
    B, S, _ = h.shape
    N, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    q = (h @ p["wq"]).reshape(B, S, N, nope + rope)
    ckr = h @ p["wkv_a"]
    latent = rms_norm(ckr[..., :r], p["kv_norm"], c["rms_norm_eps"])
    kv = (latent @ p["wkv_b"]).reshape(B, S, N, nope + vd)
    kr = rotary(ckr[..., r:], c)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], c)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(kr[:, :, None, :], (B, S, N, rope))],
        axis=-1)
    m = yarn_mscale(c["rope_scaling"]["factor"],
                    c["rope_scaling"]["mscale_all_dim"])
    scale = m * m / math.sqrt(nope + rope)
    return causal_attention(q, k, kv[..., nope:], scale) @ p["wo"]


def swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def route(h, p, c):
    """-> (chosen ids (B, S, K) over the router's whole width, their
    weights (B, S, K) that sum to routed_scaling_factor)."""
    s = jax.nn.sigmoid(h @ p["gate"])
    _, idx = lax.top_k(s + p["gate_bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, c["routed_scaling_factor"] * w / jnp.sum(w, -1, keepdims=True)


def held_experts(h, p, c):
    """The part of ``Σ_chosen w_i E_i(h)`` that the held experts give."""
    idx, w = route(h, p, c)
    first = c.get("first_expert_held", 0)

    def one(y, e):
        w1, w3, w2, eid = e
        mine = jnp.sum(jnp.where(idx == eid, w, 0.0), axis=-1)  # (B, S)
        return y + mine[..., None] * swiglu(h, w1, w3, w2), None

    ids = first + jnp.arange(p["w1"].shape[0])
    y, _ = lax.scan(one, jnp.zeros_like(h), (p["w1"], p["w3"], p["w2"], ids))
    return y


def moe(h, p, c):
    y = held_experts(h, p, c)
    if "shared_w1" in p:
        y = y + swiglu(h, p["shared_w1"], p["shared_w3"], p["shared_w2"])
    return y


def block(x, layer, c, kind):
    """One layer of ``kind`` on x (B, S, d); ``layer`` holds that layer's
    leaves under the program's names."""
    eps = c["rms_norm_eps"]
    x = x + attention(rms_norm(x, layer["attn_norm"], eps), layer, c)
    h = rms_norm(x, layer["ffn_norm"], eps)
    if kind == "dense":
        return x + swiglu(h, layer["w1"], layer["w3"], layer["w2"])
    return x + moe(h, layer, c)


def forward(tree, tokens, c):
    """Logits (B, S, vocab) of the whole model from a parameter tree
    shaped as the program's. For the tests; the benchmark walks the
    layers one at a time."""
    x = tree["embedding"][tokens]
    counts = {"dense": 0, "moe": 0}
    for i in range(c["num_hidden_layers"]):
        kind = layer_kind(i, c)
        stack = tree["dense_layers" if kind == "dense" else "layers"]
        layer = jax.tree.map(lambda a: a[counts[kind]], stack)
        counts[kind] += 1
        x = block(x, layer, c, kind)
    return rms_norm(x, tree["norm"], c["rms_norm_eps"]) @ tree["lm_head"]
