"""Jamba (Mamba-1 selective-scan layers with a few attention layers, a
dense SwiGLU MLP after every mixer) in plain float32 ``jax.numpy``: the
forward pass that serving is held to.

Follows the published description (ai21labs/AI21-Jamba2-3B ``config.json``,
``model_type: jamba``; Lieber et al. 2024, "Jamba"; Gu & Dao 2023 for the
mixer). ``d`` = hidden_size, ``d_inner`` = mamba_expand * d, ``N`` =
mamba_d_state, ``R`` = mamba_dt_rank, ``K`` = mamba_d_conv; no bias but
where said.

- Every layer: ``x = x + mixer(rms(x, w_in))``, then ``x = x + mlp(rms(x,
  w_ff))``, ``mlp(h) = (silu(h W_gate) * (h W_up)) W_down``.
- Layer ``i`` is attention iff ``i % attn_layer_period ==
  attn_layer_offset`` (``layer_kind``), Mamba otherwise; ``num_experts: 1``
  makes every feed-forward dense.
- Mamba mixer on ``h`` (S, d): ``[u | z] = h W_inproj`` (the tree holds
  ``W_inproj`` as its two halves stacked, (2, d, d_inner): u's first, the
  gate's second); ``u =
  silu(conv1d_causal_depthwise(u, K) + b_conv)``; ``[dt_r | B | C] = u
  W_x`` (R, N, N); ``dt_r = rms(dt_r, w_dt)``, ``B = rms(B, w_B)``, ``C =
  rms(C, w_C)``; ``dt = softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``
  (d_inner, N); per channel c and state n
  ``s_t[c,n] = exp(dt_t[c] A[c,n]) s_{t-1}[c,n] + dt_t[c] B_t[n] u_t[c]``,
  ``y_t[c] = sum_n C_t[n] s_t[c,n] + D[c] u_t[c]``;
  ``out = (y * silu(z)) W_out``.
- Attention mixer: ``q = h W_q`` (heads x 128), ``k = h W_k``, ``v = h
  W_v`` (1 head), no positional embedding of any kind, causal
  ``softmax(q k^T / sqrt(128)) v``, ``W_o``.
- Final ``rms(x, w_f)``, logits ``x E^T`` with ``E`` the embedding (tied).

The scan is a ``lax.scan`` over positions carrying the (d_inner, N) state:
the history never exists, so 8.7k positions fit. Attention in blocks of
query rows. No cache, no batching, no kernels.

**Seeded weights** (``weights.py`` draws ``normal``, ``ones``, ``zeros``).
The embedding std 0.02; a matrix std 1 / sqrt(rows) (0.0198 at 2560
rows: a block's pre-activations have unit scale at any width, the test
sizes too), the three that write to the residual stream a further 1 /
sqrt(2 L); conv taps std 0.5, its bias zeros; block norms, the norm on ``dt`` and ``D``
ones. What sets the scan's memory and its gain: ``W_dt`` std 0.5 /
sqrt(R) (``dt_r`` has unit RMS, so the input moves the pre-activation by
about 0.5), ``b_dt`` normal with std 2, ``A_log`` normal with std 2, and
the norm weights ``w_B``, ``w_C`` normal with std 0.25. A (channel, state)
pair's decay over one token is ``exp(-dt |A|)`` with ``dt = softplus(b_dt
+ ...)`` (0.018, 0.13, 0.69, 2.1, 4.0 for ``b_dt`` = -4, -2, 0, 2, 4) and
``|A| = exp(A_log)`` (0.018 to 55 over two std): the median pair halves
its state a token, one in six loses less than 5% a token and one in
forty less than 0.4%, so state is carried over one to several hundred
tokens: neither 0 nor 1. Read on the CPU at published widths (128
positions, PR 27): zeroing the state every 32 positions moves the logits
by as much as their own spread (mean |difference| 0.86-1.04 of a spread
of 1.01), so a scan that loses its state anywhere fails ``correct``.
``w_B`` and ``w_C`` at 0.25 and not 1 keep the mixer's gain near one:
with unit weights ``y = sum_n C_n s_n`` is some twenty times its input,
28 such layers amplify a rounding error in the eighth bit to a third of
the logits' spread, and serving in bfloat16 could not be told from
serving in float8 (first chip run of PR 27: mean gap 0.99 sound). As
drawn, bfloat16 moves the logits by 4-5% of their spread (mean gap 0.005,
share over 0.05 0.04) and float8 weights by 55% (0.82, 0.86), same run.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 512


def layer_kind(i, c):
    period, offset = c["attn_layer_period"], c["attn_layer_offset"]
    return "attention" if i % period == offset else "mamba"


def param_spec(c):
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    L = c["num_hidden_layers"]
    di = c["mamba_expand"] * d
    N, R, K = c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nq
    std = 0.02

    def leaf(shape, kind="normal", scale=std):
        return dict(shape=tuple(shape), kind=kind, scale=scale)

    def into(rows, cols):  # a matrix that reads the block's input
        return leaf((rows, cols), scale=rows**-0.5)

    def out(rows, cols):  # a matrix that writes to the residual stream
        return leaf((rows, cols), scale=rows**-0.5 / (2 * L) ** 0.5)

    spec = {"embedding": leaf((v, d))}
    for i in range(L):
        at = f"layers/{i}/"
        spec[at + "norm"] = leaf((d,), "ones")
        if layer_kind(i, c) == "attention":
            spec[at + "mixer/wq"] = into(d, nq * hd)
            spec[at + "mixer/wk"] = into(d, nkv * hd)
            spec[at + "mixer/wv"] = into(d, nkv * hd)
            spec[at + "mixer/wo"] = out(nq * hd, d)
        else:
            spec[at + "mixer/in_proj"] = leaf((2, d, di), scale=d**-0.5)
            spec[at + "mixer/conv_w"] = leaf((di, K), scale=0.5)
            spec[at + "mixer/conv_b"] = leaf((di,), "zeros")
            spec[at + "mixer/x_proj"] = into(di, R + 2 * N)
            spec[at + "mixer/dt_norm"] = leaf((R,), "ones")
            spec[at + "mixer/B_norm"] = leaf((N,), scale=0.25)
            spec[at + "mixer/C_norm"] = leaf((N,), scale=0.25)
            spec[at + "mixer/dt_proj"] = leaf((R, di), scale=0.5 / R**0.5)
            spec[at + "mixer/dt_bias"] = leaf((di,), scale=2.0)
            spec[at + "mixer/A_log"] = leaf((di, N), scale=2.0)
            spec[at + "mixer/D"] = leaf((di,), "ones")
            spec[at + "mixer/out_proj"] = out(di, d)
        spec[at + "norm2"] = leaf((d,), "ones")
        spec[at + "mlp/w1"] = into(d, f)
        spec[at + "mlp/w3"] = into(d, f)
        spec[at + "mlp/w2"] = out(f, d)
    spec["norm_f"] = leaf((d,), "ones")
    return spec


def layer_paths(spec, i):
    at = f"layers/{i}/"
    return [p for p in spec if p.startswith(at)]


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def causal_conv(u, w, b):
    """Depthwise causal conv: u (B, S, C), w (C, K), b (C,). Tap K-1 is
    the current position's."""
    S, K = u.shape[1], w.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(padded[:, k : k + S] * w[:, k] for k in range(K))


def selective_scan(u, dt, A, Bm, Cm, D):
    """u, dt (B, S, C); A (C, N); Bm, Cm (B, S, N); D (C,) -> y (B, S, C),
    from a zero state. The carried array holds ``s[c, n]`` as (B, N, C),
    channels last: 16 states on the minor axis would waste seven eighths
    of every vector register."""
    At = A.T

    def step(s, inp):
        u_t, dt_t, B_t, C_t = inp  # (B, C), (B, C), (B, N), (B, N)
        s = (jnp.exp(dt_t[:, None, :] * At) * s
             + (dt_t * u_t)[:, None, :] * B_t[:, :, None])
        return s, jnp.einsum("bnc,bn->bc", s, C_t) + D * u_t

    s0 = jnp.zeros(u.shape[:1] + At.shape, jnp.float32)
    _, y = lax.scan(
        step, s0, tuple(jnp.moveaxis(x, 1, 0) for x in (u, dt, Bm, Cm)),
        unroll=8)
    return jnp.moveaxis(y, 0, 1)


def mamba_mixer(h, p, c):
    N, R = c["mamba_d_state"], c["mamba_dt_rank"]
    eps = c["rms_norm_eps"]
    u, z = h @ p["in_proj"][0], h @ p["in_proj"][1]
    u = jax.nn.silu(causal_conv(u, p["conv_w"], p["conv_b"]))
    dbc = u @ p["x_proj"]
    dt_r = rms_norm(dbc[..., :R], p["dt_norm"], eps)
    Bm = rms_norm(dbc[..., R : R + N], p["B_norm"], eps)
    Cm = rms_norm(dbc[..., R + N :], p["C_norm"], eps)
    dt = jax.nn.softplus(dt_r @ p["dt_proj"] + p["dt_bias"])
    y = selective_scan(u, dt, -jnp.exp(p["A_log"]), Bm, Cm, p["D"])
    return (y * jax.nn.silu(z)) @ p["out_proj"]


def causal_attention(q, k, v, block=QUERY_BLOCK):
    """softmax(q k^T / sqrt(hd)) v, causal, no positions, one block of
    query rows at a time. q (B, S, Nq, hd); k, v (B, S, Nkv, hd)."""
    Bsz, S, nq, hd = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    blk = math.gcd(S, block)
    qb = q.reshape(Bsz, S // blk, blk, nkv, g, hd)
    cols = jnp.arange(S)

    def one(args):
        qi, start = args
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k) / jnp.sqrt(float(hd))
        rows = start + jnp.arange(blk)
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(one, (jnp.moveaxis(qb, 1, 0), jnp.arange(S // blk) * blk))
    return jnp.moveaxis(out, 0, 1).reshape(Bsz, S, nq * hd)


def attention_mixer(h, p, c):
    B, S, d = h.shape
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nq
    q = (h @ p["wq"]).reshape(B, S, nq, hd)
    k = (h @ p["wk"]).reshape(B, S, nkv, hd)
    v = (h @ p["wv"]).reshape(B, S, nkv, hd)
    return causal_attention(q, k, v) @ p["wo"]


def block(x, layer, c, kind):
    """One layer of ``kind`` on x (B, S, d); ``layer`` holds its leaves
    nested as the program's tree does (``norm``, ``mixer``, ``norm2``,
    ``mlp``)."""
    eps = c["rms_norm_eps"]
    mixer = attention_mixer if kind == "attention" else mamba_mixer
    x = x + mixer(rms_norm(x, layer["norm"], eps), layer["mixer"], c)
    h = rms_norm(x, layer["norm2"], eps)
    m = layer["mlp"]
    return x + (jax.nn.silu(h @ m["w1"]) * (h @ m["w3"])) @ m["w2"]


def forward(tree, tokens, c):
    """Logits (B, S, vocab) of the whole model from a parameter tree
    shaped as the program's (``layers`` a list). For the tests; the
    benchmark walks the layers one at a time."""
    x = tree["embedding"][tokens]
    for i, layer in enumerate(tree["layers"]):
        x = block(x, layer, c, layer_kind(i, c))
    return rms_norm(x, tree["norm_f"], c["rms_norm_eps"]) @ tree["embedding"].T
