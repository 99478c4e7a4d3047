"""Phi-4-mini-flash-reasoning (``model_type: phi4flash``; the "SambaY"
decoder-hybrid-decoder of arXiv:2507.06607 with the Differential Attention
of arXiv:2410.05258) in plain float32 ``jax.numpy``: the forward pass that
serving is held to. Nothing of the program is imported.

``d`` = hidden_size, ``n`` = num_hidden_layers (a multiple of 4), ``N`` /
``Nkv`` query / key-value heads of ``H = d / N``, vocabulary rows under a
head tied to the embedding, no positional embedding anywhere.

- Every layer ``l``: ``h = x + Mix_l(LN(x))``, ``y = h + MLP_l(LN(h))``;
  ``LN`` is LayerNorm with weight and bias (``layer_norm_eps``); a final
  LayerNorm before the head. ``MLP(h) = W2 (u * silu(g))`` with ``[g | u] =
  W1 h`` (the tree holds the two halves of ``W1`` as ``w1``, the gate's, and
  ``w3``), no bias.
- ``layer_kind(l, c)``, with ``m = mb_per_layer`` (2) and ``half = n / 2``:

  ========================  ==========  =====================================
  l                         kind        what it is
  ========================  ==========  =====================================
  l % m == 0, l <= half     ``mamba``   a Mamba-1 mixer; layer ``half`` also
                                        hands out its scan output ``M``
  l % m != 0, l < half      ``window``  differential attention over the last
                                        ``sliding_window`` positions
  l == half + 1             ``full``    differential attention over every
                                        earlier position; its keys and
                                        values are the second half's cache
  l % m == 0, l > half      ``gmu``     ``W_out (silu(W_in h) * M)``
  l % m != 0, l > half + 1  ``cross``   differential attention with a query
                                        and an output projection alone, over
                                        layer ``half + 1``'s keys and values
  ========================  ==========  =====================================

- Mamba-1 mixer on ``h`` (S, d), ``d_inner = 2 d``, state ``Ns = 16``,
  ``R = ceil(d / 16)``, conv ``K = 4``: ``[u | z] = h W_inproj`` (the tree
  holds the halves stacked, (2, d, d_inner), u's first); ``u =
  silu(conv1d_causal_depthwise(u, K) + b_conv)``; ``[dt_r | B | C] = u
  W_x`` (R, Ns, Ns) **with no norm on any of them** (Jamba's three
  RMSNorms are that family's own); ``dt = softplus(dt_r W_dt + b_dt)``;
  ``A = -exp(A_log)`` (d_inner, Ns); per channel c and state s
  ``s_t = exp(dt_t[c] A[c,s]) s_{t-1} + dt_t[c] B_t[s] u_t[c]``,
  ``y_t[c] = sum_s C_t[s] s_t[c,s] + D[c] u_t[c]``; ``out = (y * silu(z))
  W_out``. ``M = y``: the scan's output with its ``D`` skip, before the gate.
- Differential attention: ``q = h W_q + b_q`` (N heads of H), ``k``, ``v``
  likewise (Nkv heads). Heads in pairs of neighbours: ``q1_i = q[2i]``,
  ``q2_i = q[2i+1]`` (N/2 pairs), ``k1_j = k[2j]``, ``k2_j = k[2j+1]``,
  ``V_j = [v[2j] | v[2j+1]]`` (Nkv/2 heads, 2H wide), query pair ``i`` to
  key pair ``i // (N / Nkv)``. ``A1 = softmax(q1 k1^T / sqrt(H) + mask)``,
  ``A2 = softmax(q2 k2^T / sqrt(H) + mask)``, ``lambda = exp(lq1 . lk1) -
  exp(lq2 . lk2) + lambda_init(l)``, ``lambda_init(l) = 0.8 - 0.6 exp(-0.3
  l)`` by the layer's index, ``o_i = RMSNorm_2H((A1 - lambda A2) V_j;
  subln, eps 1e-5) * (1 - lambda_init(l))``, the N/2 x 2H values to ``W_o``
  (+ ``b_o``). Mask: keys at or before the query and, on a window layer,
  fewer than ``sliding_window`` behind it (the query's own position counts).

Departures from the published description: none known; what the published
``config.json`` has no key for is listed under ``assumed`` in
``benchmark/configs/phi-4-mini-flash.1chip.json``, item for item with the
lines above.

The scan is a ``lax.scan`` over positions carrying the (Ns, d_inner)
state; attention runs a block of query rows at a time. No cache, no
batching, no kernels.

**Seeded weights** (``weights.py`` draws ``normal``, ``ones``, ``zeros``),
drawn so that the check can see what is new. The embedding std 0.02; a
matrix std 1 / sqrt(rows), the ones that write to the residual stream a
further 1 / sqrt(2 n); LayerNorm weights ones, their biases normal with
std 0.1 (a program that drops a bias moves every layer's input); conv
taps std 0.5, its bias zeros; ``D`` ones.

- *The scan's memory* (as ``reference/jamba.py``, whose widths these are):
  ``b_dt`` and ``A_log`` normal with std 2, so a (channel, state) pair's
  decay over a token runs from nothing to all: the median pair halves its
  state a token, one in six loses less than 5% a token and one in forty
  less than 0.4%: state is carried over one to several hundred tokens.
  With no norm on ``dt_r``, ``B`` and ``C``, ``W_x`` is drawn at 0.4 /
  sqrt(d_inner) (``u`` has an RMS near 0.6 behind the silu, so ``B`` and
  ``C`` have the 0.25 that Jamba's norm weights give them and the mixer's
  gain stays near one) and ``W_dt`` at 2 / sqrt(R) (``dt_r`` then moves the
  pre-activation by about 0.5).
- *The scores' spread*: ``W_q`` and ``W_k`` are drawn sqrt(2) above the
  other matrices, so a score ``q . k / sqrt(H)`` has a spread of 2 and the
  largest of a window's 512 carry a tenth of the mass each: a key one
  place outside the window, or another layer's keys, moves an output by
  what one such key weighs. ``b_q``, ``b_k``, ``b_v`` normal with std 0.1,
  ``b_o`` 0.02.
- *lambda*: the four vectors normal with std 0.2, so ``lq . lk`` has a
  spread of 0.3 and ``lambda`` lies 0.2-0.5 off ``lambda_init(l)``, which
  itself runs from 0.2 (l = 0) to 0.8: a ``lambda`` that is its init, or
  the init of another layer, moves ``A1 - lambda A2`` by a tenth to a half
  of ``A2``. ``subln`` ones.
- *The memory* ``M`` has the scan's unit scale, so a gated memory unit's
  ``silu(W_in h) * M`` has about 0.4 of it and ``W_out`` is drawn as the
  other writers are.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 512
D_STATE, D_CONV, EXPAND = 16, 4, 2
SUBLN_EPS = 1e-5


def head_dim(c):
    return c["hidden_size"] // c["num_attention_heads"]


def mamba_sizes(c):
    """(d_inner, d_state, dt_rank, d_conv): the family's defaults."""
    d = c["hidden_size"]
    return EXPAND * d, D_STATE, math.ceil(d / 16), D_CONV


def layer_kind(i, c):
    half = c["num_hidden_layers"] // 2
    if i % c["mb_per_layer"] == 0:
        return "mamba" if i <= half else "gmu"
    if i < half:
        return "window"
    return "full" if i == half + 1 else "cross"


def hand_out_layer(c):
    """The Mamba layer whose scan output the gated memory units read."""
    return c["num_hidden_layers"] // 2


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def param_spec(c):
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    L = c["num_hidden_layers"]
    N, Nkv, H = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    di, Ns, R, K = mamba_sizes(c)
    out_gain = (2 * L) ** -0.5

    def leaf(shape, kind="normal", scale=1.0):
        return dict(shape=tuple(shape), kind=kind, scale=scale)

    def into(rows, cols, gain=1.0):  # reads unit-scale rows
        return leaf((rows, cols), scale=gain * rows**-0.5)

    def out(rows, cols):  # writes to the residual stream
        return leaf((rows, cols), scale=rows**-0.5 * out_gain)

    def norm(at):
        return {at + "/weight": leaf((d,), "ones"),
                at + "/bias": leaf((d,), scale=0.1)}

    spec = {"embedding": leaf((v, d), scale=0.02)}
    for i in range(L):
        at = f"layers/{i}/"
        kind = layer_kind(i, c)
        spec.update(norm(at + "norm"))
        if kind == "mamba":
            mixer = dict(
                in_proj=leaf((2, d, di), scale=d**-0.5),
                conv_w=leaf((di, K), scale=0.5),
                conv_b=leaf((di,), "zeros"),
                x_proj=into(di, R + 2 * Ns, 0.4),
                dt_proj=into(R, di, 2.0),
                dt_bias=leaf((di,), scale=2.0),
                A_log=leaf((di, Ns), scale=2.0),
                D=leaf((di,), "ones"),
                out_proj=out(di, d))
        elif kind == "gmu":
            mixer = dict(in_proj=into(d, di), out_proj=out(di, d))
        else:
            mixer = dict(
                wq=into(d, N * H, 2**0.5), bq=leaf((N * H,), scale=0.1),
                wo=out(N * H, d), bo=leaf((d,), scale=0.02),
                subln=leaf((2 * H,), "ones"),
                **{f"lambda_{n}": leaf((H,), scale=0.2)
                   for n in ("q1", "k1", "q2", "k2")})
            if kind != "cross":
                mixer.update(
                    wk=into(d, Nkv * H, 2**0.5), bk=leaf((Nkv * H,), scale=0.1),
                    wv=into(d, Nkv * H), bv=leaf((Nkv * H,), scale=0.1))
        spec.update({at + "mixer/" + k: s for k, s in mixer.items()})
        spec.update(norm(at + "norm2"))
        spec[at + "mlp/w1"] = into(d, f)
        spec[at + "mlp/w3"] = into(d, f)
        spec[at + "mlp/w2"] = out(f, d)
    spec.update(norm("norm_f"))
    return spec


def n_params(c):
    return sum(math.prod(s["shape"]) for s in param_spec(c).values())


def layer_paths(spec, i):
    at = f"layers/{i}/"
    return [p for p in spec if p.startswith(at)]


def layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * p["weight"] + p["bias"]


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def causal_conv(u, w, b):
    """Depthwise causal conv: u (B, S, C), w (C, K), b (C,). Tap K-1 is
    the current position's."""
    S, K = u.shape[1], w.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(padded[:, k : k + S] * w[:, k] for k in range(K))


def selective_scan(u, dt, A, Bm, Cm, D):
    """u, dt (B, S, C); A (C, Ns); Bm, Cm (B, S, Ns); D (C,) -> y (B, S,
    C), from a zero state; the carried array is (B, Ns, C)."""
    At = A.T

    def step(s, inp):
        u_t, dt_t, B_t, C_t = inp
        s = (jnp.exp(dt_t[:, None, :] * At) * s
             + (dt_t * u_t)[:, None, :] * B_t[:, :, None])
        return s, jnp.einsum("bnc,bn->bc", s, C_t) + D * u_t

    s0 = jnp.zeros(u.shape[:1] + At.shape, jnp.float32)
    _, y = lax.scan(
        step, s0, tuple(jnp.moveaxis(x, 1, 0) for x in (u, dt, Bm, Cm)),
        unroll=8)
    return jnp.moveaxis(y, 0, 1)


def mamba_mixer(h, p, c):
    """-> (the mixer's output (B, S, d), the scan's output y (B, S,
    d_inner) with its ``D`` skip, before the gate)."""
    _, Ns, R, _ = mamba_sizes(c)
    u, z = h @ p["in_proj"][0], h @ p["in_proj"][1]
    u = jax.nn.silu(causal_conv(u, p["conv_w"], p["conv_b"]))
    dbc = u @ p["x_proj"]  # no norm on dt_r, B or C
    dt = jax.nn.softplus(dbc[..., :R] @ p["dt_proj"] + p["dt_bias"])
    y = selective_scan(
        u, dt, -jnp.exp(p["A_log"]), dbc[..., R : R + Ns], dbc[..., R + Ns :],
        p["D"])
    return (y * jax.nn.silu(z)) @ p["out_proj"], y


def gmu_mixer(h, p, memory):
    return (jax.nn.silu(h @ p["in_proj"]) * memory) @ p["out_proj"]


def differential_attention(q, k, v, p, lam_init, window, block=QUERY_BLOCK):
    """q (B, S, N, H) over k, v (B, S, Nkv, H) of the same positions,
    causal and, with a ``window``, banded -> (B, S, N * H): the pairs' two
    softmaxes, their difference under ``lambda``, the norm by head and the
    ``1 - lambda_init`` factor. One block of query rows at a time."""
    B, S, N, H = q.shape
    Nkv = k.shape[2]
    g = N // Nkv  # query pairs to a key pair
    k1, k2 = k[:, :, 0::2], k[:, :, 1::2]  # (B, S, Nkv/2, H)
    V = v.reshape(B, S, Nkv // 2, 2 * H)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init)
    blk = math.gcd(S, block)
    cols = jnp.arange(S)

    def softmax_v(qi, ki, rows):
        s = jnp.einsum("bqjgh,bsjh->bjgqs", qi, ki) / jnp.sqrt(float(H))
        back = rows[:, None] - cols[None, :]
        seen = back >= 0
        if window:
            seen = seen & (back < window)
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bjgqs,bsjw->bqjgw", a, V)

    def one(args):
        qi, start = args  # (B, blk, N, H)
        rows = start + jnp.arange(blk)
        qp = qi.reshape(B, blk, Nkv // 2, g, 2, H)
        o = (softmax_v(qp[..., 0, :], k1, rows)
             - lam * softmax_v(qp[..., 1, :], k2, rows))
        o = rms_norm(o, p["subln"], SUBLN_EPS) * (1.0 - lam_init)
        return o.reshape(B, blk, N * H)

    out = lax.map(
        one, (jnp.moveaxis(q.reshape(B, S // blk, blk, N, H), 1, 0),
              jnp.arange(S // blk) * blk))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, N * H)


def attention_mixer(h, p, c, lam_init, window, kv=None):
    """A window, full or (with ``kv``, another layer's keys and values)
    cross layer -> (the mixer's output, the keys and values it attended)."""
    B, S, _ = h.shape
    N, Nkv, H = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    q = (h @ p["wq"] + p["bq"]).reshape(B, S, N, H)
    if kv is None:
        kv = ((h @ p["wk"] + p["bk"]).reshape(B, S, Nkv, H),
              (h @ p["wv"] + p["bv"]).reshape(B, S, Nkv, H))
    o = differential_attention(q, *kv, p, lam_init, window)
    return o @ p["wo"] + p["bo"], kv


def block(x, layer, c, kind, lam_init, shared):
    """One layer of ``kind`` on x (B, S, d); ``layer`` holds its leaves
    nested as the program's tree does (``norm``, ``mixer``, ``norm2``,
    ``mlp``); ``lam_init`` is ``lambda_init`` of the layer's index (an
    attention layer reads it); ``shared`` what goes across layers: ``M``
    behind a Mamba layer (the last one's is the hand-out layer's), ``kv``
    behind the full layer. -> (x, shared)."""
    eps = c["layer_norm_eps"]
    h, p = layer_norm(x, layer["norm"], eps), layer["mixer"]
    if kind == "mamba":
        out, memory = mamba_mixer(h, p, c)
        shared = {**shared, "M": memory}
    elif kind == "gmu":
        out = gmu_mixer(h, p, shared["M"])
    elif kind == "cross":
        out, _ = attention_mixer(h, p, c, lam_init, 0, shared["kv"])
    else:
        window = c["sliding_window"] if kind == "window" else 0
        out, kv = attention_mixer(h, p, c, lam_init, window)
        if kind == "full":
            shared = {**shared, "kv": kv}
    x = x + out
    h, m = layer_norm(x, layer["norm2"], eps), layer["mlp"]
    return x + (jax.nn.silu(h @ m["w1"]) * (h @ m["w3"])) @ m["w2"], shared


def forward(tree, tokens, c):
    """Logits (B, S, vocab) of the whole model from a parameter tree
    shaped as the program's (``layers`` a list). For the tests; the
    benchmark walks the layers one at a time."""
    x, shared = tree["embedding"][tokens], {}
    for i, layer in enumerate(tree["layers"]):
        x, shared = block(
            x, layer, c, layer_kind(i, c), lambda_init(i), shared)
    x = layer_norm(x, tree["norm_f"], c["layer_norm_eps"])
    return x @ tree["embedding"].T
