"""MiniCPM-SALA (``model_type: minicpm_sala``: block-sparse attention
layers that choose their context through compressed keys, beside
lightning linear-attention layers that keep a state a head) in plain
float32 ``jax.numpy``: the forward pass that serving is held to. Imports
nothing of the program.

Follows the published ``config.json`` (openbmb/MiniCPM-SALA), InfLLM-V2
(arXiv:2509.24663) and MiniCPM4 (arXiv:2506.07900) for the ``minicpm4``
layers, Lightning Attention-2 (arXiv:2401.04658) for the
``lightning-attn`` layers. ``d`` = hidden_size, ``N`` =
num_attention_heads, ``Nkv`` = num_key_value_heads, ``H`` = head_dim,
``u`` the normed input of a sub-block; no bias anywhere.

- Whole model: ``h0 = scale_emb * E[id]``; layer ``i`` (``layer_kind``:
  ``sparse`` for ``mixer_types[i] == "minicpm4"``, ``lightning`` for
  ``"lightning-attn"``): ``a = h + s * Mixer(rms(h, w_attn))``, ``h' = a +
  s * MLP(rms(a, w_ffn))`` with ``s = scale_depth / sqrt(published depth)``
  (``published.num_hidden_layers``, whatever depth is kept); ``MLP(u) =
  (silu(u W1) * (u W3)) W2``; ``logits = rms(h_L, w_f) W_head /
  (hidden_size / dim_model_base)``, head untied.
- Sparse layer: ``q = u W_q`` (N heads of H), ``k = u W_k``, ``v = u W_v``
  (Nkv heads of H); ``rms`` with a learned weight over each head's H
  values of q and of k; no rotary. For the query at position ``t`` and kv
  head ``g`` (``N / Nkv`` query heads ``h`` in a row):
  compressed keys ``Kc_j = mean(k[stride * j : stride * j + kernel])`` for
  every ``j`` whose window ends at or before ``t``; ``p[h, j] = softmax_j(q
  [t, h] . Kc_j / sqrt(H))``, ``p[j] = sum_h p[h, j]``; block ``b``
  (``block_size`` positions) scores the largest ``p[j]`` over ``r * b - 1
  <= j <= r * b + r - 1`` (``r = block_size / stride``: every window that
  touches the block), over the ``j`` that exist; block 0
  (``init_blocks``) and the ``window_size / block_size`` blocks that end
  at the query's own score +inf; the ``topk`` best blocks among ``0 .. t
  // block_size`` are chosen, ties to the lower index; ``o[t, h] =
  softmax over the positions <= t of the chosen blocks of (q[t, h] . k /
  sqrt(H))`` times ``v``. A query with ``t + 1 <= dense_len`` attends all
  positions ``<= t``. ``out = (o * sigmoid(u W_g)) W_o``.
- Lightning layer: ``q, k, v = u W_q, u W_k, u W_v`` (``lightning_nh``
  heads of ``lightning_head_dim``); ``rms`` by head on q and k; rotary on
  q and k (``rope_theta``, the whole head, its two halves paired ``(x[i],
  x[i + H/2])``). By head ``h``: ``S_t = lam_h S_{t-1} + k_t v_t^T`` (H x
  H, ``S_{-1} = 0``), ``o_t = q_t^T S_t / sqrt(H)``, ``lam_h = exp(-2^(-8
  (h + 1) / heads))``. ``out = (rms(o, w_o_norm) * sigmoid(u W_g)) W_o``,
  the norm over the concatenated heads. The recurrence is run position by
  position.

**Assumed** (the configuration file lists each with its reason): the
sparse sizes (``sparse_config``), that a position goes dense or sparse
by its own ``t``, the decay's slopes, no activation on q, k, v, the
precisions.

Departures, of memory only: a layer is walked in blocks of positions
(``BLOCK``), a sparse layer's keys, values and compressed keys made whole
first (two kv heads: small), a lightning layer's state carried from block
to block; so a sequence of 64k positions fits beside one layer's weights.

**Seeded weights** (``weights.py`` draws ``normal``, ``ones``, ``zeros``):
embedding std 0.02 (``scale_emb`` makes it 0.24); a matrix that reads a
block's input std 1 / sqrt(rows), and so do those that write to the
residual stream: ``scale_depth / sqrt(32)`` is the family's own gain on
what a sub-block adds; the head std ``(hidden_size / dim_model_base) /
sqrt(d)``, the size the family's division of the logits expects, so that
logits are of unit scale as the other families' are. The norms over q and
k make every score a product of two unit-RMS heads over sqrt(H).
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 128  # positions a step of a layer's walk takes

KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def layer_kind(i, c):
    """``sparse`` or ``lightning``: the name of the layer's stack."""
    return KINDS[c["mixer_types"][i]]


def kinds(c):
    """``{kind: number of its layers}``, in the order they first occur."""
    out = {}
    for i in range(c["num_hidden_layers"]):
        out[layer_kind(i, c)] = out.get(layer_kind(i, c), 0) + 1
    return out


def residual_gain(c):
    depth = (c.get("published") or {}).get(
        "num_hidden_layers", c["num_hidden_layers"])
    return c["scale_depth"] / math.sqrt(depth)


def logit_divisor(c):
    return c["hidden_size"] / c["dim_model_base"]


def param_spec(c):
    d, v, f = c["hidden_size"], c["vocab_size"], c["intermediate_size"]
    N, Nkv, H = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    Nl, Hl = c["lightning_nh"], c["lightning_head_dim"]
    spec = {"embedding": dict(shape=(v, d), kind="normal", scale=0.02)}
    for kind, n in kinds(c).items():
        def leaf(shape, how="normal", scale=1.0):
            return dict(shape=(n,) + tuple(shape), kind=how, scale=scale,
                        stacked=True)

        def mat(*shape):
            return leaf(shape, scale=shape[-2] ** -0.5)

        if kind == "sparse":
            heads, kv, hd = N * H, Nkv * H, H
        else:
            heads, kv, hd = Nl * Hl, Nl * Hl, Hl
        stack = {
            "attn_norm": leaf((d,), "ones"),
            "wq": mat(d, heads), "wk": mat(d, kv), "wv": mat(d, kv),
            "q_norm": leaf((hd,), "ones"), "k_norm": leaf((hd,), "ones"),
        }
        if kind == "lightning":
            stack["o_norm"] = leaf((heads,), "ones")
        stack.update(
            wg=mat(d, heads), wo=mat(heads, d),
            ffn_norm=leaf((d,), "ones"),
            w1=mat(d, f), w3=mat(d, f), w2=mat(f, d),
        )
        spec.update({f"{kind}/{k}": s for k, s in stack.items()})
    spec["norm"] = dict(shape=(d,), kind="ones")
    spec["lm_head"] = dict(
        shape=(d, v), kind="normal", scale=logit_divisor(c) * d**-0.5)
    return spec


def layer_paths(spec, kind):
    """The stacked leaves of the layers of ``kind``; a layer's index in
    its stack is its index among the layers of its kind."""
    return [p for p in spec if p.startswith(kind + "/")]


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def embed(emb, tokens, c):
    """``h0`` of ``tokens``: the embedding's rows times ``scale_emb``."""
    return emb[tokens] * c["scale_emb"]


def logits(x, norm, head, c):
    return rms_norm(x, norm, c["rms_norm_eps"]) @ head / logit_divisor(c)


def _blocks(x, blk):
    """(B, S, ...) -> (S / blk, B, blk, ...)."""
    B, S = x.shape[:2]
    return jnp.moveaxis(x.reshape((B, S // blk, blk) + x.shape[2:]), 1, 0)


def _unblocks(y):
    """(n, B, blk, ...) -> (B, n * blk, ...)."""
    y = jnp.moveaxis(y, 0, 1)
    return y.reshape((y.shape[0], y.shape[1] * y.shape[2]) + y.shape[3:])


# ---------------------------------------------------------------------------
# the sparse layer
# ---------------------------------------------------------------------------


def compress(k, sc):
    """k (B, S, Nkv, H) -> the means of every whole window of ``kernel``
    positions, ``stride`` apart: (B, nC, Nkv, H), ``nC = (S - kernel) //
    stride + 1`` (none for a sequence shorter than a window)."""
    S = k.shape[1]
    kernel, stride = sc["kernel_size"], sc["kernel_stride"]
    n = max(0, (S - kernel) // stride + 1)
    at = (jnp.arange(n) * stride)[:, None] + jnp.arange(kernel)[None, :]
    return jnp.mean(k[:, at], axis=2)


def choose_blocks(q, kc, t, nb, sc):
    """The blocks each query attends. q (B, T, Nkv, g, H) at positions
    ``t`` (T,); kc (B, nC, Nkv, H) -> (B, Nkv, T, nb) bool."""
    H = q.shape[-1]
    kernel, stride, bs = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    r = bs // stride
    nC = kc.shape[1]
    cur = t // bs  # (T,)
    blocks = jnp.arange(nb)
    exists = blocks[None, :] <= cur[:, None]  # (T, nb)
    if nC:
        s = jnp.einsum("btkgh,bjkh->bkgtj", q, kc) / math.sqrt(H)
        ends = jnp.arange(nC) * stride + kernel - 1
        valid = ends[None, :] <= t[:, None]  # (T, nC)
        s = jnp.where(valid, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(valid, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
        z = jnp.sum(e, axis=-1, keepdims=True)
        p = jnp.sum(e / jnp.where(z > 0, z, 1.0), axis=2)  # (B, Nkv, T, nC)
        js = blocks[:, None] * r + jnp.arange(-1, r)[None, :]  # (nb, r + 1)
        inside = (js >= 0) & (js < nC)
        pj = p[..., jnp.clip(js, 0, nC - 1)]  # (B, Nkv, T, nb, r + 1)
        ok = inside[None, :, :] & valid[:, jnp.clip(js, 0, nC - 1)]
        score = jnp.max(jnp.where(ok, pj, -1.0), axis=-1)  # (B, Nkv, T, nb)
    else:
        score = jnp.full(q.shape[:1] + (q.shape[2], q.shape[1], nb), -1.0)
    back = cur[:, None] - blocks[None, :]
    forced = (blocks[None, :] < sc["init_blocks"]) | (
        (back >= 0) & (back < sc["window_size"] // bs))
    key = jnp.where(forced, jnp.inf, score)
    key = jnp.where(exists, key, -jnp.inf)
    top, idx = lax.top_k(key, min(sc["topk"], nb))  # ties: the lower index
    chosen = jnp.any(
        (idx[..., None] == blocks) & (top[..., None] > -jnp.inf), axis=-2)
    dense = (t + 1 <= sc["dense_len"])[:, None]
    return jnp.where(dense, exists, chosen)


def sparse_mix(u_blocks, p, c):
    """The sparse layer's mixer over u (n, B, blk, d) -> (n, B, blk, d)."""
    N, Nkv, H = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    sc, eps = c["sparse_config"], c["rms_norm_eps"]
    n, B, blk, _ = u_blocks.shape
    S = n * blk
    bs = sc["block_size"]
    nb = -(-S // bs)

    def kv(u):
        k = rms_norm((u @ p["wk"]).reshape(B, blk, Nkv, H), p["k_norm"], eps)
        return k, (u @ p["wv"]).reshape(B, blk, Nkv, H)

    k, v = lax.map(kv, u_blocks)
    k, v = _unblocks(k), _unblocks(v)
    kc = compress(k, sc)
    block_of = jnp.arange(S) // bs

    def one(args):
        u, start = args
        t = start + jnp.arange(blk)
        q = rms_norm((u @ p["wq"]).reshape(B, blk, N, H), p["q_norm"], eps)
        q = q.reshape(B, blk, Nkv, N // Nkv, H)
        chosen = choose_blocks(q, kc, t, nb, sc)  # (B, Nkv, blk, nb)
        seen = chosen[..., block_of] & (jnp.arange(S)[None, :] <= t[:, None])
        s = jnp.einsum("btkgh,bskh->bkgts", q, k) / math.sqrt(H)
        s = jnp.where(seen[:, :, None], s, -jnp.inf)
        o = jnp.einsum("bkgts,bskh->btkgh", jax.nn.softmax(s, axis=-1), v)
        o = o.reshape(B, blk, N * H) * jax.nn.sigmoid(u @ p["wg"])
        return o @ p["wo"]

    return lax.map(one, (u_blocks, jnp.arange(n) * blk))


# ---------------------------------------------------------------------------
# the lightning layer
# ---------------------------------------------------------------------------


def decay(n_heads):
    """``lam_h = exp(-2^(-8 (h + 1) / heads))``: ALiBi's slopes."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return jnp.exp(-(2.0 ** (-8.0 * h / n_heads)))


def rotary(x, positions, theta):
    """The two halves of the last axis paired and turned by each
    position's angles. x (B, T, n, H), positions (T,)."""
    H = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, H, 2, dtype=jnp.float32) / H)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : H // 2], x[..., H // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def lightning_mix(u_blocks, p, c):
    """The lightning layer's mixer over u (n, B, blk, d), the state
    carried from block to block and stepped position by position."""
    n_h, H = c["lightning_nh"], c["lightning_head_dim"]
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    n, B, blk, _ = u_blocks.shape
    lam = decay(n_h)[None, :, None, None]

    def one(S, args):
        u, start = args
        t = start + jnp.arange(blk)
        q = rms_norm((u @ p["wq"]).reshape(B, blk, n_h, H), p["q_norm"], eps)
        k = rms_norm((u @ p["wk"]).reshape(B, blk, n_h, H), p["k_norm"], eps)
        v = (u @ p["wv"]).reshape(B, blk, n_h, H)
        q, k = rotary(q, t, theta), rotary(k, t, theta)

        def step(S, qkv):
            qt, kt, vt = qkv  # (B, n_h, H)
            S = lam * S + kt[..., :, None] * vt[..., None, :]
            return S, jnp.einsum("bnk,bnkv->bnv", qt, S) / math.sqrt(H)

        S, o = lax.scan(
            step, S, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
        o = jnp.moveaxis(o, 0, 1).reshape(B, blk, n_h * H)
        o = rms_norm(o, p["o_norm"], eps) * jax.nn.sigmoid(u @ p["wg"])
        return S, o @ p["wo"]

    S0 = jnp.zeros((B, n_h, H, H), jnp.float32)
    _, out = lax.scan(one, S0, (u_blocks, jnp.arange(n) * blk))
    return out


def block(x, layer, c, kind):
    """One layer of ``kind`` on x (B, S, d); ``layer`` holds that layer's
    leaves under the program's names."""
    eps, s = c["rms_norm_eps"], residual_gain(c)
    blk = math.gcd(x.shape[1], BLOCK)
    xb = _blocks(x, blk)
    u = lax.map(lambda a: rms_norm(a, layer["attn_norm"], eps), xb)
    mix = sparse_mix if kind == "sparse" else lightning_mix
    a = xb + s * mix(u, layer, c)

    def mlp(a):
        h = rms_norm(a, layer["ffn_norm"], eps)
        return a + s * swiglu(h, layer["w1"], layer["w3"], layer["w2"])

    return _unblocks(lax.map(mlp, a))


def forward(tree, tokens, c):
    """Logits (B, S, vocab) of the whole model from a parameter tree
    shaped as the program's. For the tests; the benchmark walks the
    layers one at a time."""
    x = embed(tree["embedding"], tokens, c)
    seen = {}
    for i in range(c["num_hidden_layers"]):
        kind = layer_kind(i, c)
        at = seen[kind] = seen.get(kind, -1) + 1
        x = block(x, jax.tree.map(lambda a: a[at], tree[kind]), c, kind)
    return logits(x, tree["norm"], tree["lm_head"], c)
