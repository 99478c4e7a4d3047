"""LFM2-24B-A2B (``model_type: lfm2_moe``: gated short convolutions
beside a few grouped-query attention layers, over many small
sigmoid-routed experts with no shared one) in plain float32
``jax.numpy``: the forward pass that serving is held to.

Follows the published ``config.json`` (LiquidAI/LFM2-24B-A2B) and, for
what it leaves unsaid, the LFM2 family's published description (Liquid
AI, "LFM2 technical report": double-gated short convolutions, GQA with
QK-norm, SwiGLU experts behind a biased sigmoid router). ``d`` =
hidden_size, ``N`` = num_attention_heads, ``Nkv`` = num_key_value_heads,
``H`` = d / N, ``K`` = conv_L_cache; no bias but the router's.

- Layer ``i``: ``a = x + op_i(rms(x, w_op))``, ``y = a + ff_i(rms(a,
  w_ff))``, eps ``norm_eps``. ``op_i`` by ``layer_types[i]``; ``ff_i`` a
  dense SwiGLU of intermediate_size for ``i < num_dense_layers`` and the
  expert layer after (``layer_kind``).
- ``conv``: ``[B | C | x] = h W_in`` (d -> 3 d, in that order); ``z_t =
  B_t * x_t``; ``c_t = sum_{j < K} w[:, j] * z_{t - K + 1 + j}`` by
  channel (depthwise, causal, ``z`` zero before the sequence); ``out_t =
  (C_t * c_t) W_out``. No activation, no bias (``conv_bias: false``). The
  whole sequence at once: no window is kept anywhere (what the program's
  chunked prefill and decode step have to agree with).
- ``full_attention``: ``q = h W_q`` (N heads of H), ``k = h W_k``, ``v =
  h W_v`` (Nkv heads of H); ``rms`` with a learned weight over each
  head's H values of q and of k; rotary embedding
  (``rope_parameters.rope_theta``, default type: the two halves of a head
  paired, ``(x[j], x[j + H/2])``) on q and k; ``score(t, u) = q_t . k_u /
  sqrt(H)`` for ``u <= t``; softmax; ``N / Nkv`` query heads share a kv
  head; ``(sum p v over heads) W_o``.
- Expert layer: ``s = sigmoid(h W_g)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + b`` are chosen
  (``use_expert_bias``: the bias chooses and does not weigh); ``w_i =
  routed_scaling_factor * s_i / (sum of the chosen s + 1e-6)``
  (``norm_topk_prob``); ``y = sum over the chosen of w_i E_i(h)``, every
  ``E_i`` a SwiGLU of moe_intermediate_size: a loop over the experts
  held, one at a time, each weighted by the row's weight for it, exactly
  zero where the row did not choose it. No shared expert.
- Final ``rms(x, w_f)`` (the family's ``embedding_norm``; ``norm_f`` in
  the tree), logits ``x E^T`` with ``E`` the embedding (tied).

**A share** (guide section 4): a tree may hold experts
``first_expert_held`` to ``first_expert_held + num_experts`` of
``published.num_experts``; the router keeps its published width, a chosen
expert that is not held adds nothing. The benchmark's configuration holds
all 64; ``share(c, first, count)`` gives the config of a share, and
tests/test_lfm2.py adds four up to the uncut layer.

**Assumed** (the config has no key; the configuration file lists them):
the tied head; H = d / N; the QK-norm before the rotary and its pairing by
halves; the ``1e-6`` under the router's sum; no activation in the
convolution.

Departures, of memory only: attention in blocks of query rows
(``reference/kexaone.py::masked_attention`` with no window).

**Seeded weights** (``weights.py`` draws ``normal``, ``ones``, ``zeros``):
as ``reference/kexaone.py`` has them and for its reasons. Embedding std
0.02; a matrix that reads the block's input std 1 / sqrt(rows); the
matrices that write to the residual stream (``W_out``, ``W_o``, every
``W2``) a further 1 / sqrt(2 L); the router's ``W_g`` std 1 / sqrt(d) and
its bias normal with std 0.02 (the law the k-exaone cell uses: small
beside a score's own spread). The convolution's taps are normal with std
``1 / sqrt(K)``: ``B`` and ``x`` have unit scale, so ``z = B * x`` has
unit variance, and ``c_t``, a sum of ``K`` such values under taps whose
squares sum to one in the mean, keeps it; ``C * c`` has unit variance
again and ``W_out`` reads unit-scale rows like every other matrix. A tap
is as large as the one on the position itself, so a window that is lost
moves ``c`` at the next ``K - 1`` positions by as much as its own size.
"""

import jax
import jax.numpy as jnp
from jax import lax

# the attention (the QK-norm's rms, the rotary by halves, the causal
# softmax in blocks of query rows) is the k-exaone reference's window
# layer without its window, the SwiGLU and the norm the sarvam
# reference's, key for key; the router differs (the 1e-6, no shared
# expert) and is written here
from benchmark.reference.kexaone import masked_attention, rotary
from benchmark.reference.sarvam import rms_norm, router_width, swiglu

ROUTER_SUM_EPS = 1e-6


def head_dim(c):
    return c["hidden_size"] // c["num_attention_heads"]


def share(c, first, count):
    """The config of the share that holds experts ``first`` to ``first +
    count`` of the router's whole width."""
    return {
        **c, "num_experts": count, "first_expert_held": first,
        "published": {**(c.get("published") or {}),
                      "num_experts": router_width(c)},
    }


def layer_kind(i, c):
    """``conv_dense``, ``conv_sparse``, ``attn_dense`` or ``attn_sparse``."""
    op = "conv" if c["layer_types"][i] == "conv" else "attn"
    return op + ("_dense" if i < c["num_dense_layers"] else "_sparse")


def param_spec(c):
    d, v = c["hidden_size"], c["vocab_size"]
    L, K = c["num_hidden_layers"], c["conv_L_cache"]
    N, Nkv, H = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    f, h = c["intermediate_size"], c["moe_intermediate_size"]
    held, E = c["num_experts"], router_width(c)
    out_gain = (2 * L) ** -0.5

    def leaf(shape, kind="normal", scale=1.0):
        return dict(shape=tuple(shape), kind=kind, scale=scale)

    def into(*shape):  # reads unit-scale rows
        return leaf(shape, scale=shape[-2] ** -0.5)

    def out(*shape):  # writes to the residual stream
        return leaf(shape, scale=shape[-2] ** -0.5 * out_gain)

    spec = {"embedding": leaf((v, d), scale=0.02)}
    for i in range(L):
        at = f"layers/{i}/"
        kind = layer_kind(i, c)
        layer = {"operator_norm": leaf((d,), "ones")}
        if kind.startswith("conv"):
            layer.update(
                in_proj=into(d, 3 * d),
                conv_w=leaf((d, K), scale=K**-0.5),
                out_proj=out(d, d))
        else:
            layer.update(
                wq=into(d, N * H), wk=into(d, Nkv * H), wv=into(d, Nkv * H),
                q_norm=leaf((H,), "ones"), k_norm=leaf((H,), "ones"),
                wo=out(N * H, d))
        layer["ffn_norm"] = leaf((d,), "ones")
        if kind.endswith("_dense"):
            layer.update(w1=into(d, f), w3=into(d, f), w2=out(f, d))
        else:
            layer.update(
                gate=into(d, E), gate_bias=leaf((E,), scale=0.02),
                w1=into(held, d, h), w3=into(held, d, h), w2=out(held, h, d))
        spec.update({at + k: s for k, s in layer.items()})
    spec["norm_f"] = leaf((d,), "ones")
    return spec


def layer_paths(spec, i):
    at = f"layers/{i}/"
    return [p for p in spec if p.startswith(at)]


def short_conv(h, p, c):
    """The gated short convolution over the whole sequence h (B, S, d)."""
    K, S = c["conv_L_cache"], h.shape[1]
    b, gate, x = jnp.split(h @ p["in_proj"], 3, axis=-1)
    z = b * x
    past = jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0)))  # zeros before the start
    conv = sum(past[:, j:j + S] * p["conv_w"][:, j] for j in range(K))
    return (gate * conv) @ p["out_proj"]


def attention(h, p, c):
    B, S, _ = h.shape
    N, Nkv, H = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    eps = c["norm_eps"]
    q = rms_norm((h @ p["wq"]).reshape(B, S, N, H), p["q_norm"], eps)
    k = rms_norm((h @ p["wk"]).reshape(B, S, Nkv, H), p["k_norm"], eps)
    v = (h @ p["wv"]).reshape(B, S, Nkv, H)
    return masked_attention(rotary(q, c), rotary(k, c), v, 0) @ p["wo"]


def route(h, p, c):
    """-> (chosen ids (B, S, K) over the router's whole width, their
    weights (B, S, K))."""
    s = jax.nn.sigmoid(h @ p["gate"])
    _, idx = lax.top_k(s + p["gate_bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    total = jnp.sum(w, -1, keepdims=True) + ROUTER_SUM_EPS
    return idx, c.get("routed_scaling_factor", 1.0) * w / total


def moe(h, p, c):
    """``sum over the chosen of w_i E_i(h)``, the part that the experts
    held give; no shared expert."""
    idx, w = route(h, p, c)
    first = c.get("first_expert_held", 0)

    def one(y, e):
        w1, w3, w2, eid = e
        mine = jnp.sum(jnp.where(idx == eid, w, 0.0), axis=-1)  # (B, S)
        return y + mine[..., None] * swiglu(h, w1, w3, w2), None

    ids = first + jnp.arange(p["w1"].shape[0])
    y, _ = lax.scan(one, jnp.zeros_like(h), (p["w1"], p["w3"], p["w2"], ids))
    return y


def block(x, layer, c, kind):
    """One layer of ``kind`` on x (B, S, d); ``layer`` holds that layer's
    leaves under the program's names."""
    eps = c["norm_eps"]
    h = rms_norm(x, layer["operator_norm"], eps)
    op = short_conv if kind.startswith("conv") else attention
    x = x + op(h, layer, c)
    h = rms_norm(x, layer["ffn_norm"], eps)
    if kind.endswith("_dense"):
        return x + swiglu(h, layer["w1"], layer["w3"], layer["w2"])
    return x + moe(h, layer, c)


def forward(tree, tokens, c):
    """Logits (B, S, vocab) of the whole model from a parameter tree
    shaped as the program's. For the tests; the benchmark walks the
    layers one at a time."""
    x = tree["embedding"][tokens]
    for i, layer in enumerate(tree["layers"]):
        x = block(x, layer, c, layer_kind(i, c))
    return rms_norm(x, tree["norm_f"], c["norm_eps"]) @ tree["embedding"].T
