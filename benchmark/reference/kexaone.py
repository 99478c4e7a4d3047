"""K-EXAONE-236B-A23B (``model_type: exaone_moe``: grouped-query
attention whose layers are of two kinds, sliding-window and full, over
many small sigmoid-routed experts beside a shared one) in plain float32
``jax.numpy``: the forward pass that serving is held to.

Follows the published ``config.json`` (LGAI-EXAONE/K-EXAONE-236B-A23B),
the DeepSeek-V3 paper its router keys come from and the EXAONE 4.0
report for what the config leaves unsaid. ``d`` = hidden_size, ``N`` =
num_attention_heads, ``Nkv`` = num_key_value_heads, ``H`` = head_dim; no
bias but the router's.

- Layer ``i`` (``layer_kind``: its attention kind ``layer_types[i]``,
  its feed-forward kind ``mlp_layer_types[i]``): ``a = x + attn(rms(x,
  w_attn))``, ``y = a + ffn(rms(a, w_ffn))``, eps ``rms_norm_eps``.
- Attention: ``q = h W_q`` (N heads of H), ``k = h W_k``, ``v = h W_v``
  (Nkv heads of H); ``rms`` with a learned weight over each head's H
  values of q and of k; on a ``sliding_attention`` layer rotary embedding
  (``rope_parameters.rope_theta``, default type: the two halves of a head
  paired, ``(x[i], x[i + H/2])``) on q and k, on a ``full_attention``
  layer none; ``score(t, u) = q_t . k_u / sqrt(H)`` for ``u <= t`` and,
  on a sliding layer, ``t - u < sliding_window`` (a position sees itself
  and the ``sliding_window - 1`` before it); softmax; ``N / Nkv`` query
  heads share a kv head; ``(sum p v over heads) W_o``. Keys and values
  are made for every position and masked: no cache of either kind, no
  ring, no pages (what the program's decode step has to agree with).
- ``dense`` feed-forward: ``(silu(h W1) * (h W3)) W2`` of
  intermediate_size. ``sparse``: ``s = sigmoid(h W_g)`` over all
  ``published.num_experts`` (the router's width); the
  ``num_experts_per_tok`` largest of ``s + b`` are chosen (``n_group`` =
  ``topk_group`` = 1: over all experts; the bias chooses and does not
  weigh); ``w_i = routed_scaling_factor * s_i / sum of the chosen s``
  (``norm_topk_prob``); ``y = sum over the chosen of w_i E_i(h) + S(h)``,
  every ``E_i`` (moe_intermediate_size) and the shared ``S``
  (``num_shared_experts * moe_intermediate_size``) a SwiGLU:
  ``reference/sarvam.py::moe``, whose keys these are (a loop over the
  experts held, one at a time, each weighted by the row's weight for it,
  exactly zero where the row did not choose it).
- Final ``rms(x, w_f)``, logits ``x W_head`` (untied).

**The share** (guide section 4): the tree holds experts
``first_expert_held`` to ``first_expert_held + num_experts`` of
``published.num_experts`` and ``vocab_size`` rows of the vocabulary. The
router keeps its published width and its experts per token; a chosen
expert that is not held adds nothing, here as in the program, and that
partial result goes on to the next layer. ``share(c, first)`` gives the
config of another share; tests/test_kexaone.py adds eight up to the
uncut layer.

**Assumed** (the config has no key; the configuration file lists them):
the QK-norm; rotary on the window layers only; the norms before each
sub-block; the router's choosing bias. **Omitted**: the
multi-token-prediction module (``num_nextn_predict_layers``), which does
not enter the next-token logits.

Departures, of memory only: attention in blocks of query rows.

**Seeded weights** (``weights.py`` draws ``normal``, ``ones``, ``zeros``):
as ``reference/sarvam.py`` has them and for its reasons. Embedding std
0.02; a matrix that reads the block's input std 1 / sqrt(rows); the
matrices that write to the residual stream (``W_o``, every ``W2``) a
further 1 / sqrt(2 L); the router's ``W_g`` std 1 / sqrt(d) and its bias
normal with std 0.02: small beside a score's own spread, as a trained
router's bias is once it has balanced the load. A chip's 16 experts
still draw 0.11-0.14 of the pairs by seed where an eighth is 0.125, and
the served tokens a second follow that share: not by the bias (std 0.1
read the same swing) but because random attention averages its values,
so every position's hidden state carries a common part (2% of its energy
after the first layer, 9% after the eighth) that pulls each expert's
score one way for the whole run. Only a bias balanced against the data
would take that out (PERF.md sections 6 and 7, PR 33).
The norms over q and k make every score a product
of two unit-RMS heads over sqrt(H): of unit scale at any width.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

# the expert layer (the DeepSeek-V3 router keys, the experts held, the
# shared expert) and the norm are the sarvam reference's, key for key
from benchmark.reference.sarvam import (  # noqa: F401
    held_experts,
    moe,
    rms_norm,
    route,
    router_width,
    share,
    swiglu,
)

QUERY_BLOCK = 256


def layer_kind(i, c):
    """``sliding_dense``, ``sliding_sparse``, ``full_dense`` or
    ``full_sparse``: the name of the layer's stack in the tree."""
    return c["layer_types"][i].split("_")[0] + "_" + c["mlp_layer_types"][i]


def kinds(c):
    """``{kind: number of its layers}``, in the order they first occur."""
    out = {}
    for i in range(c["num_hidden_layers"]):
        out[layer_kind(i, c)] = out.get(layer_kind(i, c), 0) + 1
    return out


def param_spec(c):
    d, v = c["hidden_size"], c["vocab_size"]
    L = c["num_hidden_layers"]
    N, Nkv, H = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    f, h = c["intermediate_size"], c["moe_intermediate_size"]
    held, hs = c["num_experts"], c.get("num_shared_experts", 0) * h
    out_gain = (2 * L) ** -0.5

    spec = {"embedding": dict(shape=(v, d), kind="normal", scale=0.02)}
    for kind, n in kinds(c).items():
        def leaf(shape, how="normal", scale=1.0):
            return dict(shape=(n,) + tuple(shape), kind=how, scale=scale,
                        stacked=True)

        def into(*shape):  # reads unit-scale rows
            return leaf(shape, scale=shape[-2] ** -0.5)

        def out(*shape):  # writes to the residual stream
            return leaf(shape, scale=shape[-2] ** -0.5 * out_gain)

        stack = {
            "attn_norm": leaf((d,), "ones"),
            "wq": into(d, N * H), "wk": into(d, Nkv * H),
            "wv": into(d, Nkv * H),
            "q_norm": leaf((H,), "ones"), "k_norm": leaf((H,), "ones"),
            "wo": out(N * H, d),
            "ffn_norm": leaf((d,), "ones"),
        }
        if kind.endswith("_dense"):
            stack.update(w1=into(d, f), w3=into(d, f), w2=out(f, d))
        else:
            stack.update(
                gate=into(d, router_width(c)),
                gate_bias=leaf((router_width(c),), scale=0.02),
                w1=into(held, d, h), w3=into(held, d, h), w2=out(held, h, d),
            )
            if hs:
                stack.update(shared_w1=into(d, hs), shared_w3=into(d, hs),
                             shared_w2=out(hs, d))
        spec.update({f"{kind}/{k}": s for k, s in stack.items()})
    spec["norm"] = dict(shape=(d,), kind="ones")
    spec["lm_head"] = dict(shape=(d, v), kind="normal", scale=d**-0.5)
    return spec


def layer_paths(spec, kind):
    """The stacked leaves of the layers of ``kind``; a layer's index in
    its stack is its index among the layers of its kind."""
    return [p for p in spec if p.startswith(kind + "/")]


def rotary(x, c):
    """The two halves of the last axis paired and turned by each
    position's angles. x (B, S, n, H) with S on axis 1."""
    S, H = x.shape[1], x.shape[-1]
    theta = float(c["rope_parameters"]["rope_theta"])
    inv = 1.0 / theta ** (jnp.arange(0, H, 2, dtype=jnp.float32) / H)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : H // 2], x[..., H // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def masked_attention(q, k, v, window, block=QUERY_BLOCK):
    """softmax(q k^T / sqrt(H)) v over the keys a query may see (not
    after it; with a ``window``, fewer than that many behind it), one
    block of query rows at a time. q (B, S, N, H); k, v (B, S, Nkv, H),
    each kv head serving N / Nkv query heads in a row."""
    B, S, N, H = q.shape
    g = N // k.shape[2]
    blk = math.gcd(S, block)
    qb = jnp.moveaxis(q.reshape(B, S // blk, blk, k.shape[2], g, H), 1, 0)
    cols = jnp.arange(S)

    def one(args):
        qi, start = args
        s = jnp.einsum("bqkgh,bskh->bkgqs", qi, k) / math.sqrt(H)
        back = (start + jnp.arange(blk))[:, None] - cols[None, :]
        seen = back >= 0
        if window:
            seen = seen & (back < window)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bkgqs,bskh->bqkgh", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(one, (qb, jnp.arange(S // blk) * blk))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, N * H)


def attention(h, p, c, sliding):
    B, S, _ = h.shape
    N, Nkv, H = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["rms_norm_eps"]
    q = rms_norm((h @ p["wq"]).reshape(B, S, N, H), p["q_norm"], eps)
    k = rms_norm((h @ p["wk"]).reshape(B, S, Nkv, H), p["k_norm"], eps)
    v = (h @ p["wv"]).reshape(B, S, Nkv, H)
    if sliding:
        q, k = rotary(q, c), rotary(k, c)
    window = c["sliding_window"] if sliding else 0
    return masked_attention(q, k, v, window) @ p["wo"]


def block(x, layer, c, kind):
    """One layer of ``kind`` on x (B, S, d); ``layer`` holds that layer's
    leaves under the program's names."""
    eps = c["rms_norm_eps"]
    sliding = kind.startswith("sliding_")
    x = x + attention(rms_norm(x, layer["attn_norm"], eps), layer, c, sliding)
    h = rms_norm(x, layer["ffn_norm"], eps)
    if kind.endswith("_dense"):
        return x + swiglu(h, layer["w1"], layer["w3"], layer["w2"])
    return x + moe(h, layer, c)


def forward(tree, tokens, c):
    """Logits (B, S, vocab) of the whole model from a parameter tree
    shaped as the program's. For the tests; the benchmark walks the
    layers one at a time."""
    x = tree["embedding"][tokens]
    seen = {}
    for i in range(c["num_hidden_layers"]):
        kind = layer_kind(i, c)
        at = seen[kind] = seen.get(kind, -1) + 1
        x = block(x, jax.tree.map(lambda a: a[at], tree[kind]), c, kind)
    return rms_norm(x, tree["norm"], c["rms_norm_eps"]) @ tree["lm_head"]
