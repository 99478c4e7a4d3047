"""Operations and bytes that the LFM2 configuration's algorithms need,
from shapes alone: what the published mathematics asks (the causal
products of an attention layer, its live pages, each weight that some
stream needs once, a stream's windows), never what a kernel happens to
touch. ``c`` is the configuration file's dict (the published
``config.json`` keys as run: ``num_hidden_layers`` and ``layer_types``
the layers kept, ``num_experts`` the experts held)."""

from benchmark.costs_sarvam import (  # the same router keys
    BF16,
    expected_distinct_held,
    router_width,
)


def head_dim(c):
    return c["hidden_size"] // c["num_attention_heads"]


def layers(c):
    """(convolution layers, attention layers, dense layers, expert layers)."""
    n = c["num_hidden_layers"]
    conv = sum(t == "conv" for t in c["layer_types"][:n])
    dense = min(n, c["num_dense_layers"])
    return conv, n - conv, dense, n - dense


def kv_row_bytes(c):
    """A position's key and value in one attention layer."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * BF16


def conv_params(c):
    d = c["hidden_size"]
    return 3 * d * d + d * d + d * c["conv_L_cache"]


def attention_params(c):
    d, H = c["hidden_size"], head_dim(c)
    N, Nkv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * N * H + 2 * d * Nkv * H + 2 * H


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def decode_attn_cost(c, kv_tokens):
    """(operations, bytes) of one decode step's attention over the
    attention layers' pages: the scopes ``kv_read`` and ``attn_full``.
    Bytes: the live positions' keys and values, each once a layer.
    Operations: per cached position and query head one product with the
    key and one with the value, over the head's own ``head_dim`` values
    (the kernel reads two heads a row of 128 lanes and multiplies both:
    not counted)."""
    _, attn, _, _ = layers(c)
    ops = attn * 4 * kv_tokens * c["num_attention_heads"] * head_dim(c)
    return ops, attn * kv_tokens * kv_row_bytes(c)


def prefill_attn_ops(c, prompt_tokens):
    """Operations of the attention layers over a prompt: per (query, key)
    pair of the causal triangle and query head one product with the key
    and one with the value. The flash kernel's scope in a prefill
    (``attn_full``)."""
    _, attn, _, _ = layers(c)
    pairs = prompt_tokens * (prompt_tokens + 1) // 2
    return attn * 4 * pairs * c["num_attention_heads"] * head_dim(c)


def lfm2_decode_bytes(c, n_streams, kv_tokens):
    """What one decode step must move: the operators, norms, routers,
    dense MLPs and the tied head once; of the routed experts those that
    some live stream chose (their expectation under even routing); each
    stream's embedding row; the attention layers' live pages; the live
    streams' windows in and out."""
    d = c["hidden_size"]
    conv, attn, dense, sparse = layers(c)
    width = router_width(c)
    params = (
        conv * conv_params(c) + attn * attention_params(c)
        + 2 * d * c["num_hidden_layers"]
        + dense * 3 * d * c["intermediate_size"]
        + sparse * (d * width + width
                    + expected_distinct_held(c, n_streams) * expert_params(c))
        + d + d * c["vocab_size"] + n_streams * d)
    windows = 2 * conv * n_streams * (c["conv_L_cache"] - 1) * d
    return (params + windows) * BF16 + attn * kv_tokens * kv_row_bytes(c)
