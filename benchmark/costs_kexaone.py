"""Operations and bytes that the K-EXAONE configuration's algorithms
need, from shapes alone: what the published mathematics asks (the band
of a window layer, the live pages of a full layer, each weight once),
never what a kernel happens to touch. ``c`` is the configuration file's
dict (the published ``config.json`` keys as run: ``num_experts`` the
experts held, ``published.num_experts`` the router's width,
``layer_types`` and ``mlp_layer_types`` the layers kept)."""

from benchmark.costs_sarvam import (  # the same router keys
    BF16,
    expected_distinct_held,
    router_width,
)


def layers(c):
    """(window layers, full layers, dense layers, sparse layers)."""
    n = c["num_hidden_layers"]
    window = sum(t == "sliding_attention" for t in c["layer_types"][:n])
    sparse = sum(t == "sparse" for t in c["mlp_layer_types"][:n])
    return window, n - window, n - sparse, sparse


def kv_row_bytes(c):
    """A position's key and value in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BF16


def attention_params(c):
    d, H = c["hidden_size"], c["head_dim"]
    N, Nkv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * N * H + 2 * d * Nkv * H + 2 * H + 2 * d


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def full_decode_attn_cost(c, kv_tokens):
    """(operations, bytes) of one decode step's attention over the full
    layers' pages: the scopes ``kv_read`` and ``attn_full``. Bytes: the
    live positions' keys and values, each once a full layer. Operations:
    per cached position and query head one product with the key and one
    with the value."""
    _, full, _, _ = layers(c)
    ops = full * 4 * kv_tokens * c["num_attention_heads"] * c["head_dim"]
    return ops, full * kv_tokens * kv_row_bytes(c)


def kexaone_decode_bytes(c, n_streams, kv_tokens):
    """What one decode step must move: attention, router, shared expert,
    dense MLP, norms and the head once; of the held routed experts those
    that some live stream chose (their expectation); each stream's
    embedding row; the full layers' live pages; the live streams' rings
    (a stream's ring holds ``min(its positions, sliding_window)``, taken
    as the window: a stream is past it after its first 128 positions)."""
    d = c["hidden_size"]
    window, full, dense, sparse = layers(c)
    published = router_width(c)
    params = (
        c["num_hidden_layers"] * attention_params(c)
        + dense * 3 * d * c["intermediate_size"]
        + sparse * (d * published + published
                    + c.get("num_shared_experts", 0) * expert_params(c)
                    + expected_distinct_held(c, n_streams) * expert_params(c))
        + d + d * c["vocab_size"] + n_streams * d)
    rings = window * n_streams * c["sliding_window"] * kv_row_bytes(c)
    return params * BF16 + full * kv_tokens * kv_row_bytes(c) + rings


def band_pairs(n, window):
    """(query, key) pairs of a causal band over ``n`` positions from 0 on:
    position ``t`` sees ``min(window, t + 1)``."""
    if n <= window:
        return n * (n + 1) // 2
    return window * n - window * (window - 1) // 2


def prefill_window_attn_ops(c, prompt_tokens):
    """Operations of the window layers' attention over a prompt: per
    (query, key) pair of the band and query head one product with the key
    and one with the value. The scope ``attn_window``."""
    window, _, _, _ = layers(c)
    return (window * 4 * band_pairs(prompt_tokens, c["sliding_window"])
            * c["num_attention_heads"] * c["head_dim"])
