"""Bytes that the algorithms need, from shapes alone. ``c`` is a
configuration file's dict (the published ``config.json`` keys as run)."""


BF16 = 2  # bytes of a served weight and of a cached key or value


def mixtral_decode_bytes(c, n_tokens, distinct_experts_per_layer, kv_tokens):
    """What one decode step must read from memory: the non-expert weights
    once, each distinct routed expert once, the head, the embedding rows
    of the tokens, and the live keys and values."""
    d, f = c["hidden_size"], c["intermediate_size"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nq
    attn_w = d * hd * (2 * nq + 2 * nkv) + d * c["num_local_experts"]
    expert_w = 3 * d * f
    L = c["num_hidden_layers"]
    weights = (
        L * attn_w
        + sum(distinct_experts_per_layer) * expert_w
        + d * c["vocab_size"]
        + n_tokens * d
    )
    kv = 2 * L * kv_tokens * nkv * hd
    return (weights + kv) * BF16
