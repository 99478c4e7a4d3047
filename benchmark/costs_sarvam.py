"""Operations and bytes that the Sarvam configuration's algorithms need,
from shapes alone. ``c`` is the configuration file's dict (the published
``config.json`` keys as run: ``num_experts`` the experts held,
``published.num_experts`` the router's width)."""

BF16 = 2


def _w(c):
    return (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"])


def router_width(c):
    return (c.get("published") or {}).get("num_experts", c["num_experts"])


def layers(c):
    """(leading dense layers, MoE layers)."""
    dense = c.get("first_k_dense_replace", 0)
    return dense, c["num_hidden_layers"] - dense


def latent_bytes_per_token(c):
    """What one position leaves in the cache, over all layers."""
    return c["num_hidden_layers"] * (
        c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BF16


def attention_params(c):
    d, N, r, nope, rope, v = _w(c)
    return (d * N * (nope + rope) + d * (r + rope) + r
            + r * N * (nope + v) + N * v * d + 2 * d)


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def mla_decode_attn_cost(c, n_streams, kv_tokens):
    """(operations, bytes) of one decode step's attention over the
    latent, all layers: the scopes ``latent_gather``, ``mla_absorb`` and
    ``attn``. Bytes: the live latent pages and ``W_kvb``, each once.
    Operations: each stream's queries through ``W_kvb^K`` and outputs
    through ``W_kvb^V``, and per cached position and head one product
    over the latent's full width and one over its value part."""
    _, N, r, nope, rope, v = _w(c)
    L = c["num_hidden_layers"]
    ops = L * (2 * n_streams * N * r * (nope + v)
               + 2 * kv_tokens * N * ((r + rope) + r))
    byts = L * (kv_tokens * (r + rope) + r * N * (nope + v)) * BF16
    return ops, byts


def expected_distinct_held(c, n_streams):
    """Of the experts held, how many at least one of ``n_streams`` tokens
    chooses, in expectation under even routing."""
    miss = (1.0 - c["num_experts_per_tok"] / router_width(c)) ** n_streams
    return c["num_experts"] * (1.0 - miss)


def sarvam_decode_bytes(c, n_streams, kv_tokens):
    """What one decode step must move: attention, router, shared expert,
    dense MLP, norms and the head once; of the held routed experts those
    that some live stream chose (their expectation); each stream's
    embedding row; the live latent pages."""
    d = c["hidden_size"]
    dense, moe = layers(c)
    published = router_width(c)
    params = (
        c["num_hidden_layers"] * attention_params(c)
        + dense * 3 * d * c["intermediate_size"]
        + moe * (d * published + published
                 + c.get("num_shared_experts", 0) * expert_params(c)
                 + expected_distinct_held(c, n_streams) * expert_params(c))
        + d + d * c["vocab_size"] + n_streams * d)
    return params * BF16 + kv_tokens * latent_bytes_per_token(c)


def moe_grouped_cost(c, pairs_held, chunks):
    """(operations, bytes) of the prefill's grouped product over the held
    experts: the scopes ``moe_group``, ``moe_experts`` and
    ``moe_combine``. ``pairs_held``: the (token, choice) pairs that
    landed on held experts, counted by the program over all MoE layers;
    ``chunks``: trips of the prefill's loop, each of which reads every
    held expert of every MoE layer once. A pair's row goes in and its
    result comes out at the model's width."""
    d = c["hidden_size"]
    _, moe = layers(c)
    ops = 2 * pairs_held * expert_params(c)
    byts = (chunks * moe * c["num_experts"] * expert_params(c)
            + 2 * pairs_held * d) * BF16
    return ops, byts
