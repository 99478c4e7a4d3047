"""Bytes that the Jamba configuration's algorithms need, from shapes
alone. ``c`` is the configuration file's dict (the published
``config.json`` keys as run)."""

BF16, F32 = 2, 4


def _widths(c):
    d = c["hidden_size"]
    return d, c["mamba_expand"] * d, c["mamba_d_state"]


def mamba_layers(c):
    period, offset = c["attn_layer_period"], c["attn_layer_offset"]
    L = c["num_hidden_layers"]
    return L - sum(1 for i in range(L) if i % period == offset)


def param_count(c):
    """Every weight once; the head is the embedding."""
    d, di, N = _widths(c)
    R, K, f = c["mamba_dt_rank"], c["mamba_d_conv"], c["intermediate_size"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nq
    mamba = (d * 2 * di + di * (K + 1) + di * (R + 2 * N) + R + 2 * N
             + R * di + di + di * N + di + di * d)
    attn = d * hd * (2 * nq + 2 * nkv)
    shared = 3 * d * f + 2 * d
    L, Lm = c["num_hidden_layers"], mamba_layers(c)
    return (Lm * mamba + (L - Lm) * attn + L * shared + d
            + c["vocab_size"] * d)


def selective_scan_bytes(c, positions, calls=1):
    """What the selective scans of ``calls`` prefill programs must move
    for ``positions`` padded positions in all, over every Mamba layer: a
    position's ``u`` and ``dt`` in and ``y`` out per channel, its ``B``
    and ``C``, all float32 as the scan takes and gives them; once a call
    ``A`` and ``D`` in and the (d_inner, N) state in and out. The
    (positions, d_inner, N) history is not among them: the algorithm
    needs none of it."""
    _, di, N = _widths(c)
    per_position = (3 * di + 2 * N) * F32
    per_call = (3 * N * di + di) * F32
    return mamba_layers(c) * (positions * per_position + calls * per_call)


def state_bytes_per_stream(c):
    """One stream's slab: per Mamba layer the float32 state and the
    bfloat16 conv window."""
    _, di, N = _widths(c)
    return mamba_layers(c) * (
        N * di * F32 + (c["mamba_d_conv"] - 1) * di * BF16)


def jamba_decode_bytes(c, n_streams, kv_tokens):
    """What one decode step must move: every weight once (the tied
    embedding serves as the head), each live stream's slab in and out,
    the live keys and values of the attention layers."""
    d = c["hidden_size"]
    hd = d // c["num_attention_heads"]
    n_attn = c["num_hidden_layers"] - mamba_layers(c)
    kv = 2 * n_attn * kv_tokens * c["num_key_value_heads"] * hd * BF16
    return (param_count(c) * BF16
            + 2 * n_streams * state_bytes_per_stream(c) + kv)
