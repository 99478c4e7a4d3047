"""Bytes that the phi4flash configuration's decode step needs, from shapes
alone: what the published mathematics asks (every weight once, a stream's
window of each window layer, its slab read and written, the full layer's
live pages once a layer that reads them), never what a kernel happens to
touch. ``c`` is the configuration file's dict (the published
``config.json`` keys; nothing is cut)."""

from benchmark.reference.phi4flash import layer_kind, mamba_sizes, n_params

BF16 = 2
F32 = 4


def layers(c):
    """{kind: how many layers of it}."""
    kinds = [layer_kind(i, c) for i in range(c["num_hidden_layers"])]
    return {k: kinds.count(k) for k in set(kinds)}


def kv_row_bytes(c):
    """A position's keys and values in one attention layer's cache: the
    full layer's pages, a window layer's ring."""
    return 2 * c["num_key_value_heads"] * (
        c["hidden_size"] // c["num_attention_heads"]) * BF16


def shared_kv_readers(c):
    """The layers that read the full layer's pages: itself and every
    cross layer."""
    n = layers(c)
    return n["full"] + n["cross"]


def slab_bytes(c):
    """One stream's slabs: the float32 scan state and the conv's last
    inputs, every Mamba layer."""
    di, ns, _, k = mamba_sizes(c)
    return layers(c)["mamba"] * (di * ns * F32 + di * (k - 1) * BF16)


def ring_bytes(c, positions):
    """``positions`` ring entries (summed over streams), every window
    layer."""
    return layers(c)["window"] * positions * kv_row_bytes(c)


def weight_bytes(c):
    return n_params(c) * BF16


def resident_bytes(c, slots, pool_positions):
    """What a deployment of ``slots`` slots and a pool of
    ``pool_positions`` positions holds on the chip: the arrays a decode
    step takes (weights, rings, slabs, the full layer's pool)."""
    return (
        weight_bytes(c) + ring_bytes(c, slots * c["sliding_window"])
        + slots * slab_bytes(c) + pool_positions * kv_row_bytes(c))


def shared_kv_attn_bytes(c, kv_tokens):
    """The eight reads of the full layer's live pages in one decode step:
    ``kv_tokens`` cached positions (summed over the live streams), keys
    and values, once a layer that attends them."""
    return shared_kv_readers(c) * kv_tokens * kv_row_bytes(c)


def shared_kv_attn_ops(c, kv_tokens):
    """Per cached position, reading layer and query head one product with
    the key (its own 64 values) and one with the pair's value (128)."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    return shared_kv_readers(c) * kv_tokens * c["num_attention_heads"] * (
        2 * hd + 2 * 2 * hd)


def decode_bytes(c, n_streams, kv_tokens, ring_positions):
    """What one decode step must move: every weight once (the tied
    embedding once, as the head) and each stream's embedding row; the
    live streams' ring entries (``ring_positions``: the sum over streams
    of ``min(context, sliding_window)``), every window layer; their slabs
    in and out; the full layer's live pages (``kv_tokens`` positions)
    once a layer that reads them."""
    return (
        weight_bytes(c) + n_streams * c["hidden_size"] * BF16
        + ring_bytes(c, ring_positions) + 2 * n_streams * slab_bytes(c)
        + shared_kv_attn_bytes(c, kv_tokens))
