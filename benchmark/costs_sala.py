"""Operations and bytes that the MiniCPM-SALA configuration's algorithms
need, from shapes alone: what the published mathematics asks (the
**chosen** positions of a sparse layer and the compressed keys its choice
scores, the state of a lightning layer, each weight once), never what a
kernel happens to touch. ``c`` is the configuration file's dict (the
published ``config.json`` keys as run: ``num_hidden_layers`` and
``mixer_types`` the layers kept, ``sparse_config`` the sizes of the
choice)."""

BF16 = 2
F32 = 4
LIN_CHUNK = 256  # positions of one masked product of the chunked form


def layers(c):
    """(sparse layers, lightning layers) kept."""
    kinds = c["mixer_types"][: c["num_hidden_layers"]]
    sparse = sum(k == "minicpm4" for k in kinds)
    return sparse, len(kinds) - sparse


def mlp_params(c):
    return 3 * c["hidden_size"] * c["intermediate_size"]


def sparse_layer_params(c):
    """q, o and the gate; k and v of the kv heads; the MLP; the norms."""
    d, H = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"] * H, c["num_key_value_heads"] * H
    return 3 * d * heads + 2 * d * kv + mlp_params(c) + 2 * H + 2 * d


def lightning_layer_params(c):
    """q, k, v, o and the gate; the MLP; the norms (by head, the output's,
    the two of the block)."""
    d, H = c["hidden_size"], c["lightning_head_dim"]
    heads = c["lightning_nh"] * H
    return 5 * d * heads + mlp_params(c) + 2 * H + heads + 2 * d


def held_params(c):
    """Every parameter the configuration holds: the layers kept, the
    final norm, the embedding and the untied head whole."""
    sparse, lightning = layers(c)
    return (sparse * sparse_layer_params(c)
            + lightning * lightning_layer_params(c)
            + c["hidden_size"] + 2 * c["vocab_size"] * c["hidden_size"])


# -- the choice ---------------------------------------------------------------


def index_rows(c, context):
    """Compressed keys a query with ``context`` positions up to its own
    scores (whole windows only), one kv head's."""
    sc = c["sparse_config"]
    return max(0, (context - sc["kernel_size"]) // sc["kernel_stride"] + 1)


def attended_positions(c, context):
    """Positions a query with ``context`` positions up to its own (``t +
    1``) attends in a sparse layer: all of them while ``t + 1 <=
    dense_len``, then the positions up to its own of ``topk`` blocks, its
    own block the last."""
    sc = c["sparse_config"]
    if context <= sc["dense_len"]:
        return context
    bs, t = sc["block_size"], context - 1
    return (min(t // bs + 1, sc["topk"]) - 1) * bs + t % bs + 1


def chooses(c, context):
    return context > c["sparse_config"]["dense_len"]


def kv_row_bytes(c):
    """A position's key and value in one sparse layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BF16


def index_row_bytes(c):
    """One compressed key of every kv head of one sparse layer."""
    return c["num_key_value_heads"] * c["head_dim"] * BF16


# -- decode -------------------------------------------------------------------


def sparse_decode_attn_cost(c, attended, scored):
    """(operations, bytes) of one decode step's choice and attention in
    the sparse layers: the scopes ``sparse_select`` and ``sparse_attn``.
    ``attended``: the live streams' attended positions together
    (``attended_positions``), ``scored``: the compressed keys those of
    them that choose score (``index_rows``). Bytes: those keys and the
    attended positions' keys and values, each once a sparse layer.
    Operations: per compressed key and query head one product, per
    attended position and query head one with the key and one with the
    value."""
    sparse, _ = layers(c)
    NH = c["num_attention_heads"] * c["head_dim"]
    ops = sparse * (2 * scored + 4 * attended) * NH
    byts = sparse * (scored * index_row_bytes(c) + attended * kv_row_bytes(c))
    return ops, byts


def lightning_state_bytes(c):
    """One stream's float32 states, the lightning layers together."""
    _, lightning = layers(c)
    return lightning * c["lightning_nh"] * c["lightning_head_dim"] ** 2 * F32


def sala_decode_bytes(c, n_streams, attended, scored):
    """What one decode step must move: every weight once (of the
    embedding the live streams' rows), the attended positions' keys and
    values and the scored compressed keys, the live streams' lightning
    states read and written."""
    d = c["hidden_size"]
    weights = held_params(c) - c["vocab_size"] * d + n_streams * d
    _, attn = sparse_decode_attn_cost(c, attended, scored)
    return weights * BF16 + attn + 2 * n_streams * lightning_state_bytes(c)


# -- prefill ------------------------------------------------------------------


def sparse_prefill_attn_cost(c, positions, chunk):
    """(operations, bytes) of the sparse layers' choice and attention
    over the first ``positions`` positions of a sequence, computed
    ``chunk`` at a time: the scopes ``sparse_select``, ``sparse_attn`` and
    ``attn``. Operations: per position its attended positions' two
    products a query head and, where it chooses, one product a compressed
    key. Bytes: a chunk's operands once, the keys and values up to its
    end and the compressed keys it scores, its queries and its output."""
    sparse, _ = layers(c)
    NH = c["num_attention_heads"] * c["head_dim"]
    attended = scored = 0
    for t in range(positions):
        attended += attended_positions(c, t + 1)
        if chooses(c, t + 1):
            scored += index_rows(c, t + 1)
    byts = 0
    for end in range(chunk, positions + chunk, chunk):
        end = min(end, positions)
        byts += end * kv_row_bytes(c) + 2 * min(chunk, end) * NH * BF16
        if chooses(c, end):
            byts += index_rows(c, end) * index_row_bytes(c)
    return sparse * (4 * attended + 2 * scored) * NH, sparse * byts


def lin_scan_cost(c, positions, chunk=LIN_CHUNK):
    """(operations, bytes) of the lightning layers' chunked form over
    ``positions`` positions: the scope ``lin_scan``. Per head, inside a
    chunk of ``chunk`` positions every causal pair once (a product with
    the key, one with the value), and per position the state read
    (``q^T S``) and written (``k v^T``). Bytes: q, k, v in and the output
    out, the state read and written a chunk."""
    _, lightning = layers(c)
    n, H = c["lightning_nh"], c["lightning_head_dim"]
    chunks = -(-positions // chunk)
    pairs = chunks * chunk * (chunk + 1) // 2
    ops = lightning * n * (4 * H * pairs + 4 * H * H * positions)
    byts = lightning * n * (
        positions * H * (3 * BF16 + F32) + chunks * 2 * H * H * F32)
    return ops, byts
