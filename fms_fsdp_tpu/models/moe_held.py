"""The held share of a sigmoid-routed expert layer: what a chip of an
expert-parallel deployment computes of a layer, without the exchange.

``s = sigmoid(W_g h)`` in float32 over all ``num_experts``; the ``top_k``
largest of ``s + b`` are chosen (the bias chooses and does not weigh);
``w_i = routed_scaling_factor * s_i / sum of the chosen s`` (with a
config's ``router_sum_eps`` under the sum, where it has one); the layer
adds ``sum over the chosen experts held here of w_i E_i(h)`` and, beside
it, the shared expert's ``S(h)`` (whole on every chip). One copy for the
families that route this way (models/sarvam.py, models/kexaone.py);
under it models/mixtral.py's ``_expert_mix``, ``_all_experts_swiglu`` and
``routed_moe_form`` over the experts held.

A config gives four things: ``top_k``, ``routed_scaling_factor``,
``num_experts`` (the router's width) and ``held`` = (first id, count) of
the routed experts this program holds. A layer is a dict of leaves:
``gate (D, num_experts)``, ``gate_bias (num_experts,)``, ``w1``/``w3``
``(held, D, h)``, ``w2 (held, h, D)`` and, where there is a shared
expert, ``shared_w1``/``shared_w3``/``shared_w2``.

Three forms of the held part: over a decode step's rows the two of
models/mixtral.py (``routed_moe_form``: every held expert streamed once,
or one product a routed pair); over a prompt's chunk the (token, choice)
pairs that land on held experts sorted by expert and multiplied group by
group (``_moe_grouped``, through ops/grouped_matmul.py), none dropped,
and only their rows moved: a slab of the sorted pairs at a time
(``grouped_slab``), one trip unless the routing is skewed onto the
experts held.
"""

import itertools

import jax
import jax.numpy as jnp
from jax import lax

from fms_fsdp_tpu.models.mixtral import (
    _all_experts_swiglu,
    _expert_mix,
    routed_moe_form,
)
from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops.grouped_matmul import (
    group_row_tiles,
    grouped_matmul,
    row_tile,
)


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


@scoped("moe_router")
def _router(h, layer, cfg):
    """h (..., D) -> (chosen ids (..., K) int over all ``num_experts``,
    their weights (..., K) fp32 that sum to ``routed_scaling_factor``).
    Scores are sigmoids in fp32; the bias is added for the choice
    alone."""
    scores = jax.nn.sigmoid((h @ layer["gate"]).astype(jnp.float32))
    _, idx = lax.top_k(
        scores + layer["gate_bias"].astype(jnp.float32), cfg.top_k
    )
    w = jnp.take_along_axis(scores, idx, axis=-1)
    scaled = cfg.routed_scaling_factor * w
    total = jnp.sum(w, axis=-1, keepdims=True)
    # a family that normalises over ``sum + eps`` says so in its config
    # (models/configs.py::Lfm2MoeConfig); the others' programs have no add
    eps = getattr(cfg, "router_sum_eps", 0.0)
    if eps:
        total = total + eps
    return idx, scaled / total


@scoped("moe_shared")
def _shared(h, layer):
    if "shared_w1" not in layer:
        return jnp.zeros_like(h)
    return _swiglu(h, layer["shared_w1"], layer["shared_w3"], layer["shared_w2"])


def _held_mixture(h, layer, cfg, idx, w):
    """Every held expert over every row of h (B, S, D), mixed by the
    rows' weights for them (exactly zero where a row chose another)."""
    first, held = cfg.held
    with jax.named_scope("moe_experts"):
        mix = _expert_mix(idx, w, held, first).astype(h.dtype)
        out = _all_experts_swiglu(h, layer)  # (B, S, held, D)
    with jax.named_scope("moe_combine"):
        return jnp.einsum("bse,bsed->bsd", mix, out)


def _moe_dense_held(h, layer, cfg):
    """Held experts' part of the mixture, every held expert over every
    row (the parity form). h (B, S, D)."""
    return _held_mixture(h, layer, cfg, *_router(h, layer, cfg))


def _moe_token(h, layer, cfg, moe_impl: str, routed=None):
    """Held experts' part of the mixture over a decode step's rows.
    h (B, m, D) post-ffn_norm. The two routed forms of
    models/mixtral.py over the experts held. ``routed``: the rows'
    ``_router`` answer where the caller has asked for it already (it
    counts the experts chosen)."""
    assert moe_impl in ("dense", "routed"), (
        f"unknown decode moe_impl {moe_impl!r}"
    )
    idx, w = routed or _router(h, layer, cfg)
    if moe_impl == "dense":
        return _held_mixture(h, layer, cfg, idx, w)
    B, m, K = idx.shape
    first, held = cfg.held
    if routed_moe_form(B * m * K, held) == "all_experts":
        return _held_mixture(h, layer, cfg, idx, w)
    rows = h.reshape(B * m, -1)
    local = idx.reshape(B * m, K) - first
    here = (local >= 0) & (local < held)
    out = []
    for r, k in itertools.product(range(B * m), range(K)):
        with jax.named_scope("moe_experts"):
            # a pair on an expert that is not held reads a held one and
            # is weighed by exactly zero below
            w1, w3, w2 = (
                lax.dynamic_index_in_dim(
                    layer[name], jnp.clip(local[r, k], 0, held - 1), 0,
                    keepdims=False,
                )
                for name in ("w1", "w3", "w2")
            )
            out.append(_swiglu(rows[r], w1, w3, w2))
    with jax.named_scope("moe_combine"):
        out = jnp.stack(out).reshape(B, m, K, -1)
        wt = jnp.where(here.reshape(B, m, K), w, 0.0).astype(h.dtype)
        return jnp.einsum("bmkd,bmk->bmd", out, wt)


# a slab is a whole number of these rows (and of any row tile up to them)
_ROW_TILE = 256


def _gmm(x, stacks, sizes, l):
    """Rows of ``x`` (M, k), sorted by group, times their group's matrix
    in layer ``l`` of a stack (L, G, k, n), or of two (gate and up:
    ``silu(x w1) * (x w3)`` in one pass over the rows):
    ops/grouped_matmul.py. The kernel is handed the whole stacks and
    finds layer ``l`` by its block index, so no layer's slice of them is
    ever copied out (a sliced operand is: 1.6 GB a layer and chunk at the
    published widths). Its grid follows ``sum(sizes)``: rows past that
    are not visited and hold whatever the buffer held, NaN included; the
    caller zeroes them before any product reads them."""
    # timed on the chip at the published widths (PERF.md section 6, PRs 43-44;
    # ms: up / down product alone, then gate, up and down together):
    #   6144 rows, 4041 landed in 32 groups, 4096 x 2048 (sarvam)
    #     megablox (256, 2048, 1024)      1.44 / 1.21   3.92-3.97
    #     megablox (256, k whole, 512)    1.22 / 1.21
    #     this kernel's tiles through the grid's own pipeline (a block
    #     asked for one meeting ahead)    1.14 / 1.17   3.31
    #     a block asked for a group ahead, rows 64 / 128 / 192 / 256
    #       gate and up in one pass       1.93 / 1.58 / 1.97 / 2.17
    #       down                          1.03 / 0.85 / 1.02 / 1.12
    #     the schedule (128, 1024 | 4096) 1.61 / 0.85   2.38
    #   3072 rows, 2048 landed in 16 groups, 6144 x 2048 (k-exaone)
    #     megablox (256, 2048, 1024)      1.03 / 0.91   2.87-2.95
    #     the schedule (128, 512 | 3072)  1.31 / 0.68   1.92
    #   8192 | 2048 rows, all landed in 64 groups, 2048 x 1536 (lfm2)
    #     megablox (256, 2048, 768)       0.94 | 0.72   2.68 | 2.01
    #     rows 16 / 32 / 64 through the grid's pipeline, 2048 rows
    #                                     0.76 / 0.69 / 0.66
    #     the schedule (128, 1536 | 2048) 0.68 | 0.62   1.82 | 1.73
    # a meeting takes the matrix unit as long for 16 rows as for 128 and
    # twice as long for 256, so tiles shorter than the groups only add
    # meetings; the bytes alone need 2.15 / 1.61 / 1.48 ms
    return grouped_matmul(x, stacks, sizes, l)


def grouped_slab(cfg, pairs: int) -> int:
    """Rows one trip of ``_moe_grouped``'s loop takes of a chunk's
    ``pairs`` (token, choice) pairs: one and a half times the share that
    lands on the experts held when the router is balanced, in whole row
    tiles, at most all of them (a layer that holds every expert takes
    them in one trip)."""
    want = -(-3 * pairs * cfg.held[1] // (2 * cfg.num_experts))
    return min(pairs, -(-want // _ROW_TILE) * _ROW_TILE)


def grouped_tile_rows(cfg, pairs: int) -> int:
    """Rows of the row tiles a grouped product takes a slab of a chunk's
    ``pairs`` in (ops/grouped_matmul.py::row_tile)."""
    return row_tile(grouped_slab(cfg, pairs))


def _moe_grouped(h, layer, cfg, experts=None, l=0):
    """Held experts' part of the mixture over a chunk's rows h (T, D):
    the (token, choice) pairs on held experts sorted by expert, each
    group through its expert, no pair dropped. ``experts``: the MoE
    layers' stacked ``w1``/``w3``/``w2`` (L, held, ...) with ``l`` the
    layer's index in them (``layer``'s own, as a stack of one, when
    None).

    Only the pairs that landed here are moved: the sorted pairs are
    taken ``grouped_slab`` rows at a time, in a loop whose trip count
    ``ceil(landed / slab)`` is read on the device (one trip unless the
    routing is skewed onto the experts held, none when nothing landed).
    A trip gathers its rows of ``h``, multiplies them group by group
    with the slab's own group sizes, and adds each row's weighted result
    to its token: one product of a (T, slab) matrix that holds each
    pair's weight in its token's row with the slab's results, on the
    MXU, products of the operands as they are summed in float32.

    Returns (y (T, D), the number of pairs that landed on held experts,
    the loop's trips, and the (group, row tile) meetings that a grouped
    product of those trips ran, counted from each slab's group sizes as
    the kernel's grid counts them: times the tile's rows
    (``grouped_tile_rows``) the rows a product multiplied, of which the
    pairs landed are the ones kept)."""
    if experts is None:
        experts = {name: layer[name][None] for name in ("w1", "w3", "w2")}
    idx, w = _router(h, layer, cfg)  # (T, K)
    T, K = idx.shape
    first, held = cfg.held
    slab = grouped_slab(cfg, T * K)
    tm = grouped_tile_rows(cfg, T * K)
    with jax.named_scope("moe_group"):
        local = idx.reshape(T * K) - first
        here = (local >= 0) & (local < held)
        # pairs on experts that are not held sort behind every group
        key = jnp.where(here, local, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        # whole slabs, so that the last one's slice starts where it says
        order = jnp.pad(order, (0, -(T * K) % slab))
        sizes = jnp.sum(
            key[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32
        )
        ends = jnp.cumsum(sizes)
        starts, n_here = ends - sizes, ends[-1]
        trips = (n_here + slab - 1) // slab
        weights = w.reshape(T * K)

    def trip(s, carry):
        y, met = carry
        lo = s * slab
        with jax.named_scope("moe_group"):
            pairs = lax.dynamic_slice_in_dim(order, lo, slab)
            # of each group, the rows that lie in this slab
            mine = jnp.clip(
                jnp.minimum(ends, lo + slab) - jnp.maximum(starts, lo), 0
            )
            met = met + jnp.sum(group_row_tiles(mine, tm))
            valid = jnp.arange(slab) < n_here - lo
            token = pairs // K
            xs = h[token]
        with jax.named_scope("moe_experts"):
            hid = _gmm(xs, (experts["w1"], experts["w3"]), mine, l)
            out = _gmm(hid, (experts["w2"],), mine, l)
        with jax.named_scope("moe_combine"):
            # timed on the chip at the published widths, 2048 tokens, a
            # slab of 3072 x 6144 / 6144 x 4096 (PERF.md, PR 35): this
            # product 0.45 / 0.60 ms, a float32 scatter-add by token
            # 1.10 / 1.19, a Pallas kernel adding rows by a prefetched
            # table 0.32 / 0.38 alone but 2% of the layer when in it;
            # un-sorting all 16384 rows for an einsum took 2.46 / 1.66
            out = jnp.where(valid[:, None], out, jnp.zeros_like(out))
            # a row past the valid count is zero now: its weight is moot
            wt = weights[pairs].astype(h.dtype)
            place = jnp.where(
                token[None, :] == jnp.arange(T)[:, None], wt[None, :],
                jnp.zeros((), h.dtype),
            )
            y = y + jnp.dot(place, out, preferred_element_type=jnp.float32)
        return y, met

    y, met = lax.fori_loop(
        0, trips, trip,
        (jnp.zeros((T, h.shape[-1]), jnp.float32), jnp.zeros((), jnp.int32)),
    )
    return y.astype(h.dtype), n_here, trips, met
