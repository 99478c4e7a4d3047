"""Attention ops.

Replaces the reference's SDPA FlashAttention-2 CUDA path (credited at
ref:README.md:5,46; invoked inside fms LLaMA's MultiHeadAttention). Two
implementations behind one dispatcher:

- "xla":    jnp einsum attention with fp32 softmax — always correct, used
            for CPU tests and as numerical ground truth. XLA fuses it but
            materializes the (B, N, S, S) score matrix.
- "pallas": blockwise MXU-tiled causal flash attention (ops/flash_attention.py)
            — O(S) memory, GQA-aware, written blockwise so a "context" mesh
            axis (ring attention) composes with it.

All functions take q:(B, S, Nq, H), k/v:(B, S, Nkv, H) with Nq % Nkv == 0
(GQA: 64/8 heads at 70B per ref:config_utils.py:26-34).

``chunk_attention`` is the same attention for a prompt taken a chunk at a
time (the hybrid's looped prefill): the queries of one chunk against a
cache that holds the chunks before it.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops import flash_attention as _fa
from fms_fsdp_tpu.ops.norms import rms_norm
from fms_fsdp_tpu.ops.rope import rotate_halves


def xla_attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None):
    """Reference einsum attention with fp32 softmax."""
    b, sq, nq, h = q.shape
    nkv = k.shape[2]
    scale = scale if scale is not None else h**-0.5
    group = nq // nkv
    # Grouped matmul: fold the GQA group into the query head dim so kv heads
    # are never materialized repeated.
    qg = q.reshape(b, sq, nkv, group, h)
    scores = (
        jnp.einsum(
            "bqkgh,bskh->bkgqs", qg, k, preferred_element_type=jnp.float32
        )
        * scale
    )
    if causal:
        sk = k.shape[1]
        # top-left alignment for sq != sk (query i attends keys <= i),
        # matching both the Pallas kernel and torch SDPA is_causal
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(b, sq, nq, h)


def configure_flash_variant(variant) -> None:
    """Apply TrainConfig.flash_kernel_variant before the step is traced
    (a trace-time env read was the old mechanism — cached jits would keep
    a stale variant, and the FWD-named env var silently governed the dq
    backward kernel too; see ops/flash_attention.py::set_kernel_variant).

    Applied unconditionally so every step build resolves the variant
    from its own config: None restores the import-time default
    (FLASH_KERNEL_VARIANT env, else auto) rather than inheriting a
    forcing left by an earlier build in the same process."""
    _fa.set_kernel_variant(variant)


def _flash_sharded(q, k, v, causal, mesh):
    """Flash under shard_map on a multi-device mesh: batch over the data
    axes, heads over the tensor axis (dropped when GQA q/kv head counts
    would pair up differently), sequence whole — the context-axis case
    routes to ring attention in the models before reaching here.

    Required, not an optimization: a Mosaic kernel cannot be partitioned
    by GSPMD, so an un-wrapped pallas_call on a >1-device mesh fails to
    compile with "Mosaic kernels cannot be automatically partitioned"
    (caught by scripts/aot_lower_kernels.py against a v5e topology — the
    CPU multichip dryruns resolve impl='auto' to XLA and never see it)."""
    from jax.sharding import PartitionSpec as P

    from fms_fsdp_tpu.ops.pallas_mode import interpret_default
    from fms_fsdp_tpu.parallel.mesh import AXIS_TENSOR, DATA_AXES
    from fms_fsdp_tpu.parallel.sharding import resolve_spec

    base = P(DATA_AXES, None, AXIS_TENSOR, None)
    spec_q = resolve_spec(base, q.shape, mesh)
    spec_kv = resolve_spec(base, k.shape, mesh)
    if spec_q[2] != spec_kv[2]:
        # q heads divide the tensor axis but kv heads don't (or vice
        # versa): a split would mispair GQA groups — replicate heads
        spec_q = P(spec_q[0], None, None, None)
        spec_kv = P(spec_kv[0], None, None, None)
    interpret = interpret_default()

    def body(ql, kl, vl):
        return _fa.flash_attention(
            ql, kl, vl, causal=causal, interpret=interpret
        )

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv),
        out_specs=spec_q,
        check_vma=False,
    )(q, k, v)


def _flash(q, k, v, causal, mesh):
    if mesh is not None and mesh.size > 1:
        return _flash_sharded(q, k, v, causal, mesh)
    from fms_fsdp_tpu.ops.pallas_mode import interpret_default

    return _fa.flash_attention(
        q, k, v, causal=causal, interpret=interpret_default()
    )


def attention(q, k, v, *, causal: bool = True, impl: str = "auto", mesh=None):
    """Dispatch: Pallas flash kernel on TPU for eligible shapes (head_dim a
    128-multiple, 256-aligned seq), XLA einsum otherwise. ``mesh`` must be
    passed whenever the computation is jitted over a >1-device mesh — the
    kernel then runs per-device under shard_map (see _flash_sharded)."""
    if impl == "pallas":
        if not _fa.supports(q.shape, k.shape):
            raise NotImplementedError(
                f"attention_kernel='pallas' requires a "
                f"128-multiple head_dim and 256-aligned sequence lengths; "
                f"got q{q.shape} k{k.shape}"
            )
        return _flash(q, k, v, causal, mesh)
    if (
        impl == "auto"
        and jax.default_backend() == "tpu"
        and _fa.supports(q.shape, k.shape)
    ):
        return _flash(q, k, v, causal, mesh)
    return xla_attention(q, k, v, causal=causal)


def chunk_attention(q, k_cache, v_cache, start, *, impl: str = "auto"):
    """Causal attention of the ``c`` queries at positions ``start`` to
    ``start + c`` against a cache written up to there.

    q (B, c, Nq, H); k_cache/v_cache (B, L, Nkv, H), L at least
    ``start + c``; ``start`` a multiple of ``c``, traced or not. The
    chunk's own block of keys goes under the causal mask, each earlier
    block is seen
    whole, and the partials merge exactly through their log-sum-exp, as
    ring attention merges a device's (ops/ring_attention.py). Each
    partial is the flash kernel where ``attention`` would take it and an
    einsum over one (c, c) score block elsewhere. The cache from
    ``start + c`` on is never read, so the cost follows the positions
    computed so far and not the cache's length. Returns (B, c, Nq, H)."""
    from fms_fsdp_tpu.ops.pallas_mode import interpret_default
    from fms_fsdp_tpu.ops.ring_attention import einsum_partial, merge_partial

    b, c, _, h = q.shape
    scale = h**-0.5
    flash = _fa.supports(
        q.shape, (b, c) + k_cache.shape[2:], forward_only=True
    ) and (
        impl == "pallas" or (impl == "auto" and jax.default_backend() == "tpu")
    )

    def partial_at(at, diag):
        k = lax.dynamic_slice_in_dim(k_cache, at, c, axis=1)
        v = lax.dynamic_slice_in_dim(v_cache, at, c, axis=1)
        if flash:
            return _fa.flash_attention(
                q, k, v, causal=diag, scale=scale, return_lse=True,
                interpret=interpret_default(),
            )
        return einsum_partial(q, k, v, diag, scale)

    o, lse = partial_at(start, True)
    o, _ = lax.fori_loop(
        0,
        start // c,
        lambda i, carry: merge_partial(carry, *partial_at(i * c, False)),
        (o.astype(jnp.float32), lse),
    )
    return o.astype(q.dtype)


def qkv_by_head(h, layer, cfg, positions, rotate: bool):
    """h (B, S, D) -> q (B, S, N, H), k and v (B, S, Nkv, H): projected
    through the layer's ``wq``, ``wk``, ``wv``, q and k normed by head
    (``q_norm``, ``k_norm``) and, where ``rotate``, turned at
    ``positions`` (B, S). ``cfg`` gives ``nheads``, ``kvheads``,
    ``head_dim``, ``norm_eps`` and ``rope_theta``."""
    B, S, _ = h.shape
    hd = cfg.head_dim
    with jax.named_scope("qkv"):
        # the products end here, as (B, S, heads * H): asked for them by
        # head, with the norm's sum over a head's values behind, the
        # chip's compiler lays W_q out by head first, a transposed copy of
        # it a layer and call (100 MB at K-EXAONE's published widths, in
        # every decode step; deviceless v5e compile, PERF.md PR 33)
        q, k, v = lax.optimization_barrier(
            (h @ layer["wq"], h @ layer["wk"], h @ layer["wv"])
        )
        q = q.reshape(B, S, cfg.nheads, hd)
        k = k.reshape(B, S, cfg.kvheads, hd)
        v = v.reshape(B, S, cfg.kvheads, hd)
    with jax.named_scope("qk_norm"):
        q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.norm_eps)
    if rotate:
        with jax.named_scope("rope"):
            q = rotate_halves(q, positions, cfg.rope_theta)
            k = rotate_halves(k, positions, cfg.rope_theta)
    return q, k, v


def band_mask(q_pos, k_pos, window):
    """(..., Sq, Sk) bool: key at ``k_pos`` is seen from ``q_pos``: not
    after it and, with a ``window``, fewer than that many behind it."""
    back = q_pos[..., :, None] - k_pos[..., None, :]
    seen = back >= 0
    return seen & (back < window) if window else seen


def masked_attention(q, k, v, mask, scale=None):
    """q (B, Sq, N, H) over k, v (B, Sk, Nkv, H) where ``mask`` (B or 1,
    Sq, Sk) -> (normalised output (B, Sq, N, H) fp32, log-sum-exp (B, Sq,
    N, 1) fp32): a partial that ``ops/ring_attention.py::merge_partial``
    joins with others. A row that sees nothing gives a log-sum-exp near
    ``NEG_INF`` and weighs nothing in a merge. ``scale``: the scores'
    factor where it is not ``H ** -0.5``."""
    B, Sq, N, H = q.shape
    nkv = k.shape[2]
    g = N // nkv
    s = jnp.einsum(
        "bqkgh,bskh->bkgqs", q.reshape(B, Sq, nkv, g, H), k,
        preferred_element_type=jnp.float32,
    ) * (H**-0.5 if scale is None else scale)
    s = jnp.where(mask[:, None, None], s, _fa.NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v)
    o = o.astype(jnp.float32).reshape(B, Sq, N, H) / jnp.moveaxis(
        l, 3, 1
    ).reshape(B, Sq, N, 1)
    lse = jnp.moveaxis(m + jnp.log(l), 3, 1).reshape(B, Sq, N, 1)
    return o, lse


# ---------------------------------------------------------------------------
# a window layer's prompt, a chunk at a time (kexaone, phi4flash)
# ---------------------------------------------------------------------------


@scoped("attn_window")
def window_chunk_attention(
    q, k, v, tail_k, tail_v, start, window, flash, scale=None
):
    """A window layer's attention over one chunk: the chunk's queries q
    (B, c, N, H) at positions ``start`` to ``start + c`` over the chunk's
    own keys and values k, v (B, c, Nkv, H) and the ``window`` positions
    before it, tail_k, tail_v (B, window, Nkv, H) (position ``start -
    window + u`` at ``u``; nothing real when ``start`` is 0). No earlier
    position is walked. Where ``flash``: the windowed flash kernel over
    the chunk's band, and the first ``window - 1`` queries' part of the
    tail as a small einsum merged in through the log-sum-exp; else one
    masked einsum over tail and chunk. ``scale``: the scores' factor where
    it is not ``H ** -0.5``. Returns (B, c, N, H)."""
    from fms_fsdp_tpu.ops.pallas_mode import interpret_default
    from fms_fsdp_tpu.ops.ring_attention import merge_partial

    B, c, N, H = q.shape
    q_pos = start + jnp.arange(c, dtype=jnp.int32)
    t_pos = start - window + jnp.arange(window, dtype=jnp.int32)
    if not flash:
        k_pos = jnp.concatenate([t_pos, q_pos])
        mask = band_mask(q_pos, k_pos, window) & (k_pos >= 0)[None, :]
        o, _ = masked_attention(
            q,
            jnp.concatenate([tail_k, k], axis=1),
            jnp.concatenate([tail_v, v], axis=1),
            mask[None],
            scale=scale,
        )
        return o.astype(q.dtype)
    o, lse = _fa.flash_attention(
        q, k, v, causal=True, window=window, return_lse=True,
        interpret=interpret_default(), scale=scale,
    )
    rows = min(window, c)  # the queries that can see into the tail
    mask = band_mask(q_pos[:rows], t_pos, window) & (t_pos >= 0)[None, :]
    o_t, lse_t = masked_attention(
        q[:, :rows], tail_k, tail_v, mask[None], scale=scale
    )
    head, _ = merge_partial(
        (o[:, :rows].astype(jnp.float32), lse[:, :rows]), o_t, lse_t
    )
    return jnp.concatenate([head.astype(q.dtype), o[:, rows:]], axis=1)


@scoped("win_write")
def as_ring(tail, lengths, window):
    """The last ``window`` positions of each row's prompt, in order
    (B, window, ...), as the ring a decode step reads: position ``t`` at
    ``t mod window``."""
    return jax.vmap(lambda t, p: jnp.roll(t, p % window, axis=0))(
        tail, lengths
    )


# ---------------------------------------------------------------------------
# differential attention (arXiv:2410.05258), as rows of two heads
# ---------------------------------------------------------------------------
#
# Heads in pairs of neighbours: query heads ``(2i, 2i + 1)`` are ``q1_i,
# q2_i``, key heads ``(2j, 2j + 1)`` are ``k1_j, k2_j``, and the pair's two
# value heads side by side are its one value head ``V_j``, ``2 H`` wide.
# ``o_i = RMSNorm((softmax(q1 k1^T) - lambda softmax(q2 k2^T)) V_j) * (1 -
# lambda_init)``. A pair of key heads side by side is a row of ``2 H``
# lanes (128 at heads of 64: a whole tile), which is how a cache holds
# them; a query head that stands in its own half of such a row, zeros in
# the other (``diff_rows``), scores against its own key head alone, and
# the value product gives it all ``2 H`` lanes of ``V_j``. So the two
# softmaxes are one grouped-query attention of ``N`` query heads over
# ``Nkv / 2`` heads of ``2 H`` (scores' factor ``H ** -0.5``), through
# whatever runs that: a masked einsum, the windowed flash kernel, the
# ragged paged kernel. ``diff_combine`` is what follows it.


def diff_rows(q):
    """q (..., N, H) -> (..., N, 2 H): head ``n``'s values in half ``n %
    2`` of its row, zeros in the other."""
    N, H = q.shape[-2:]
    half = jax.nn.one_hot(jnp.arange(N) % 2, 2, dtype=q.dtype)  # (N, 2)
    return (q[..., None, :] * half[:, :, None]).reshape(
        q.shape[:-1] + (2 * H,)
    )


def diff_lambda(layer, lambda_init: float):
    """``exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` in float32, from
    a layer's four learned vectors."""
    f32 = jnp.float32

    def dot(a, b):
        return jnp.sum(layer[a].astype(f32) * layer[b].astype(f32))

    return (
        jnp.exp(dot("lambda_q1", "lambda_k1"))
        - jnp.exp(dot("lambda_q2", "lambda_k2")) + lambda_init
    )


@scoped("diff_combine")
def diff_combine(o, layer, lambda_init: float, eps: float):
    """o (..., N, 2 H): query head ``2i``'s and ``2i + 1``'s attention
    outputs over ``V`` -> (..., N / 2 * 2 H) float32: their difference
    under ``lambda``, taken in float32, RMSNorm by head (``subln``) and
    the ``1 - lambda_init`` factor."""
    N, W = o.shape[-2:]
    o = o.astype(jnp.float32).reshape(o.shape[:-2] + (N // 2, 2, W))
    d = o[..., 0, :] - diff_lambda(layer, lambda_init) * o[..., 1, :]
    d = rms_norm(d, layer["subln"], eps) * (1.0 - lambda_init)
    return d.reshape(d.shape[:-2] + (N // 2 * W,))
