"""Ragged paged-attention decode (serving path).

The serving engine (fms_fsdp_tpu/serve/) stores the kv cache in
fixed-size *pages* — (page_size, Nkv, H) tiles scattered through a
shared pool — with a per-sequence page table mapping logical cache
positions to pool pages. Decode-time attention then has two jobs the
training kernels never had: gather k/v *through the page table*, and
handle *ragged* sequence lengths (every batch row sits at its own
position) in one batched call. This module follows *Ragged Paged
Attention: A High-Performance and Flexible LLM Inference Kernel for TPU*
(PAPERS.md): one kernel invocation serves the whole mixed-length decode
batch; per-row length masking replaces per-row dispatch.

Two implementations, one contract:

- ``paged_attention_reference``: pure JAX — gather the pages back into a
  contiguous (B, S, Nkv, H) cache and run :func:`gqa_attend`, the exact
  attend math the dense decode path (models/generation.py::decode_chunk)
  uses. Because the gathered array is bit-identical to the dense cache
  (the serve allocator points unwritten table slots at a pristine zero
  page), the reference path is **bit-identical** to dense decode — the
  correctness anchor tier-1 pins on CPU.
- ``_paged_decode_kernel``: the Pallas kernel — grid (batch, kv block);
  the page table rides as scalar prefetch so each of a cell's pages is
  DMA'd straight from its pool page by a BlockSpec index map (no
  contiguous copy ever materializes), with the FlashAttention-2 online
  softmax accumulated in VMEM scratch across the block walk. A cell
  holds every kv head of its pages — a block may not take one head out
  of the (Nkv, H) minor tile on a TPU — and attends all query heads
  against them in one pass, masking other heads' columns. Blocks past a
  row's length run no compute (pl.when) and fetch no data (the index
  map clamps onto the last live page — a repeat fetch the pipeline
  elides), which is what makes the ragged batch one kernel call instead
  of B.

Tile resolution (page_size at allocator build, block_kv per call) goes
through the tuning table (fms_fsdp_tpu/tune/lookup.py::
resolve_paged_decode) like every other kernel. ``block_kv`` may be any
multiple of ``page_size`` (the cell fetches ``block_kv // page_size``
pool pages), and int8/fp8-quantized pools are read natively — the
per-page scale blocks are fetched beside the pages and applied to the
scores and probabilities in VMEM, so quantized serving does not fall
back to the reference gather.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops.pallas_mode import interpret_default

NEG_INF = -1e30
LOG2E = 1.4426950408889634  # log2(e)


# ---------------------------------------------------------------------------
# shared dense attend math (also the body of decode_chunk's attention)
# ---------------------------------------------------------------------------


@scoped("attn")
def gqa_attend(q, k_cache, v_cache, positions):
    """Grouped-query attention of m query positions against a cache.

    q (B, m, Nq, H); k_cache/v_cache (B, S, Nkv, H); positions (B, m)
    int32 — query i of row b sits at positions[b, i] and sees cache
    entries <= it. Returns (B, m, Nq*H).

    This is the exact attend the dense decode path runs
    (models/generation.py::decode_chunk imports it); the paged reference
    below calls it on the gathered cache, which is what makes paged
    decode bit-identical to dense decode.
    """
    b, m, nq, hd = q.shape
    nkv = k_cache.shape[2]
    group = nq // nkv
    s = k_cache.shape[1]
    qg = q.reshape(b, m, nkv, group, hd)
    scores = jnp.einsum(
        "bmkgh,bskh->bkgms", qg, k_cache, preferred_element_type=jnp.float32
    ) * (hd**-0.5)
    idx = jnp.arange(s)[None, None, None, None, :]
    qpos = positions[:, None, None, :, None]
    scores = jnp.where(idx <= qpos, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgms,bskh->bmkgh", probs, v_cache)
    return out.reshape(b, m, nq * hd)


# ---------------------------------------------------------------------------
# reference (gather) implementation
# ---------------------------------------------------------------------------


@scoped("kv_gather")
def gather_pages(pages, page_table):
    """pages (P, ps, Nkv, H) + page_table (B, maxp) -> (B, maxp*ps, Nkv, H).

    The contiguous per-sequence view of a paged pool. Table slots past a
    sequence's allocation point at the reserved zero page, so the
    gathered array equals the dense cache (zeros beyond the written
    prefix) bit-for-bit.
    """
    b, maxp = page_table.shape
    ps = pages.shape[1]
    g = pages[page_table]  # (B, maxp, ps, Nkv, H)
    return g.reshape(b, maxp * ps, *pages.shape[2:])


def paged_attention_reference(q, k_pages, v_pages, page_table, seq_lens):
    """One ragged decode position per row, via gather + dense attend.

    q (B, Nq, H); k_pages/v_pages (P, ps, Nkv, H); page_table (B, maxp)
    int32; seq_lens (B,) int32 = the position each row's query sits at
    (it sees cache entries <= seq_lens[b], i.e. seq_lens[b]+1 tokens —
    the freshly written current token included). Returns (B, Nq*H).
    """
    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    return gqa_attend(q[:, None], k, v, seq_lens[:, None])[:, 0]


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _paged_decode_kernel(
    lens_ref,  # scalar prefetch: (B,) int32 query positions
    table_ref,  # scalar prefetch: (B, maxp) int32 page table
    q_ref,  # (1, Nq, H)
    *rest,  # ppb k pages, ppb v pages (, ppb k scales, ppb v scales); o; scratch
    page_size,
    pages_per_block,
    nkv,
    scale,
    quantized,
):
    """One (batch row, kv block) grid cell: ``pages_per_block`` pool pages,
    every kv head at once.

    A page arrives as its (page_size * Nkv, H) row-major view — row
    ``t * Nkv + h`` is token t of kv head h — because a cell may not take
    one head out of the (Nkv, H) minor tile (Mosaic refuses the slice).
    All Nq query heads multiply against all of those rows in one MXU pass
    and the columns belonging to another kv head are masked with the
    out-of-range positions, so each query row's softmax runs over exactly
    its own head's tokens. No per-head loop, no strided load.
    """
    ppb = pages_per_block
    k_refs, v_refs = rest[:ppb], rest[ppb : 2 * ppb]
    rest = rest[2 * ppb :]
    if quantized:
        ks_refs, vs_refs = rest[:ppb], rest[ppb : 2 * ppb]
        rest = rest[2 * ppb :]
    o_ref, acc_ref, m_ref, l_ref = rest

    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = lens_ref[b]  # query position; attends to cache idx <= pos
    block = ppb * page_size
    nq = q_ref.shape[1]
    group = nq // nkv
    cols = page_size * nkv

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # blocks holding no position <= pos run no compute (and fetched no
    # data: the index maps clamped them onto the last live page)
    @pl.when(j * block <= pos)
    def _():
        # scale + change of base folded into q; exp2 replaces exp in the
        # online softmax (same trick as ops/flash_attention.py)
        q = (q_ref[0] * (scale * LOG2E)).astype(q_ref.dtype)  # (Nq, H)
        col = jax.lax.broadcasted_iota(jnp.int32, (nq, cols), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (nq, cols), 0)
        tok = col // nkv
        first_q = (col % nkv) * group  # first query head of the column's kv head
        own_head = (row >= first_q) & (row < first_q + group)
        m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
        for i in range(ppb):
            k = k_refs[i][0]  # (ps*Nkv, H), storage dtype
            v = v_refs[i][0]
            if quantized:
                # int8/fp8 values are exact in the compute dtype; the
                # per-row absmax scales fold into the score columns and
                # the probabilities instead of a (ps*Nkv, H) dequantize
                k = k.astype(q.dtype)
                v = v.astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (Nq, ps*Nkv), base-2 domain
            if quantized:
                s = s * ks_refs[i][0]
            kpos = (j * ppb + i) * page_size + tok
            s = jnp.where(own_head & (kpos <= pos), s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp2(s - m_new)
            alpha = jnp.exp2(m - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = p * vs_refs[i][0]
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc = acc * alpha + pv
            m = m_new
        m_ref[...], l_ref[...], acc_ref[...] = m, l, acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[...]
        # a row that attended nothing (a negative position) has l == 0;
        # emit zeros, not 0/0 NaN — its output is discarded either way
        # but NaN would trip downstream finiteness guards
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def paged_attention_kernel(
    q, k_pages, v_pages, page_table, seq_lens, *,
    k_scales=None, v_scales=None, block_kv=None, interpret=None,
):
    """Pallas ragged paged-attention decode; contract of
    :func:`paged_attention_reference` (same shapes, same masking rule).

    Grid (B, ceil(maxp / pages_per_block)); the page table and row
    positions ride as scalar prefetch, and each of a cell's
    ``block_kv // page_size`` pages is its own BlockSpec operand whose
    index map reads the pool page out of the table — so every fetch goes
    through the Pallas pipeline (double-buffered, repeat fetches of a
    clamped dead page elided) and no contiguous copy of a sequence ever
    materializes. Quantized pools carry ``k_scales``/``v_scales``
    (per-row absmax, see ops/quant.py), fetched the same way.
    Online-softmax state lives in VMEM scratch across the block walk
    (the ``arbitrary`` grid dim).
    """
    b, nq, hd = q.shape
    num_pool_pages, page_size, nkv, _ = k_pages.shape
    maxp = page_table.shape[1]
    if interpret is None:
        interpret = interpret_default()
    if block_kv is None:
        block_kv = page_size
    if block_kv % page_size != 0 or block_kv <= 0:
        raise ValueError(
            f"block_kv ({block_kv}) must be a positive multiple of the "
            f"pool page size ({page_size})"
        )
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    quantized = k_scales is not None
    ppb = block_kv // page_size
    cols = page_size * nkv

    def page_map(i):
        def index_map(b_, j_, lens, table):
            # clamp dead slots onto the row's last live page (repeat fetch)
            last = jnp.maximum(lens[b_], 0) // page_size
            return (table[b_, jnp.minimum(j_ * ppb + i, last)], 0, 0)

        return index_map

    def row_map(b_, j_, *_):
        return (b_, 0, 0)

    def page_specs(rows, width):
        return [
            pl.BlockSpec((1, rows, width), page_map(i)) for i in range(ppb)
        ]

    # (P, ps, Nkv, H) -> (P, ps*Nkv, H): the trailing block dims equal the
    # array's, which is what the TPU lowering requires of a block that
    # is not (8, 128)-aligned
    operands = [k_pages.reshape(num_pool_pages, cols, hd)] * ppb
    operands += [v_pages.reshape(num_pool_pages, cols, hd)] * ppb
    in_specs = [pl.BlockSpec((1, nq, hd), row_map)]
    in_specs += page_specs(cols, hd) * 2
    if quantized:
        operands += [k_scales.reshape(num_pool_pages, 1, cols)] * ppb
        operands += [v_scales.reshape(num_pool_pages, 1, cols)] * ppb
        in_specs += page_specs(1, cols) * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, -(-maxp // ppb)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nq, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((nq, hd), jnp.float32),
            pltpu.VMEM((nq, 1), jnp.float32),
            pltpu.VMEM((nq, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            page_size=page_size,
            pages_per_block=ppb,
            nkv=nkv,
            scale=hd**-0.5,
            quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nq, hd), q.dtype),
        # scratch carries across the block walk; batch rows independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(seq_lens.astype(jnp.int32), page_table.astype(jnp.int32), q, *operands)
    return out.reshape(b, nq * hd)


# ---------------------------------------------------------------------------
# latent (MLA) pages: one pool, the key's leading lanes are the value
# ---------------------------------------------------------------------------


def _latent_decode_kernel(
    lens_ref,  # scalar prefetch: (B,) int32 query positions
    table_ref,  # scalar prefetch: (B, maxp) int32 page table
    layer_ref,  # scalar prefetch: (1,) int32 layer of the pool
    q_ref,  # (1, N, W)
    *rest,  # ppb pages (1, 1, ps, W); o (1, N, Vw); scratch
    page_size,
    pages_per_block,
    value_width,
    scale,
):
    """One (batch row, block of pages) grid cell of absorbed latent
    attention: every query head against the same ``W``-wide rows, whose
    first ``value_width`` lanes are also the value. ``_paged_decode_kernel``
    with one kv head, one operand for keys and values, and no columns to
    mask but the positions past the row's."""
    ppb = pages_per_block
    page_refs = rest[:ppb]
    o_ref, acc_ref, m_ref, l_ref = rest[ppb:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = lens_ref[b]
    n = q_ref.shape[1]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * (ppb * page_size) <= pos)
    def _():
        q = (q_ref[0] * (scale * LOG2E)).astype(q_ref.dtype)  # (N, W)
        tok = jax.lax.broadcasted_iota(jnp.int32, (n, page_size), 1)
        m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
        for i in range(ppb):
            k = page_refs[i][0, 0]  # (ps, W)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (N, ps), base-2 domain
            kpos = (j * ppb + i) * page_size + tok
            s = jnp.where(kpos <= pos, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp2(s - m_new)
            alpha = jnp.exp2(m - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(k.dtype), k[:, :value_width],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m = m_new
        m_ref[...], l_ref[...], acc_ref[...] = m, l, acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


# cache positions one grid cell of the latent kernel attends
LATENT_BLOCK_TOKENS = 512


@scoped("attn")
def latent_attention_kernel(
    q, pool, layer, page_table, seq_lens, *, value_width, scale,
    interpret=None,
):
    """Ragged paged decode attention over one layer of a latent pool.

    q (B, N, W): a query a row and head, as wide as a pool entry; pool
    (L, P, page_size, W), read where it lies (``layer`` a traced scalar:
    no slice of the pool is ever made); page_table (B, maxp); row ``b``
    sees cache positions <= seq_lens[b]. A position's value is the first
    ``value_width`` lanes of its entry. Returns (B, N, value_width) fp32:
    softmax(scale * q k^T) v.

    Grid (B, blocks of pages); each page of a cell is its own BlockSpec
    operand whose index map reads the pool page out of the table, dead
    blocks clamped onto the row's last live page (a repeat fetch the
    pipeline elides) and skipped; the running softmax lives in VMEM
    scratch across a row's walk."""
    b, n, w = q.shape
    page_size = pool.shape[2]
    maxp = page_table.shape[1]
    if interpret is None:
        interpret = interpret_default()
    from fms_fsdp_tpu.ops.selective_scan import largest_divisor

    ppb = largest_divisor(maxp, max(1, LATENT_BLOCK_TOKENS // page_size))

    def page_map(i):
        def index_map(b_, j_, lens, table, layer_):
            last = jnp.maximum(lens[b_], 0) // page_size
            return (layer_[0], table[b_, jnp.minimum(j_ * ppb + i, last)], 0, 0)

        return index_map

    def row_map(b_, j_, *_):
        return (b_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, maxp // ppb),
        in_specs=[pl.BlockSpec((1, n, w), row_map)] + [
            pl.BlockSpec((1, 1, page_size, w), page_map(i)) for i in range(ppb)
        ],
        out_specs=pl.BlockSpec((1, n, value_width), row_map),
        scratch_shapes=[
            pltpu.VMEM((n, value_width), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _latent_decode_kernel,
            page_size=page_size,
            pages_per_block=ppb,
            value_width=value_width,
            scale=scale,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, value_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        seq_lens.astype(jnp.int32),
        page_table.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q,
        *([pool] * ppb),
    )


# ---------------------------------------------------------------------------
# chosen pages: block-sparse attention through compressed keys (InfLLM-V2)
# ---------------------------------------------------------------------------
#
# A query does not walk its stream's whole table. It scores the stream's
# compressed keys (the mean of ``kernel_size`` keys every
# ``kernel_stride`` positions: an index cache beside the pages), pools the
# scores to blocks of ``block_size`` positions and attends ``topk`` blocks
# only. With a page as long as a block a chosen block is a page, so the
# ragged kernel above walks a list of chosen pages in place of the table.
# ``sp`` is models/configs.py::SalaSparseConfig. Scores, softmax and
# pooling are float32.


def compress_keys(k, sp):
    """The means of the whole windows of k (B, S, Nkv, H) that start every
    ``kernel_stride`` positions from 0 on: (B, Nkv, S // stride - 1, H) in
    k's dtype (``kernel_size`` is twice the stride), summed in float32.
    Window ``j`` covers positions ``stride * j`` to ``stride * j +
    kernel_size - 1``."""
    B, S, nkv, H = k.shape
    halves = jnp.sum(
        k.astype(jnp.float32).reshape(B, S // sp.kernel_stride,
                                      sp.kernel_stride, nkv, H),
        axis=2,
    )
    means = (halves[:, :-1] + halves[:, 1:]) / sp.kernel_size
    return jnp.moveaxis(means, 2, 1).astype(k.dtype)


def block_keys(q, kc, t, sp):
    """What each query ranks the blocks of its context by. q (B, T, Nkv,
    g, H): the ``g`` query heads of a kv head choose together; kc (B, Nkv,
    nb * r, H): compressed key ``j`` at row ``j`` (``r`` a block; rows
    whose window has not ended by a query's position are not read for
    it); t (B, T) int32 the queries' positions. -> (key (B, Nkv, T, nb)
    float32, exists (B, T, nb), dense (B, T, 1)): ``key`` is +inf for a
    block that is always attended (the first ``init_blocks``, the
    ``window_size`` positions' worth that end at the query's own, and
    every block of a query with ``t + 1 <= dense_len``), -inf for a block
    past the query's own, else the largest over the compressed keys that
    touch the block of the softmax over compressed keys, summed over the
    group's heads."""
    B, T, nkv, g, H = q.shape
    r = sp.per_block
    nb = kc.shape[2] // r
    s = jnp.einsum(
        "btkgh,bkjh->bkgtj", q, kc, preferred_element_type=jnp.float32
    ) * (H**-0.5)
    ends = jnp.arange(nb * r, dtype=jnp.int32) * sp.kernel_stride + (
        sp.kernel_size - 1
    )
    valid = ends[None, None, :] <= t[:, :, None]  # (B, T, nC)
    v5 = valid[:, None, None]
    s = jnp.where(v5, s, NEG_INF)
    e = jnp.where(v5, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    z = jnp.sum(e, axis=-1, keepdims=True)
    p = jnp.sum(e / jnp.maximum(z, 1e-30), axis=2)  # (B, Nkv, T, nC)
    p = jnp.where(valid[:, None], p, -1.0).reshape(B, nkv, T, nb, r)
    # block b: windows r*b - 1 .. r*b + r - 1, every one that touches it
    before = jnp.concatenate(
        [jnp.full((B, nkv, T, 1), -1.0), p[..., :-1, r - 1]], axis=-1
    )
    score = jnp.maximum(jnp.max(p, axis=-1), before)
    blocks = jnp.arange(nb, dtype=jnp.int32)
    back = (t // sp.block_size)[..., None] - blocks  # (B, T, nb)
    exists = back >= 0
    dense = (t + 1 <= sp.dense_len)[..., None]
    forced = (blocks < sp.init_blocks) | (back < sp.window_blocks) | dense
    key = jnp.where(forced[:, None], jnp.inf, score)
    return jnp.where(exists[:, None], key, -jnp.inf), exists, dense


def chosen_mask(key, exists, dense, sp):
    """``block_keys``' ranking -> (B, Nkv, T, nb) bool: the ``topk`` best
    blocks of each query, ties to the lower index, and every block up to
    its own where the query is ``dense``."""
    nb = key.shape[-1]
    top, idx = jax.lax.top_k(key, min(sp.topk, nb))
    hit = (idx[..., None] == jnp.arange(nb, dtype=jnp.int32)) & (
        top[..., None] > -jnp.inf
    )
    return jnp.where(dense[:, None], exists[:, None], jnp.any(hit, axis=-2))


def chosen_list(key, dense, sp):
    """``block_keys``' ranking for one query a row (T = 1) -> (blocks (B,
    Nkv, w) int32, the chosen blocks in rising order and then zeros; n
    (B, Nkv) int32, how many): ``topk`` of them, or every block up to the
    query's own where it is ``dense``; ``w`` = ``sp.list_blocks`` (or
    every block, if the context has fewer). The query's own block is the
    last of its list."""
    nb = key.shape[-1]
    w = min(sp.list_blocks, nb)
    top, idx = jax.lax.top_k(key[:, :, 0], w)  # (B, Nkv, w)
    place = jnp.arange(w, dtype=jnp.int32)
    keep = (top > -jnp.inf) & (dense[:, :, None, 0] | (place < sp.topk))
    n = jnp.sum(keep, axis=-1).astype(jnp.int32)
    idx = jnp.sort(jnp.where(keep, idx, nb), axis=-1)
    return jnp.where(place < n[..., None], idx, 0).astype(jnp.int32), n


def chosen_pages_attention(
    q, k_pages, v_pages, page_table, seq_lens, blocks, n, *,
    first_page=0, kernel=True, block_kv=None,
):
    """One query a row over its chosen pages. q (B, Nkv, g, H); k_pages,
    v_pages (P', page_size, 1, H): pools of one kv head a page, the pages
    of kv head ``h`` of the layer at ``first_page[h] + id`` (``first_page``
    (Nkv,) int32); page_table (B, maxp); ``blocks`` (B, Nkv, w) and ``n``
    (B, Nkv) from ``chosen_list``: row ``b``'s query at position
    ``seq_lens[b]`` attends, for kv head ``h``, the positions up to its
    own of pages ``page_table[b, blocks[b, h, :n[b, h]]]``. Each (row, kv
    head) is a row of the ragged kernel (``kernel``) or of the gathered
    reference, its table the chosen pages and its length the chosen
    positions. -> (B, Nkv * g * H)."""
    B, nkv, g, H = q.shape
    w = blocks.shape[-1]
    page_size = k_pages.shape[1]
    table = jnp.take_along_axis(
        page_table[:, None, :], blocks, axis=-1
    ) + jnp.asarray(first_page, jnp.int32).reshape(1, nkv, 1)
    # the chosen pages in a row: the last is the query's own block
    lens = (n - 1) * page_size + (seq_lens % page_size)[:, None]
    attend = paged_attention_kernel if kernel else paged_attention_reference
    kw = {"block_kv": block_kv} if kernel else {}
    o = attend(
        q.reshape(B * nkv, g, H), k_pages, v_pages,
        table.reshape(B * nkv, w), lens.reshape(B * nkv), **kw,
    )
    return o.reshape(B, nkv * g * H)
