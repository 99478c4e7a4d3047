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
- ``_paged_decode_kernel``: the Pallas kernel. **A grid cell is a
  stream** (a row of the batch): the cell itself loops over the row's
  own live blocks of ``block_kv`` positions, ``0 .. seq_lens[b] //
  block_kv``, with the FlashAttention-2 online softmax carried in VMEM
  scratch, so a slot that holds no stream, or a short one, costs one grid
  step and no more. (Until PR 47 the grid was (batch, every block a slot
  could hold): a dead cell computed and fetched nothing and still cost
  0.8 us on a v5e, 1.7 of the 2.3 ms of a call at the phi4flash cell's
  128 slots of 16 blocks.) **The pools stay in HBM and a block's pages
  are copied by hand**, straight from their pool pages through the table
  in scalar memory, into one of two VMEM buffers: the next block's are
  asked for before this one is multiplied, and behind a row's last block
  the first block of the next row that walks, so that only the call's
  first fetch is waited for. Pages past a row's last live one are not
  fetched at all (the buffers are zeroed once a call: what a buffer held
  may be no number). No contiguous copy of a sequence ever materializes.
  **A product takes the heads of one 32-bit word.** A page arrives as
  its (page_size * Nkv, H) row-major view, row ``t * Nkv + h`` token t
  of kv head h; one head's rows lie ``Nkv`` rows apart, and the chip
  reads rows that far apart in words of 32 bits (a block of a BlockSpec
  or a copy may not take one head out of the (Nkv, H) minor tile; a
  strided read of the block in VMEM may). A word holds two neighbouring
  bfloat16 rows, so a product multiplies a *pair* of kv heads' rows of a
  whole block against that pair's query rows and masks the other head's
  columns with the out-of-range positions: scores of ``2 * group x 2 *
  block`` where every head at once made ``Nq x Nkv * page`` (float32
  pools: one head a product; an odd ``Nkv``, and int8/fp8 pools, whose
  scales lie a position and head along the lanes: every head at once, a
  page a product, as before). On the chip, alone, at the phi4flash
  cell's shapes and a window's lengths (PERF.md section 6, PR 47): the
  walk with every head at once 1.16 ms a call where the grid took 2.32;
  a pair a block 1.11; a pair a page 1.84 (twenty small products a block
  do not overlap); the other routes, a strided copy that lands a head's
  rows apart or a page whose rows lie head by head, were not built: a
  copy cannot split the two rows of a word, and no cell asked for a new
  pool layout.

Tile resolution (page_size at allocator build, block_kv per call) goes
through the tuning table (fms_fsdp_tpu/tune/lookup.py::
resolve_paged_decode) like every other kernel. ``block_kv`` may be any
multiple of ``page_size`` (the positions of one hand-fetched block, two
of which are resident beside the scores), and int8/fp8-quantized pools
are read natively — the per-page scale rows are fetched beside the pages
and applied to the scores and probabilities in VMEM, so quantized serving
does not fall back to the reference gather.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops.pallas_mode import interpret_default
from fms_fsdp_tpu.ops.ring_attention import merge_partial
from fms_fsdp_tpu.ops.selective_scan import largest_divisor

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


def _heads_per_product(nkv, itemsize, quantized):
    """How many of a position's ``nkv`` heads one product takes. A page's
    row ``t * nkv + h`` is position t of kv head h, so one head's rows
    lie ``nkv`` rows apart: a strided read of the block in VMEM, which
    the chip makes in words of 32 bits. A word holds one float32 row or
    two neighbouring bfloat16 rows, so a product takes that many heads
    where they divide ``nkv``; else (and under scales, which lie a
    position and head along the lanes) every head at once, the other
    heads' columns masked."""
    per_word = 4 // itemsize
    if quantized or per_word > 2 or nkv % per_word:
        return nkv
    return per_word


def _positions_per_product(hg, quantized, block_kv, page_size):
    """The positions whose rows one product takes: a block's, but a page's
    where scales come a page or every head of more than two is multiplied
    at once (a page's columns a head are a block's worth already)."""
    return block_kv if hg <= 2 and not quantized else page_size


def _paged_decode_kernel(
    lens_ref,  # scalar prefetch: (B,) int32 query positions
    table_ref,  # scalar prefetch: (B, maxp) int32 page table
    next_ref,  # scalar prefetch: (B + 1,) int32, the next row that walks
    q_ref,  # (1, G, R, H): G products' query rows, R = hg * group
    *rest,  # k, v (, k scales, v scales) in HBM; o; their buffers; scratch
    page_size,
    pages_per_block,
    nkv,
    hg,
    chunk,
    scale,
    quantized,
):
    """One grid cell is one stream (a row of the batch): the cell loops
    over the row's live blocks of ``pages_per_block`` pages, the online
    softmax carried in VMEM scratch, and every other row costs a grid
    step and nothing else.

    The pools stay in HBM. A block's live pages are copied by hand into
    one of two VMEM buffers, the next block's asked for before this one
    is multiplied, and behind a row's last block the first block of the
    next row that walks (``next_ref``), so no row but the first waits
    for a fetch. A page arrives as its (page_size * Nkv, H) row-major
    view, row ``t * Nkv + h`` token t of kv head h. A product takes
    ``hg`` heads' rows of ``chunk`` positions out of the buffer
    (``_heads_per_product``: a strided read) against those heads' query
    rows alone, and masks the columns of the other ``hg - 1`` heads
    with the positions past the row's."""
    ppb = pages_per_block
    n_ops = 4 if quantized else 2
    hbm, o_ref = rest[:n_ops], rest[n_ops]
    bufs = rest[n_ops + 1 : 2 * n_ops + 1]
    sem, slot_ref, acc_ref, m_ref, l_ref = rest[2 * n_ops + 1 :]

    b = pl.program_id(0)
    rows_n = pl.num_programs(0)
    pos = lens_ref[b]  # query position; attends to cache idx <= pos
    block = ppb * page_size
    page_rows = page_size * nkv
    G, R, hd = q_ref.shape[1:]
    group = R // hg

    def fetch(row, j, slot, wait):
        """Start (or wait for) the copies of block ``j`` of ``row``, its
        pages up to the row's last live one, into ``slot``."""
        last = lens_ref[row] // page_size
        for i in range(ppb):
            page = j * ppb + i

            @pl.when(page <= last)
            def _():
                pid = table_ref[row, page]
                for n, (src, buf) in enumerate(zip(hbm, bufs)):
                    dst = (
                        buf.at[slot, pl.ds(i * page_rows, page_rows)]
                        if n < 2 else buf.at[slot, i]
                    )
                    copy = pltpu.make_async_copy(
                        src.at[pid], dst, sem.at[n, slot]
                    )
                    if wait:
                        copy.wait()
                    else:
                        copy.start()

    @pl.when(b == 0)
    def _():
        # a page past a row's last is never fetched; what a buffer held
        # before the call may be no number, and 0 * NaN is NaN
        for buf in bufs:
            buf[...] = jnp.zeros(buf.shape, buf.dtype)
        slot_ref[0] = 0

        @pl.when(next_ref[0] < rows_n)
        def _():
            fetch(next_ref[0], 0, 0, wait=False)

    def heads(buf, slot, c, g):
        """``hg`` heads' rows of chunk ``c`` of the block in ``slot``:
        (chunk * hg, H), row ``t * hg + h``."""
        if hg == nkv:
            return buf[slot, pl.ds(c * chunk * nkv, chunk * nkv), :]
        per = nkv // hg
        at = pl.ds(c * chunk * per + g, chunk, stride=per)
        if buf.dtype.itemsize == 4:
            return buf[slot, at, :]
        return pltpu.bitcast(buf.bitcast(jnp.uint32)[slot, at, :], buf.dtype)

    def multiply(j, slot):
        cols = chunk * hg
        col = jax.lax.broadcasted_iota(jnp.int32, (R, cols), 1)
        tok = col // hg
        if hg > 1:
            row = jax.lax.broadcasted_iota(jnp.int32, (R, cols), 0)
            own_head = row // group == col % hg
        for c in range(block // chunk):
            first = j * block + c * chunk

            @pl.when(first <= pos)  # else no position of the chunk is seen
            def _():
                live = first + tok <= pos
                if hg > 1:
                    live &= own_head
                for g in range(G):
                    # scale + change of base folded into q; exp2 replaces
                    # exp in the online softmax (ops/flash_attention.py)
                    q = (q_ref[0, g] * (scale * LOG2E)).astype(q_ref.dtype)
                    k = heads(bufs[0], slot, c, g)
                    v = heads(bufs[1], slot, c, g)
                    if quantized:
                        # int8/fp8 values are exact in the compute dtype;
                        # the per-row absmax scales fold into the score
                        # columns and the probabilities instead of a
                        # (ps*Nkv, H) dequantize
                        k = k.astype(q.dtype)
                        v = v.astype(q.dtype)
                    s = jax.lax.dot_general(
                        q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )  # (R, cols), base-2 domain
                    if quantized:
                        s = s * bufs[2][slot, c]
                    s = jnp.where(live, s, NEG_INF)
                    m = m_ref[g]
                    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                    p = jnp.exp2(s - m_new)
                    alpha = jnp.exp2(m - m_new)
                    l_ref[g] = l_ref[g] * alpha + jnp.sum(
                        p, axis=1, keepdims=True
                    )
                    if quantized:
                        p = p * bufs[3][slot, c]
                    pv = jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    acc_ref[g] = acc_ref[g] * alpha + pv
                    m_ref[g] = m_new

    @pl.when(pos < 0)
    def _():
        # a row at a negative position attends nothing: zeros, not 0/0
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(pos >= 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        blocks = pos // block + 1
        behind = next_ref[b + 1]

        def walk(j, slot):
            @pl.when(j + 1 < blocks)
            def _():
                fetch(b, j + 1, 1 - slot, wait=False)

            @pl.when((j + 1 == blocks) & (behind < rows_n))
            def _():
                fetch(behind, 0, 1 - slot, wait=False)

            fetch(b, j, slot, wait=True)
            multiply(j, slot)
            return 1 - slot

        slot_ref[0] = jax.lax.fori_loop(0, blocks, walk, slot_ref[0])
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_attention_kernel(
    q, k_pages, v_pages, page_table, seq_lens, *,
    k_scales=None, v_scales=None, block_kv=None, interpret=None, scale=None,
    nkv=None, out_dtype=None,
):
    """Pallas ragged paged-attention decode; contract of
    :func:`paged_attention_reference` (same shapes, same masking rule;
    ``scale``: the scores' factor where it is not ``H ** -0.5``; ``nkv``:
    the pages come as the cells read them, (P, page_size * Nkv, H), and
    this is their ``Nkv``; ``out_dtype``: the result's where it is not
    the queries': float32 hands the cells' own accumulator out unrounded).

    Grid (B,): a cell is a row, which walks its own live blocks of
    ``block_kv`` positions (``_paged_decode_kernel``); the page table,
    the row positions and each row's successor among the rows at a
    position >= 0 ride as scalar prefetch. The pools are left where they
    lie and a block's pages copied by hand, so no contiguous copy of a
    sequence ever materializes. Quantized pools carry
    ``k_scales``/``v_scales`` (per-row absmax, see ops/quant.py), fetched
    the same way. The cells run in order on one core (``arbitrary``):
    each leaves the next one's first fetch in flight.
    """
    b, nq, hd = q.shape
    if nkv is None:
        num_pool_pages, page_size, nkv, _ = k_pages.shape
    else:
        assert k_scales is None, "scales come a position and kv head"
        num_pool_pages, page_size = k_pages.shape[0], k_pages.shape[1] // nkv
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
    cols = page_size * nkv
    # (P, ps, Nkv, H) -> (P, ps*Nkv, H): a page as one run of rows
    # (a no-op where ``nkv`` said that the pages come so)
    operands = [
        k_pages.reshape(num_pool_pages, cols, hd),
        v_pages.reshape(num_pool_pages, cols, hd),
    ]
    if k_scales is not None:
        operands += [
            k_scales.reshape(num_pool_pages, 1, cols),
            v_scales.reshape(num_pool_pages, 1, cols),
        ]
    return _paged_decode(
        q, seq_lens.astype(jnp.int32), page_table.astype(jnp.int32),
        *operands, nkv=nkv, block_kv=block_kv, interpret=interpret,
        scale=hd**-0.5 if scale is None else scale,
        out_dtype=jnp.dtype(out_dtype or q.dtype),
    )


# jitted, so that a program whose layers call it with one signature lowers
# the kernel once
@functools.partial(
    jax.jit,
    static_argnames=(
        "nkv", "block_kv", "interpret", "scale", "out_dtype"),
)
def _paged_decode(
    q, seq_lens, page_table, k_pages, v_pages, *scales, nkv, block_kv,
    interpret, scale, out_dtype,
):
    b, nq, hd = q.shape
    cols = k_pages.shape[1]
    page_size = cols // nkv
    ppb = block_kv // page_size
    quantized = bool(scales)
    store = k_pages.dtype
    hg = _heads_per_product(nkv, store.itemsize, quantized)
    chunk = _positions_per_product(hg, quantized, block_kv, page_size)
    G, R = nkv // hg, hg * (nq // nkv)

    # of each row, the next one that walks (B where none does); [0] the first
    ids = jnp.where(
        seq_lens >= 0, jnp.arange(b, dtype=jnp.int32), jnp.int32(b))
    walks = jnp.concatenate([
        jax.lax.cummin(ids, reverse=True), jnp.full((1,), b, jnp.int32)])

    def row_map(b_, *_):
        return (b_, 0, 0, 0)

    buffers = [pltpu.VMEM((2, ppb * cols, hd), store)] * 2
    if quantized:
        buffers += [pltpu.VMEM((2, ppb, 1, cols), scales[0].dtype)] * 2
    resident = 2 * 2 * ppb * cols * hd * store.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, G, R, hd), row_map)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * (2 + len(scales)),
        out_specs=pl.BlockSpec((1, G, R, hd), row_map),
        scratch_shapes=buffers + [
            pltpu.SemaphoreType.DMA((2 + len(scales), 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((G, R, hd), jnp.float32),
            pltpu.VMEM((G, R, 1), jnp.float32),
            pltpu.VMEM((G, R, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            page_size=page_size,
            pages_per_block=ppb,
            nkv=nkv,
            hg=hg,
            chunk=chunk,
            scale=scale,
            quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, G, R, hd), out_dtype),
        # a cell leaves the next one's first block in flight
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=resident + 16 * 2**20,
        ),
        interpret=interpret,
        name="paged_attention_decode",
    )(seq_lens, page_table, walks, q.reshape(b, G, R, hd),
      k_pages, v_pages, *scales)
    return out.reshape(b, nq * hd)


# ---------------------------------------------------------------------------
# heads narrower than a tile: two heads of 64 share 128 lanes
# ---------------------------------------------------------------------------


def packed_row_width(nkv: int, hd: int) -> int:
    """Lanes of a row of a page of ``packed_pages_attention_kernel``: the
    tile's 128 where a position's ``nkv`` heads of ``hd`` fill whole
    rows of it, else all of them in one row (test sizes)."""
    return 128 if (nkv * hd) % 128 == 0 and 128 % hd == 0 else nkv * hd


def tile_rows(nkv: int, hd: int) -> int:
    """Rows a position's ``nkv`` heads of ``hd`` take in such a page."""
    return nkv * hd // packed_row_width(nkv, hd)


def packed_pages_attention_kernel(
    q, k_pages, v_pages, page_table, seq_lens, *, nkv: int,
    block_kv=None, interpret=None,
):
    """``paged_attention_kernel`` for heads narrower than the 128 lanes
    of a tile (64: two a tile), over pages stored at their own width.

    q (B, Nq, H); k_pages/v_pages (P, page_size * tile_rows, W) with W =
    ``packed_row_width(nkv, H)``, 128 at the published sizes: a
    position's ``nkv`` heads side by side and the positions behind one
    another, as rows of W lanes, nothing padded. (A pool whose last axis
    is one head of 64 the chip lays out with positions minor and copies
    whole around every write, 6 GB of temporaries at the lfm2 widths;
    one whose last axis is a position's 512 values it copies whole into
    the rows the cells read, 2 GB: deviceless v5e compiles, PERF.md
    PR 41.) ``pack = W / H`` neighbouring kv heads are read as one head
    of W lanes, which is how they lie in a page; a query head's values
    stand in its own kv head's lanes of the row and zeros in the others,
    so its scores are its own head's; of the W lanes the values' product
    gives it, it keeps its own head's. The kernel is the one the
    128-wide families run, told ``scale = H ** -0.5``. Returns (B, Nq *
    H)."""
    b, nq, hd = q.shape
    pack = k_pages.shape[-1] // hd
    assert k_pages.shape[-1] == packed_row_width(nkv, hd), (k_pages.shape, hd)
    assert nkv % pack == 0 and nq % nkv == 0, (hd, nkv, nq)
    # which of a row's heads each query head's kv head is
    lane = jax.nn.one_hot(
        (jnp.arange(nq) // (nq // nkv)) % pack, pack, dtype=q.dtype
    )  # (Nq, pack)
    q_row = (q[:, :, None, :] * lane[None, :, :, None]).reshape(
        b, nq, pack * hd
    )
    out = paged_attention_kernel(
        q_row, k_pages, v_pages, page_table, seq_lens, block_kv=block_kv,
        interpret=interpret, scale=hd**-0.5, nkv=nkv // pack,
    ).reshape(b, nq, pack, hd)
    return jnp.sum(out * lane[None, :, :, None], axis=2).reshape(b, nq * hd)


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
    first ``value_width`` lanes are also the value: one kv head, one
    operand for keys and values, and no columns to mask but the positions
    past the row's. The grid is still every block a slot could hold, the
    pages BlockSpec operands, as ``_paged_decode_kernel``'s was until
    PR 47 made its cell a stream."""
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


# ---------------------------------------------------------------------------
# chosen blocks of a prefill chunk: each query's own list out of a context
# that is resident in vector memory
# ---------------------------------------------------------------------------
#
# A prefill chunk has thousands of queries and every one of them chose its
# own blocks; what the queries of a block share is the forced half of the
# choice (the first blocks and the band that ends at their own), which the
# caller multiplies as one band. The free half has nothing to share: under
# seeded weights the union of a tile's free blocks is the context. So the
# kernel below holds one kv head's keys and values of the context in
# vector memory (a segment of it where the whole would not fit) and takes
# each query's free blocks out of that memory by index: no page is fetched
# from HBM a second time and the scores never leave the chip. It shares no
# logic with the decode kernel above, which walks one query's pages out
# of a pool.

# bytes of vector memory one kv head's keys and values may take while a
# chunk's queries gather from them: a context that needs more is held a
# segment at a time, each query's list cut by segment
RESIDENT_KV_BYTES = 64 * 2**20
# rows the resident keys and values are copied in at a time
_COPY_ROWS = 2048
# lanes the log-sum-exp of a query's heads is written over
_LSE_LANES = 128


def free_list(key, sp):
    """``block_keys``' ranking -> (free (B, Nkv, T, W) int32, n (B, Nkv,
    T) int32): the blocks each query chose beyond the forced ones, best
    first and ties to the lower index (``lax.top_k``'s places after the
    forced), then zeros; ``W = min(topk, nb) - init_blocks -
    window_blocks``, which has to be at least 1. For queries that are not
    dense and whose forced blocks all exist and stand apart (own block
    ``>= init_blocks + window_blocks - 1``): with the forced blocks the
    list is ``chosen_mask``'s row, block for block."""
    forced = sp.init_blocks + sp.window_blocks
    top, idx = jax.lax.top_k(key, min(sp.topk, key.shape[-1]))
    keep = top[..., forced:] > -jnp.inf
    free = jnp.where(keep, idx[..., forced:], 0).astype(jnp.int32)
    return free, jnp.sum(keep, axis=-1).astype(jnp.int32)


def _gather_blocks_kernel(
    upto_ref,  # scalar prefetch: (1,) int32, positions written so far
    lst_ref,  # SMEM (1, tq * w): the cell's lists, block indices in the segment
    cnt_ref,  # SMEM (1, tq): how many slots of each list count
    q_ref,  # (1, tq, g, H)
    k_hbm,  # (B, Nkv, S, H), left in place
    v_hbm,
    o_ref,  # (1, 1, tq, g, H) float32
    lse_ref,  # (1, 1, tq, g, _LSE_LANES) float32
    k_res,  # VMEM (segment, H)
    v_res,
    sem,
    *,
    block_size,
    width,
    segment,
    copy_rows,
    scale,
):
    """One (row, kv head, segment, tile of queries) cell. At a segment's
    first tile the kv head's keys and values of the segment, up to the
    positions written so far, are copied into ``k_res``/``v_res``; every
    tile of queries then gathers from them. A query's ``width`` slots are
    taken out in one piece (a slot past its count reads block 0 and is
    masked): one product of the kv head's query heads against them, the
    softmax in float32, one product with the values."""
    b, h, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tq, g, H = q_ref.shape[1:]
    bs = block_size

    @pl.when(pl.program_id(3) == 0)
    def _():
        rows = jnp.clip(upto_ref[0] - s * segment, 0, segment)
        pieces = (rows + copy_rows - 1) // copy_rows

        def copies(i):
            at = pl.ds(i * copy_rows, copy_rows)
            src = pl.ds(s * segment + i * copy_rows, copy_rows)
            return (
                pltpu.make_async_copy(
                    k_hbm.at[b, h, src], k_res.at[at], sem.at[0]),
                pltpu.make_async_copy(
                    v_hbm.at[b, h, src], v_res.at[at], sem.at[1]),
            )

        def start(i, _):
            for c in copies(i):
                c.start()

        def wait(i, _):
            for c in copies(i):
                c.wait()

        jax.lax.fori_loop(0, pieces, start, None)
        jax.lax.fori_loop(0, pieces, wait, None)

    col = jax.lax.broadcasted_iota(jnp.int32, (g, width * bs), 1)

    def query(t, _):
        def gathered(ref):
            return jnp.concatenate([
                ref[pl.ds(pl.multiple_of(
                    lst_ref[0, t * width + i] * bs, bs), bs), :]
                for i in range(width)
            ], axis=0)  # (width * bs, H)

        sc = jax.lax.dot_general(
            q_ref[0, t], gathered(k_res), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (g, width * bs)
        live = col < cnt_ref[0, t] * bs
        sc = jnp.where(live, sc, NEG_INF)
        m = jnp.max(sc, axis=1, keepdims=True)
        p = jnp.where(live, jnp.exp(sc - m), 0.0)
        l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
        v = gathered(v_res)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0, 0, t] = o / l
        lse_ref[0, 0, t] = jnp.broadcast_to(
            m + jnp.log(l), (g, lse_ref.shape[-1]))

    jax.lax.fori_loop(0, tq, query, None)


def gathered_blocks_attention(
    q, kb, vb, free, n, upto, *, block_size, q_tile=128, segment=None,
    interpret=None,
):
    """Each query over its own list of whole blocks. q (B, c, Nkv, g, H);
    kb, vb (B, kv_len, Nkv, H), written up to ``upto`` positions (a traced
    int32; what lies past it is not read); ``free`` (B, Nkv, c, W) int32
    and ``n`` (B, Nkv, c) from ``free_list``: query ``t`` of row ``b``
    attends, for kv head ``h``, every position of blocks ``free[b, h, t,
    :n[b, h, t]]`` (blocks before its own: no causal edge inside them).
    -> (normalised output (B, c, N, H) float32, log-sum-exp (B, c, N, 1)
    float32), a partial that ``merge_partial`` joins with the band's; a
    query with an empty list gives a log-sum-exp near ``NEG_INF``.

    The keys and values of one kv head are resident in vector memory
    while the chunk's queries gather from them, ``segment`` positions at
    a time (by default the whole context, or what ``RESIDENT_KV_BYTES``
    hold): each segment takes the lists' blocks that lie in it, in rising
    order, and the segments' partials are merged here."""
    H, kv_len, bs = q.shape[-1], kb.shape[1], block_size
    if interpret is None:
        interpret = interpret_default()
    if segment is None:
        fit = RESIDENT_KV_BYTES // (2 * H * kb.dtype.itemsize)
        segment = bs * largest_divisor(kv_len // bs, fit // bs)
    assert kv_len % segment == 0 and segment % bs == 0, (kv_len, segment, bs)
    return _gathered_blocks(
        q, kb, vb, free, n, jnp.asarray(upto, jnp.int32), block_size=bs,
        q_tile=q_tile, segment=segment, interpret=interpret,
    )


# jitted, so that a program whose layers call it with one signature lowers
# the kernel once
@functools.partial(
    jax.jit, static_argnames=("block_size", "q_tile", "segment", "interpret"))
def _gathered_blocks(
    q, kb, vb, free, n, upto, *, block_size, q_tile, segment, interpret
):
    B, c, nkv, g, H = q.shape
    kv_len = kb.shape[1]
    bs = block_size
    W = free.shape[-1]
    n_seg = kv_len // segment
    copy_rows = bs * largest_divisor(segment // bs, max(1, _COPY_ROWS // bs))
    tq = largest_divisor(c, q_tile)

    if n_seg == 1:
        lists, counts = free[None], n[None]
    else:
        # a segment's share of each list: the blocks that lie in it, in
        # rising order from slot 0 on
        seg_blocks = segment // bs
        valid = jnp.arange(W, dtype=jnp.int32) < n[..., None]
        rel = free[None] - (
            jnp.arange(n_seg, dtype=jnp.int32) * seg_blocks
        ).reshape(n_seg, 1, 1, 1, 1)
        inside = valid[None] & (rel >= 0) & (rel < seg_blocks)
        counts = jnp.sum(inside, axis=-1).astype(jnp.int32)
        rel = jnp.sort(jnp.where(inside, rel, seg_blocks), axis=-1)
        lists = jnp.where(rel < seg_blocks, rel, 0)
    # (n_seg, B, Nkv, c, ...) -> a row of scalar memory a cell
    cells = B * nkv * n_seg * (c // tq)
    lists = jnp.moveaxis(lists, 0, 2).reshape(cells, 1, tq * W)
    counts = jnp.moveaxis(counts, 0, 2).reshape(cells, 1, tq)

    def cell(b, h, s, i, upto):
        return (((b * nkv + h) * n_seg + s) * (c // tq) + i, 0, 0)

    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out_map = lambda b, h, s, i, upto: (s, b, i, h, 0)  # noqa: E731
    resident = 2 * segment * H * kb.dtype.itemsize
    o, lse = pl.pallas_call(
        functools.partial(
            _gather_blocks_kernel, block_size=bs, width=W, segment=segment,
            copy_rows=copy_rows, scale=H**-0.5,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nkv, n_seg, c // tq),
            in_specs=[
                smem((None, 1, tq * W), cell),
                smem((None, 1, tq), cell),
                pl.BlockSpec(
                    (1, tq, g, H), lambda b, h, s, i, upto: (b, i, h, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, tq, g, H), out_map),
                pl.BlockSpec((1, 1, tq, g, _LSE_LANES), out_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((segment, H), kb.dtype),
                pltpu.VMEM((segment, H), vb.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_seg, B, c, nkv * g, H), jnp.float32),
            jax.ShapeDtypeStruct(
                (n_seg, B, c, nkv * g, _LSE_LANES), jnp.float32),
        ],
        # the resident keys and values cross the tiles of a segment
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,
            vmem_limit_bytes=min(resident + 24 * 2**20, 120 * 2**20),
        ),
        interpret=interpret,
        name="gathered_blocks_attention",
    )(
        jnp.reshape(upto, (1,)), lists, counts,
        q.reshape(B, c, nkv * g, H),
        # by head: a copy may not take a head out of the (Nkv, H) minor
        # tile, and the compiler keeps the prefill's buffers head-major
        # anyway, so this moves nothing there
        jnp.moveaxis(kb, 2, 1), jnp.moveaxis(vb, 2, 1),
    )
    lse = lse[..., :1]
    out = (o[0], lse[0])
    for s in range(1, n_seg):
        out = merge_partial(out, o[s], lse[s])
    return out
