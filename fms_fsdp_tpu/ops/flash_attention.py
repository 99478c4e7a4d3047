"""Blockwise causal flash attention for TPU (Pallas/Mosaic).

Replaces the reference's SDPA FlashAttention-2 CUDA path
(ref:README.md:5,46) with MXU-tiled kernels:

- forward: one grid step per (batch, q-head, q-block); the kv stream for
  the matching GQA kv-head stays in VMEM and is walked block-by-block with
  the FlashAttention-2 online softmax (fp32 running max/denominator), so
  HBM traffic is O(S) and the (S, S) score matrix never materializes;
- backward: a dq kernel mirroring the forward walk, and a dk/dv kernel
  gridded (b, kv-head, k-block, gqa-member, q-block) that streams q
  through the grid, accumulates dk/dv in fp32 VMEM scratch across the
  (gqa-member, q-block) sweep, and computes scores transposed (BK, BQ)
  so softmax stats broadcast from row-layout (B, N, 1, S) lse/delta —
  column layout would lane-pad each stat element x128 in VMEM;
- GQA native: kv heads are indexed via block-spec index maps
  (kv_head = q_head // group) — kv is never materialized repeated
  (70B trains at 64 q / 8 kv heads, ref:config_utils.py:26-34).

The layout inside the kernels is (B, N, S, H). The training variants have
one head width, a multiple of 128; the forward kernels also take values of
another width than queries and keys (latent attention: 192 and 128, as they
are) and heads 64 wide (a block of 64 lanes: ``supports(...,
forward_only=True)``). A "context" mesh axis walks remote kv blocks: ops/ring_attention.py.
"""

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fms_fsdp_tpu.obs.scopes import scoped

NEG_INF = -1e30
LOG2E = 1.4426950408889634  # log2(e)
LN2 = 0.6931471805599453

# The online softmax runs in base 2: the scale (and the log2(e) change of
# base) is folded into q before the kv walk — one (BQ, H) multiply instead
# of a (BQ, BK) multiply per score block — and exp2 replaces exp (the VPU
# computes exp as exp2 plus that same multiply; doing it explicitly once
# removes it from the hot loop). At head 128 the score-path elementwise
# work is what bounds these kernels (VPU ~2T op/s vs MXU 197 TF/s: ~5 VPU
# ops/elem cost more than the 256 MXU FLOPs/elem), so each op removed is
# direct throughput.


def _causal_mask(scores, q_block, k_block, q_start, k_start):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, k_block), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, k_block), 1)
    return jnp.where(qpos >= kpos, scores, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_k, causal):
    block_q = q_ref.shape[2]
    head = v_ref.shape[3]  # the accumulator's and the output's width
    seq_k = k_ref.shape[2]
    qi = pl.program_id(2)
    q_start = qi * block_q

    # scale + change of base folded into q (see module note above); native
    # dtype feeds the MXU at full rate
    q = (q_ref[0, 0] * (scale * LOG2E)).astype(q_ref.dtype)  # (BQ, H)

    if causal:
        num_kb = (q_start + block_q + block_k - 1) // block_k
        diag_start = q_start // block_k  # first block needing a mask
    else:
        num_kb = seq_k // block_k
        diag_start = num_kb

    def make_body(masked):
        def body(kb, carry):
            acc, m, l = carry
            k_start = kb * block_k
            k = k_ref[0, 0, pl.ds(k_start, block_k), :]
            v = v_ref[0, 0, pl.ds(k_start, block_k), :]
            s = jax.lax.dot_general(
                q,
                k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (BQ, BK) fp32, base-2 domain
            if masked:
                s = _causal_mask(s, block_q, block_k, q_start, k_start)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp2(s - m_new)
            alpha = jnp.exp2(m - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype),
                v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc = acc * alpha + pv
            return acc, m_new, l

        return body

    acc = jnp.zeros((block_q, head), jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    # sub-diagonal blocks skip mask construction entirely (VPU savings);
    # only the diagonal span pays for position math
    carry = jax.lax.fori_loop(0, diag_start, make_body(False), (acc, m, l))
    acc, m, l = jax.lax.fori_loop(diag_start, num_kb, make_body(True), carry)

    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    # back to the natural-log domain: lse = ln(sum exp(s)) = m*ln2 + ln(l)
    lse_ref[0, 0] = m * LN2 + jnp.log(l)


@scoped("flash_attention_fwd")
def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               variant=None):
    """q (B, Nq, Sq, H), k (B, Nkv, Sk, H), v (B, Nkv, Sk, Hv) -> (o, lse),
    o (B, Nq, Sq, Hv) as wide as the values.

    Two implementations (identical math/contract): the kv-resident
    fori_loop kernel below, and the kv-streamed grid kernel
    (_fwd_kernel_kvgrid). ``variant`` pins the family for this call (the
    tuning-table choice, resolved in flash_attention); otherwise
    FLASH_KERNEL_VARIANT / set_kernel_variant overrides the automatic one."""
    if _use_kvgrid(k.shape[2], variant):
        return _flash_fwd_kvgrid(
            q, k, v, scale, causal, block_q, block_k, interpret
        )
    batch, nq, seq_q, head = q.shape
    nkv, seq_k, vdim = k.shape[1], k.shape[2], v.shape[3]
    group = nq // nkv

    grid = (batch, nq, seq_q // block_q)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_k=block_k, causal=causal
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, head), lambda b, h, i: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, seq_k, head), lambda b, h, i: (b, h // group, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, seq_k, vdim), lambda b, h, i: (b, h // group, 0, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_q, vdim), lambda b, h, i: (b, h, i, 0)
            ),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, nq, seq_q, vdim), q.dtype),
            jax.ShapeDtypeStruct((batch, nq, seq_q, 1), jnp.float32),
        ],
        # every grid cell is independent (no scratch carried between
        # steps): telling Mosaic lets it pipeline/partition freely
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _fwd_kernel_kvgrid(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, num_kb,
):
    """kv-streamed forward: grid (b, h, qi, ki), one kv block per cell.

    The resident kernel above stages the whole per-head kv stream in VMEM
    and walks it with fori_loop — VMEM residency O(S), hard sequence cap
    ~8k, and the first cell stalls on the full-kv DMA. Here kv arrives
    one (BK, H) block per grid step, so Mosaic double-buffers the next
    block's DMA behind the current block's compute, residency is O(BQ+BK)
    (any sequence length), and the online-softmax state (acc, m, l) lives
    in VMEM scratch carried across the ki sweep.

    Causal skip: cells entirely above the diagonal run no compute
    (pl.when) and fetch no data (their kv index map is clamped onto the
    diagonal block, a repeat fetch Mosaic elides). The output is written
    at the last ki step, which always runs.
    """
    block_q = q_ref.shape[2]
    block_k = k_ref.shape[2]
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * block_q

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if causal:
        last_kb = (q_start + block_q - 1) // block_k  # last contributing
        run = ki <= last_kb
        k_start = jnp.minimum(ki, last_kb) * block_k  # matches the clamp
        # only the diagonal span needs element masking
        is_diag = k_start + block_k > q_start
    else:
        run = True
        k_start = ki * block_k
        is_diag = False

    def contribution(masked):
        q = (q_ref[0, 0] * (scale * LOG2E)).astype(q_ref.dtype)
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BQ, BK), base-2 domain
        if masked:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    if causal:
        @pl.when(run & is_diag)
        def _():
            contribution(True)

        @pl.when(run & jnp.logical_not(is_diag))
        def _():
            contribution(False)
    else:
        contribution(False)

    @pl.when(ki == num_kb - 1)
    def _():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] * LN2 + jnp.log(l)


def _flash_fwd_kvgrid(q, k, v, scale, causal, block_q, block_k, interpret):
    """kv-streamed variant of _flash_fwd; same contract."""
    batch, nq, seq_q, head = q.shape
    nkv, seq_k, vdim = k.shape[1], k.shape[2], v.shape[3]
    group = nq // nkv
    num_kb = seq_k // block_k

    def kvmap(b, h, i, j):
        if causal:
            # clamp above-diagonal cells onto the diagonal block: no DMA
            # is issued for skipped cells (repeat fetch), and in-bounds
            # for every (i, j)
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        return (b, h // group, j, 0)

    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel_kvgrid, scale=scale, causal=causal, num_kb=num_kb
        ),
        grid=(batch, nq, seq_q // block_q, num_kb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, head), kvmap),
            pl.BlockSpec((1, 1, block_k, vdim), kvmap),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, vdim), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, nq, seq_q, vdim), q.dtype),
            jax.ShapeDtypeStruct((batch, nq, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, vdim), jnp.float32),  # acc
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max (base 2)
            pltpu.VMEM((block_q, 1), jnp.float32),  # running denominator
        ],
        # state carries across the ki sweep; outer three dims independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# forward under a sliding window (serving's window layers; no backward)
# ---------------------------------------------------------------------------


def _band_blocks(i, block_q, block_k, window):
    """(first, last) K block that the Q block ``i`` can see under the
    causal mask and a window of ``window`` positions (a position sees
    itself and the ``window - 1`` before it). Traced or not."""
    q_start = i * block_q
    first = jnp.maximum(q_start - (window - 1), 0) // block_k
    return first, (q_start + block_q - 1) // block_k


def _fwd_kernel_window(
    q_ref, k_ref, vt_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale, window, num_kb,
):
    """kv-streamed forward over the band: grid (b, kv head, qi, j), cell
    ``j`` holding the ``j``-th K block of the Q block's band and the Q
    block of every query head of the KV head's group, ``group * BQ``
    queries (head ``g``'s at ``g * BQ``), so that a K and a V block are
    fetched once a group and both products run over all its queries. A K
    block that lies wholly outside the band is neither fetched (the index
    map never names it; cells past the band's last block are clamped onto
    it, a repeat fetch Mosaic elides) nor computed (pl.when); the band's
    two edges are masked element by element in every cell that runs (at a
    window no wider than a block there is no cell without an edge), by a
    query's position in its own head's block.

    Scores are held keys down, queries across: (BK, group * BQ). A band
    is a block or two of keys wide, so a query's max and sum over its
    keys are what a cell does most. With queries down, both are
    cross-lane reductions, two a vreg of scores, and the (BQ, 1) state
    is a vreg every eight queries, as many as the scores have: 23 ns a
    vreg of scores on a v5e, one head a cell or eight (PERF.md section
    6, PR 38). Down the sublanes both are element-wise, the state is a
    vreg every 1024 queries, and the accumulator (Hv, group * BQ) is
    turned once a Q block: 4 ns."""
    group, block_q, head = q_ref.shape[2:]
    block_k = k_ref.shape[2]
    rows = group * block_q
    qi = pl.program_id(2)
    j = pl.program_id(3)
    q_start = qi * block_q
    first, last = _band_blocks(qi, block_q, block_k, window)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(first + j <= last)
    def _():
        k_start = (first + j) * block_k
        q = (q_ref[0, 0] * (scale * LOG2E)).astype(q_ref.dtype)
        vt = vt_ref[0, 0]
        s = jax.lax.dot_general(
            k_ref[0, 0], q.reshape(rows, head), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BK, group * BQ), base-2 domain
        back = (
            q_start - k_start
            + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
        )  # how far the key lies behind the query, whatever its head
        back = jnp.concatenate([back] * group, axis=1)
        s = jnp.where((back >= 0) & (back < window), s, NEG_INF)
        # a query that sees nothing of this block and nothing yet keeps
        # m = NEG_INF and adds p = 1 a key; the first block it does see
        # (its own position's, which every query has) wipes that: alpha = 0
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
        pv = jax.lax.dot_general(
            vt, p.astype(vt.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (Hv, group * BQ)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == num_kb - 1)
    def _():
        for g in range(group):
            at = pl.ds(g * block_q, block_q)
            l = l_ref[:, at]
            o_ref[0, 0, g] = (acc_ref[:, at] / l).T.astype(o_ref.dtype)
            lse_ref[0, 0, g] = m_ref[:, at] * LN2 + jnp.log(l)


@scoped("flash_attention_fwd")
def _flash_fwd_window(q, k, v, scale, window, block_q, block_k, interpret):
    """q (B, Nq, S, H), k (B, Nkv, S, H), v (B, Nkv, S, Hv) -> (o, lse) of
    causal attention in which a position sees itself and the ``window -
    1`` before it. The work follows the band, ``S * window``, and not
    ``S * S / 2``; a grid cell holds the ``Nq // Nkv`` query heads of one
    KV head (q seen as (B, Nkv, group, S, H): no copy) and reads V keys
    across, (B, Nkv, Hv, S): the caller's transpose of v lands there. On
    a TPU both blocks are multiples of 128 or the whole length."""
    batch, nq, seq, head = q.shape
    nkv, vdim = k.shape[1], v.shape[3]
    group = nq // nkv
    rows = group * block_q
    # the widest band of any Q block, in K blocks: static
    num_kb = max(
        (i * block_q + block_q - 1) // block_k
        - max(i * block_q - (window - 1), 0) // block_k + 1
        for i in range(seq // block_q)
    )

    def qmap(b, h, i, j):
        return (b, h, 0, i, 0)

    def kb(i, j):
        first, last = _band_blocks(i, block_q, block_k, window)
        return jnp.minimum(first + j, last)

    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel_window, scale=scale, window=window, num_kb=num_kb
        ),
        grid=(batch, nkv, seq // block_q, num_kb),
        in_specs=[
            pl.BlockSpec((1, 1, group, block_q, head), qmap),
            pl.BlockSpec(
                (1, 1, block_k, head), lambda b, h, i, j: (b, h, kb(i, j), 0)
            ),
            pl.BlockSpec(
                (1, 1, vdim, block_k), lambda b, h, i, j: (b, h, 0, kb(i, j))
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, group, block_q, vdim), qmap),
            pl.BlockSpec(
                (1, 1, group, 1, block_q), lambda b, h, i, j: (b, h, 0, 0, i)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, nkv, group, seq, vdim), q.dtype),
            jax.ShapeDtypeStruct((batch, nkv, group, 1, seq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((vdim, rows), jnp.float32),  # acc, queries across
            pltpu.VMEM((1, rows), jnp.float32),  # running max (base 2)
            pltpu.VMEM((1, rows), jnp.float32),  # running denominator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q.reshape(batch, nkv, group, seq, head), k, jnp.swapaxes(v, 2, 3))
    return o.reshape(batch, nq, seq, vdim), lse.reshape(batch, nq, seq, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_window_bnsh(q, k, v, scale, window, block_q, block_k, interpret):
    return _flash_fwd_window(
        q, k, v, scale, window, block_q, block_k, interpret
    )


def _flash_window_fwd(q, k, v, scale, window, block_q, block_k, interpret):
    out = _flash_fwd_window(
        q, k, v, scale, window, block_q, block_k, interpret
    )
    return out, None


def _flash_window_bwd(scale, window, block_q, block_k, interpret, res, g):
    # the dq and dk/dv kernels walk every block under the diagonal; only
    # the forward walks a band (serving's window layers: no training path)
    raise NotImplementedError(
        f"flash_attention backward under a sliding window (window={window}) "
        "is not built: the dq and dk/dv kernels take no window; only the "
        "forward kernel walks the band"
    )


_flash_window_bnsh.defvjp(_flash_window_fwd, _flash_window_bwd)


# ---------------------------------------------------------------------------
# backward: dq
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale, block_k, causal
):
    block_q = q_ref.shape[2]
    head = q_ref.shape[3]
    seq_k = k_ref.shape[2]
    qi = pl.program_id(2)
    q_start = qi * block_q

    # base-2 softmax recompute: scale*log2(e) folded into q, lse converted
    # to base 2 (cheap: (BQ, 1)), p = exp2(s2 - lse2) == exp(s - lse)
    q = (q_ref[0, 0] * (scale * LOG2E)).astype(q_ref.dtype)
    do = do_ref[0, 0]
    lse2 = lse_ref[0, 0] * LOG2E  # (BQ, 1)
    delta = delta_ref[0, 0]

    if causal:
        num_kb = (q_start + block_q + block_k - 1) // block_k
        diag_start = q_start // block_k
    else:
        num_kb = seq_k // block_k
        diag_start = num_kb

    def make_body(masked):
        def body(kb, dq):
            k_start = kb * block_k
            k = k_ref[0, 0, pl.ds(k_start, block_k), :]
            v = v_ref[0, 0, pl.ds(k_start, block_k), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # base-2 domain
            if masked:
                s = _causal_mask(s, block_q, block_k, q_start, k_start)
            p = jnp.exp2(s - lse2)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            ds = (p * (dp - delta) * scale).astype(k.dtype)
            return dq + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )

        return body

    dq = jnp.zeros((block_q, head), jnp.float32)
    dq = jax.lax.fori_loop(0, diag_start, make_body(False), dq)
    dq = jax.lax.fori_loop(diag_start, num_kb, make_body(True), dq)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _dq_kernel_kvgrid(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale, causal, num_kb,
):
    """kv-streamed dq: grid (b, h, qi, ki), dq accumulated in VMEM scratch
    across the ki sweep — the streamed counterpart of _dq_kernel, same
    skip/clamp scheme as _fwd_kernel_kvgrid, O(block) VMEM residency."""
    block_q = q_ref.shape[2]
    block_k = k_ref.shape[2]
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * block_q

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if causal:
        last_kb = (q_start + block_q - 1) // block_k
        run = ki <= last_kb
        k_start = jnp.minimum(ki, last_kb) * block_k  # matches the clamp
        is_diag = k_start + block_k > q_start
    else:
        run = True
        k_start = ki * block_k
        is_diag = False

    def contribution(masked):
        q = (q_ref[0, 0] * (scale * LOG2E)).astype(q_ref.dtype)
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse2 = lse_ref[0, 0] * LOG2E
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # base-2 domain
        if masked:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp2(s - lse2)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        @pl.when(run & is_diag)
        def _():
            contribution(True)

        @pl.when(run & jnp.logical_not(is_diag))
        def _():
            contribution(False)
    else:
        contribution(False)

    @pl.when(ki == num_kb - 1)
    def _():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_dq_kvgrid(
    q, k, v, dout, lse, delta, *, scale, causal, block_q, block_k, interpret,
    out_dtype=None,
):
    """kv-streamed variant of flash_dq; same contract."""
    batch, nq, seq_q, head = q.shape
    nkv, seq_k = k.shape[1], k.shape[2]
    group = nq // nkv
    num_kb = seq_k // block_k

    def kvmap(b, h, i, j):
        if causal:
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        return (b, h // group, j, 0)

    def qmap(b, h, i, j):
        return (b, h, i, 0)

    return pl.pallas_call(
        functools.partial(
            _dq_kernel_kvgrid, scale=scale, causal=causal, num_kb=num_kb
        ),
        grid=(batch, nq, seq_q // block_q, num_kb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head), qmap),
            pl.BlockSpec((1, 1, block_k, head), kvmap),
            pl.BlockSpec((1, 1, block_k, head), kvmap),
            pl.BlockSpec((1, 1, block_q, head), qmap),
            pl.BlockSpec((1, 1, block_q, 1), qmap),
            pl.BlockSpec((1, 1, block_q, 1), qmap),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, head), qmap),
        out_shape=jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, dout, lse, delta)


# ---------------------------------------------------------------------------
# backward: dk, dv
# ---------------------------------------------------------------------------


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,  # inputs
    dk_ref, dv_ref,  # outputs
    dk_acc, dv_acc,  # fp32 scratch
    *,
    scale, causal,
    group, num_qb,
):
    """Streamed-q dk/dv: grid (b, kvh, ki, g, qi), q walked via the grid.

    The kv block stays resident across the whole (g, qi) sweep; dk/dv
    accumulate in fp32 VMEM scratch across both the q walk and the GQA
    group, and are written once at the final (g, qi) step. Scores are
    computed transposed — (BK, BQ) — so the softmax stats broadcast from
    row-layout lse/delta (B, N, 1, S): a (S, 1) column layout would pad
    each element to a full 128-lane vector in VMEM.
    """
    block_k = k_ref.shape[2]
    block_q = q_ref.shape[2]
    ki = pl.program_id(2)
    g = pl.program_id(3)
    qi = pl.program_id(4)
    k_start = ki * block_k

    if causal:
        qi0 = (ki * block_k) // block_q  # first q block on/under the diagonal
        run = qi >= qi0
    else:
        qi0 = 0
        run = True

    # Zero-init at the first *visited* cell (not the first contributing
    # one): a k-block entirely past the q sequence (causal cross-length)
    # never contributes, and its write-out below must emit zeros, not
    # whatever the previous k-block left in scratch.
    @pl.when((g == 0) & (qi == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def contribution(masked, q_start):
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        # base-2 recompute, same folding as the dq kernel; lse2 is (1, BQ).
        # The raw q is still needed below: dk = ds^T . q (unscaled).
        q = q_ref[0, 0]
        q2 = (q * (scale * LOG2E)).astype(q.dtype)
        do = do_ref[0, 0]
        lse2 = lse_ref[0, 0] * LOG2E  # (1, BQ) rows
        delta = delta_ref[0, 0]
        st = jax.lax.dot_general(
            k, q2, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BK, BQ), base-2 domain
        if masked:
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0
            )
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1
            )
            st = jnp.where(qpos >= kpos, st, NEG_INF)
        pt = jnp.exp2(st - lse2)  # (BK, BQ)
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype),
            do,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BK, BQ)
        dst = (pt * (dpt - delta) * scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        q_start = qi * block_q
        # blocks straddling the diagonal need the element mask
        is_diag = q_start < k_start + block_k - 1

        @pl.when(run & is_diag)
        def _():
            contribution(True, q_start)

        @pl.when(run & jnp.logical_not(is_diag))
        def _():
            contribution(False, q_start)

    else:

        @pl.when(run)
        def _():
            contribution(False, qi * block_q)

    @pl.when((g == group - 1) & (qi == num_qb - 1))
    def _():
        dk_ref[0, 0] = dk_acc[...]
        dv_ref[0, 0] = dv_acc[...]


def flash_dq(
    q, k, v, dout, lse, delta, *, scale, causal, block_q, block_k, interpret,
    out_dtype=None, variant=None,
):
    """dq of one attention partial, (B, N, S, H) layout. ``lse``/``delta``
    are the (global) softmax stats of the queries, (B, N, S, 1) fp32 —
    callable per ring step with stats from the full softmax. ``out_dtype``
    (default q.dtype) should be fp32 when partials are accumulated across
    ring steps, so per-step rounding doesn't compound.

    The kv-streamed implementation engages automatically past the
    resident kernels' sequence cap (or via ``variant`` — the per-call
    pin the VJP threads through so forward and backward always pick the
    same family — or FLASH_KERNEL_VARIANT=kvgrid) — one rule for the
    forward and this kernel so the whole VJP shares a residency model."""
    if _use_kvgrid(k.shape[2], variant):
        return _flash_dq_kvgrid(
            q, k, v, dout, lse, delta, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
            out_dtype=out_dtype,
        )
    batch, nq, seq_q, head = q.shape
    nkv, seq_k = k.shape[1], k.shape[2]
    group = nq // nkv
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_k=block_k, causal=causal),
        grid=(batch, nq, seq_q // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, seq_k, head), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, seq_k, head), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, block_q, head), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, head), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
    )(q, k, v, dout, lse, delta)


def flash_dkv(q, k, v, dout, lse, delta, *, scale, causal, block_q, block_k, interpret):
    """(dk, dv) of one attention partial, (B, N, S, H) layout, fp32
    outputs. Stats as in flash_dq."""
    batch, nq, seq_q, head = q.shape
    nkv, seq_k = k.shape[1], k.shape[2]
    group = nq // nkv
    # row-layout stats for the transposed dk/dv kernel: (B, N, 1, S)
    lse_rows = jnp.swapaxes(lse, 2, 3)
    delta_rows = jnp.swapaxes(delta, 2, 3)
    num_qb = seq_q // block_q
    num_kb = seq_k // block_k

    def _clamp_qi(qi, ki):
        # clamp skipped (above-diagonal) cells onto the first contributing
        # q block so no extra DMA is issued for them; the upper clamp keeps
        # the fetch in-bounds for k-blocks wholly past the q sequence
        # (causal cross-length), where no cell contributes at all
        return jnp.minimum(
            jnp.maximum(qi, (ki * block_k) // block_q), num_qb - 1
        )

    def qmap(b, kvh, ki, g, qi):
        if causal:
            qi = _clamp_qi(qi, ki)
        return (b, kvh * group + g, qi, 0)

    def qmap_rows(b, kvh, ki, g, qi):
        if causal:
            qi = _clamp_qi(qi, ki)
        return (b, kvh * group + g, 0, qi)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel,
            scale=scale,
            causal=causal,
            group=group,
            num_qb=num_qb,
        ),
        grid=(batch, nkv, num_kb, group, num_qb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head), qmap),
            pl.BlockSpec(
                (1, 1, block_k, head), lambda b, kvh, ki, g, qi: (b, kvh, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, head), lambda b, kvh, ki, g, qi: (b, kvh, ki, 0)
            ),
            pl.BlockSpec((1, 1, block_q, head), qmap),
            pl.BlockSpec((1, 1, 1, block_q), qmap_rows),
            pl.BlockSpec((1, 1, 1, block_q), qmap_rows),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_k, head), lambda b, kvh, ki, g, qi: (b, kvh, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, head), lambda b, kvh, ki, g, qi: (b, kvh, ki, 0)
            ),
        ],
        # fp32 outputs: dk/dv accumulate in fp32 scratch; keep the store
        # dtype fp32 so GQA-group sums don't round between members
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head), jnp.float32),
            pltpu.VMEM((block_k, head), jnp.float32),
        ],
        # dk/dv accumulate in scratch across the (g, qi) sweep — those two
        # dims must run in order; the outer three are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel",
                "parallel",
                "parallel",
                "arbitrary",
                "arbitrary",
            )
        ),
        interpret=interpret,
    )(q, k, v, dout, lse_rows, delta_rows)
    return dk, dv


@scoped("flash_attention_bwd")
def _flash_bwd(scale, causal, block_q, block_k, interpret, variant,
               residuals, dout, dlse=None):
    """Backward for o (and optionally the lse output), one head width.

    A differentiable lse output only shifts the per-row delta: the lse
    cotangent enters as ds_ij += p_ij * dlse_i, and ds is already
    p * (dp - delta), so delta_eff = delta - dlse — zero kernel changes.
    """
    q, k, v, o, lse = residuals
    if v.shape[-1] != q.shape[-1]:
        # the dq and dk/dv kernels size every block by q's width; only the
        # forward takes two (serving's latent attention: no training path)
        raise NotImplementedError(
            "flash_attention backward with a value width other than the "
            f"query/key width is not built (keys {q.shape[-1]} wide, "
            f"values {v.shape[-1]} wide): the dq and dk/dv kernels take "
            "one head width; only the forward kernels take two"
        )
    delta = jnp.sum(
        o.astype(jnp.float32) * dout.astype(jnp.float32), axis=-1, keepdims=True
    )
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    kw = dict(
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    dq = flash_dq(q, k, v, dout, lse, delta, variant=variant, **kw)
    dk, dv = flash_dkv(q, k, v, dout, lse, delta, **kw)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_bnsh(
    q, k, v, scale, causal, block_q, block_k, interpret, variant
):
    o, _ = _flash_fwd(
        q, k, v, scale, causal, block_q, block_k, interpret, variant
    )
    return o


def _flash_attention_fwd(
    q, k, v, scale, causal, block_q, block_k, interpret, variant
):
    o, lse = _flash_fwd(
        q, k, v, scale, causal, block_q, block_k, interpret, variant
    )
    return o, (q, k, v, o, lse)


_flash_attention_bnsh.defvjp(
    _flash_attention_fwd,
    lambda scale, causal, bq, bk, interp, var, res, g: _flash_bwd(
        scale, causal, bq, bk, interp, var, res, g
    ),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_lse_bnsh(
    q, k, v, scale, causal, block_q, block_k, interpret, variant
):
    """(o, lse) with lse (B, N, S, 1) fp32 as a *differentiable* output —
    the ring-attention building block (partials merge through lse)."""
    return _flash_fwd(
        q, k, v, scale, causal, block_q, block_k, interpret, variant
    )


def _flash_attention_lse_fwd(
    q, k, v, scale, causal, block_q, block_k, interpret, variant
):
    o, lse = _flash_fwd(
        q, k, v, scale, causal, block_q, block_k, interpret, variant
    )
    return (o, lse), (q, k, v, o, lse)


_flash_attention_lse_bnsh.defvjp(
    _flash_attention_lse_fwd,
    lambda scale, causal, bq, bk, interp, var, res, g: _flash_bwd(
        scale, causal, bq, bk, interp, var, res, g[0], dlse=g[1]
    ),
)


def _pick_block(seq: int, target: int, kind: str = "") -> int:
    b = min(seq, target)
    while seq % b != 0:
        b //= 2
    b = max(b, 1)
    if kind and 2 * b < min(seq, target):
        # divisibility halving degraded the tile below half the request
        # (e.g. seq 2944 @ 512 -> 128) — count it in the obs registry
        # and warn once; a silent 4x tile shrink is an MFU cliff
        from fms_fsdp_tpu.tune.lookup import note_block_degradation

        note_block_degradation(kind, seq, target, b)
    return b


# The resident kernels stage the full per-head sequence in VMEM (k+v
# forward and dq): ~8 * S * H bytes. Past this cap the dispatch switches
# to the kv-streamed kernels (O(block) residency, any length), so the
# Pallas path has no sequence limit.
MAX_KERNEL_SEQ = 8192

# Kernel-family override ("resident" | "kvgrid" | None = automatic by
# sequence length). It governs the forward AND the dq backward kernel.
# Read ONCE at import (canonical env var FLASH_KERNEL_VARIANT;
# FLASH_FWD_VARIANT kept as a legacy alias): a trace-time env read would
# let a mid-process change silently disagree with already-cached jits.
_ENV_VARIANT = os.environ.get(
    "FLASH_KERNEL_VARIANT", os.environ.get("FLASH_FWD_VARIANT")
)
if _ENV_VARIANT not in (None, "auto", "resident", "kvgrid"):
    # fail loud: a typo'd env value silently falling back to automatic
    # dispatch would mislabel every benchmark run under it
    raise ValueError(
        f"FLASH_KERNEL_VARIANT={_ENV_VARIANT!r}: expected "
        f"'resident' | 'kvgrid' | 'auto'"
    )
_VARIANT = None if _ENV_VARIANT == "auto" else _ENV_VARIANT


def set_kernel_variant(variant):
    """Select the kernel family: "resident" | "kvgrid" force one, "auto"
    forces the automatic by-sequence-length dispatch, None restores the
    import-time default (the FLASH_KERNEL_VARIANT env value, else auto) —
    so every step build resolves the variant deterministically from its
    own config, never inheriting a forcing left by an earlier build. Call
    before tracing: already-cached jits keep the variant they were traced
    with. Config plumbing: TrainConfig.flash_kernel_variant."""
    global _VARIANT
    assert variant in (None, "auto", "resident", "kvgrid"), variant
    if variant is None:
        variant = _ENV_VARIANT
    _VARIANT = None if variant == "auto" else variant


def _use_kvgrid(seq_k: int, variant=None) -> bool:
    # per-call pin (the tuning-table family, threaded through the VJP)
    # first; then the process-wide forcing; then the sequence-length rule
    if variant == "kvgrid":
        return True
    if variant == "resident":
        return False
    if _VARIANT == "kvgrid":
        return True
    if _VARIANT == "resident":
        return False
    return seq_k > MAX_KERNEL_SEQ


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _wire_ste(x, wire: str):
    """Round-trip one attention operand through the quantized-family
    wire format (per-row absmax along the head dim; int8 grid or e4m3
    fp8 — operands want mantissa, unlike the e5m2 gradient wire) with
    straight-through gradients: the round-trip is piecewise constant,
    so its true jacobian is 0 a.e. — the identity cotangent is the
    standard QAT estimator and keeps the backward exactly the
    unquantized kernel's."""
    from fms_fsdp_tpu.ops.quant import activation_roundtrip

    return activation_roundtrip(x, wire)


def _wire_ste_fwd(x, wire):
    return _wire_ste(x, wire), None


def _wire_ste_bwd(wire, res, g):
    del wire, res
    return (g,)


_wire_ste.defvjp(_wire_ste_fwd, _wire_ste_bwd)


def supports(q_shape, k_shape, forward_only: bool = False) -> bool:
    """Eligibility of the Pallas path for these shapes. ``forward_only``:
    the caller never differentiates (serving's prefill); the forward
    kernels also take heads 64 wide, as blocks of 64 lanes."""
    _, sq, nq, h = q_shape
    _, sk, nkv, _ = k_shape
    if _VARIANT == "resident":
        max_seq = MAX_KERNEL_SEQ  # resident forced: the cap is real
    else:
        max_seq = float("inf")  # kv-streamed kernels engage past the cap
    return (
        (h % 128 == 0 or (forward_only and h == 64))
        and sq % 256 == 0
        and sk % 256 == 0
        and sq <= max_seq
        and sk <= max_seq
        and nq % max(nkv, 1) == 0
    )


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    scale=None,
    block_q=None,
    block_k=None,
    interpret: bool = False,
    return_lse: bool = False,
    variant=None,
    quant=None,
    window=None,
):
    """q: (B, S, Nq, H); k: (B, S, Nkv, H); v: (B, S, Nkv, Hv) ->
    (B, S, Nq, Hv). Hv = H everywhere but in latent attention's forward
    (Hv != H has no backward). ``block_q``/``block_k``/``variant``
    default to the tuning-table resolution (tune/lookup.py): exact
    signature match, then nearest, then the static 512/512 defaults —
    bit-identical to the pre-tuner behavior when ``kernel_tuning="off"``
    or the table has no legal entry. Passing them explicitly pins the
    values (tests, ring attention's bwd partials). The resolution is
    pure host table/cost-model work at trace time — never a sweep.

    A table entry carrying ``quant`` ("int8"/"fp8") — or the explicit
    ``quant=`` arg (the autotune sweep pinning a candidate) — selects
    the quantized kernel family: q/k are round-tripped through the wire
    format (per-row absmax scales, straight-through gradients) before
    the score GEMM. The committed table carries no quant entries, so
    stock runs never take this branch.

    With ``return_lse``, also returns the per-query logsumexp
    (B, S, Nq, 1) fp32 as a differentiable output, enabling exact
    merging of attention partials over disjoint kv sets (ring attention).

    ``window`` (None: none): a position sees itself and the ``window -
    1`` before it. Causal, queries and keys of one length, forward only:
    a kernel of its own that fetches and computes the K blocks of each Q
    block's band and no others, a KV head's group of query heads a grid
    cell (blocks of ``block_q`` x ``block_k``, 256 x 128 unless pinned;
    the tuning table has no entry for it). Scores are kept keys down, so
    a query's sum over its keys runs down the sublanes and not across
    the lanes as in the causal kernel: the same float32 work in another
    order, equal to the last place.
    """
    if window is not None:
        if not causal or q.shape[1] != k.shape[1] or window < 1:
            raise ValueError(
                "a sliding window is causal over queries and keys of one "
                f"length (causal={causal}, {q.shape[1]} queries, "
                f"{k.shape[1]} keys, window={window})"
            )
        scale = float(scale if scale is not None else q.shape[-1] ** -0.5)
        # timed on the chip (PERF.md section 6, PR 38): 2048 positions, a
        # window of 128, heads of 128, transposes in and out included. 64
        # query heads on 8: 256 x 128 278 us, 128 x 128 285, 128 x 256
        # 340, 256 x 256 340; with a loop over the group's heads in the
        # cell 324, 340, 379, 375 and 512 x 128 505 (one head a cell,
        # queries down: 1153). 8 on 8: 256 x 128 131, 128 x 128 154-157
        # (159). A block of 128 queries walks 256 keys for a band of 128
        # where one of 256 walks 384, in 256 cells and not 192: level.
        ot, lse = _flash_window_bnsh(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), scale, int(window),
            _pick_block(q.shape[1], block_q or 256),
            _pick_block(k.shape[1], block_k or 128), interpret,
        )
        if return_lse:
            return jnp.swapaxes(ot, 1, 2), jnp.swapaxes(lse, 1, 2)
        return jnp.swapaxes(ot, 1, 2)
    from fms_fsdp_tpu.tune.lookup import (
        record_final_flash_blocks,
        resolve_flash,
    )

    scale = float(scale if scale is not None else q.shape[-1] ** -0.5)
    # a per-call variant arg pins the family; else the process-wide
    # forcing (set_kernel_variant) pins it; else the table may pick it
    bq, bk, fam, qnt, _ = resolve_flash(
        q.shape,
        k.shape,
        str(q.dtype),
        requested_q=block_q,
        requested_k=block_k,
        requested_variant=variant if variant is not None else _VARIANT,
        requested_quant=quant,
    )
    if qnt in ("int8", "fp8"):
        # quantized family (tuning table or the autotune sweep opted
        # in): q/k ride the wire format of the score GEMM. Execution
        # today is simulated quantization — the operands are
        # round-tripped through the wire dtype (straight-through
        # gradients) before the unquantized kernel — so the numerics
        # are exactly the quantized kernel's while the int8/fp8 Mosaic
        # score path lands; the tuner's VMEM model (tune/candidates.py)
        # prices the 1-byte kv residency so committed tables stay
        # forward-compatible.
        q = _wire_ste(q, qnt)
        k = _wire_ste(k, qnt)
        if fam == "resident" and k.shape[1] > MAX_KERNEL_SEQ:
            # the cost model legalizes resident past the bf16 cap on
            # the strength of the 1-byte kv stream, but the SIMULATED
            # execution still runs the full-width bf16 kernel — let the
            # sequence rule pick the executable family until the real
            # quantized kernel lands (record_final_flash_blocks states
            # what actually ran)
            fam = None
    block_q = _pick_block(q.shape[1], bq, kind="q")
    block_k = _pick_block(k.shape[1], bk, kind="k")
    # the record must state what actually runs: the post-halving tiles
    # AND the post-dispatch family (fam=None means the seq-length rule
    # decides, which resolve_flash could not know)
    record_final_flash_blocks(
        block_q, block_k, kvgrid=_use_kvgrid(k.shape[1], fam)
    )
    # kernels run in (B, N, S, H)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if return_lse:
        ot, lse = _flash_attention_lse_bnsh(
            qt, kt, vt, scale, causal, block_q, block_k, interpret, fam
        )
        return jnp.swapaxes(ot, 1, 2), jnp.swapaxes(lse, 1, 2)
    ot = _flash_attention_bnsh(
        qt, kt, vt, scale, causal, block_q, block_k, interpret, fam
    )
    return jnp.swapaxes(ot, 1, 2)
