"""Ring attention: causal attention with the sequence sharded over the
"context" mesh axis.

Beyond-reference capability (the reference has no sequence/context
parallelism, SURVEY.md §2.b): each device holds S/cp query and kv chunks;
kv chunks rotate around the ring via ``lax.ppermute`` while every device
merges its queries' attention over each visiting chunk.

Chunk relations are decided at chunk granularity — a visiting chunk is
either fully visible (behind the local queries: plain non-causal flash),
the diagonal (standard causal flash), or fully in the future (skipped via
``lax.cond``, no compute). Each partial comes from the Pallas flash
kernel with its logsumexp exposed (flash_attention(return_lse=True)), so
per-step memory is O(S/cp * block) — the (S/cp)^2 score materialization
of the einsum path exists only as the small-shape fallback. Partials
merge exactly through lse:

    lse' = logaddexp(lse_a, lse_b)
    o'   = o_a * exp(lse_a - lse') + o_b * exp(lse_b - lse')

The backward is a ring of its own (custom VJP): residuals are only the
LOCAL q/k/v/out/lse chunks — O(S/cp) per device — and the kv chunks are
re-streamed around the ring with their dk/dv accumulators traveling
alongside, so after cp steps every chunk arrives home fully accumulated.
Per-step partial gradients use the flash dq/dkv kernels with the global
softmax stats (the FlashAttention decomposition makes partial gradients
exact given global lse/delta).

Composes with GQA and the tensor axis (heads split by shard_map).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from fms_fsdp_tpu.ops.flash_attention import (
    NEG_INF,
    _pick_block,
    flash_attention,
    flash_dkv,
    flash_dq,
)
from fms_fsdp_tpu.parallel.mesh import AXIS_CONTEXT, AXIS_TENSOR, DATA_AXES


def _scores(q, k, causal, scale):
    """(grouped q, scores) for the einsum fallback: scores
    (b, nkv, group, sq, sk) fp32, causal-masked for the diagonal chunk
    relation (fully-visible chunks pass causal=False)."""
    b, sq, nq, h = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    group = nq // nkv
    qg = q.reshape(b, sq, nkv, group, h)
    s = (
        jnp.einsum(
            "bqkgh,bskh->bkgqs", qg, k, preferred_element_type=jnp.float32
        )
        * scale
    )
    if causal:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    return qg, s


def einsum_partial(q, k, v, causal, scale):
    """Small-shape fallback: (o_norm, lse) via a materialized score matrix."""
    b, sq, nq, h = q.shape
    _, s = _scores(q, k, causal, scale)
    nkv = k.shape[2]
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(v.dtype), v)
    o = o.astype(jnp.float32) / jnp.maximum(l, 1e-30)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    # (b, nkv, group, sq, ...) -> (b, sq, nq, ...)
    o = jnp.moveaxis(o, 3, 1).reshape(b, sq, nq, h)
    lse = jnp.moveaxis(lse, 3, 1).reshape(b, sq, nq, 1)
    return o, lse


def merge_partial(carry, o, lse):
    """(running fp32 output, running lse) with one more partial over a
    disjoint kv set merged in, exactly:  lse' = logaddexp(lse_a, lse_b),
    o' = o_a exp(lse_a - lse') + o_b exp(lse_b - lse')."""
    acc, lse_run = carry
    lse_new = jnp.logaddexp(lse_run, lse)
    # fully-masked-so-far rows: keep weights finite
    w_run = jnp.exp(jnp.maximum(lse_run - lse_new, NEG_INF))
    w_new = jnp.exp(jnp.maximum(lse - lse_new, NEG_INF))
    return acc * w_run + o.astype(jnp.float32) * w_new, lse_new


def _einsum_partial_grads(q, k, v, do, lse, delta, causal, scale):
    """Small-shape fallback gradients of one partial given global stats.
    Returns (dq, dk, dv) in fp32, (B, S, N, H) layouts."""
    b, sq, nq, h = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    qg, s = _scores(q, k, causal, scale)
    dog = do.astype(jnp.float32).reshape(b, sq, nkv, group, h)
    stats = lambda t: jnp.moveaxis(  # noqa: E731  (b,sq,nq,1)->(b,nkv,g,sq,1)
        t.reshape(b, sq, nkv, group, 1), 1, 3
    )
    p = jnp.exp(s - stats(lse))  # (b, nkv, g, sq, sk) via (...,sq,1) bcast
    dp = jnp.einsum(
        "bqkgh,bskh->bkgqs", dog, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - stats(delta)) * scale
    dq = jnp.einsum(
        "bkgqs,bskh->bqkgh", ds, k.astype(jnp.float32)
    ).reshape(b, sq, nq, h)
    dk = jnp.einsum("bkgqs,bqkgh->bskh", ds, qg.astype(jnp.float32))
    dv = jnp.einsum("bkgqs,bqkgh->bskh", p, dog)
    return dq, dk, dv


def _flash_eligible(q_shape, kv_shape, cp: int) -> bool:
    """Local-chunk eligibility for the Pallas partials: the kernel's own
    supports() gate at the per-device shapes, on a backend that can run it
    (TPU, or CPU via interpret mode)."""
    from fms_fsdp_tpu.ops.flash_attention import supports

    b, s, nq, h = q_shape
    local_q = (b, s // cp, nq, h)
    local_kv = (kv_shape[0], kv_shape[1] // cp, kv_shape[2], kv_shape[3])
    return supports(local_q, local_kv) and jax.default_backend() in (
        "tpu",
        "cpu",
    )


def _bnsh(*arrs):
    return tuple(jnp.swapaxes(a, 1, 2) for a in arrs)


def ring_attention(q, k, v, mesh, *, causal: bool = True, scale=None):
    """q (B, S, Nq, H), k/v (B, S, Nkv, H) — S sharded over AXIS_CONTEXT."""
    scale = float(scale if scale is not None else q.shape[-1] ** -0.5)
    cp = mesh.shape[AXIS_CONTEXT]
    assert q.shape[1] % cp == 0, (
        f"context axis size ({cp}) must divide sequence length {q.shape[1]}"
    )
    from fms_fsdp_tpu.parallel.sharding import resolve_spec

    # batch/tensor dims that don't divide their mesh axes fall back to
    # replicated (the op's contract is the context axis; the others are
    # opportunistic)
    base = P(DATA_AXES, AXIS_CONTEXT, AXIS_TENSOR, None)
    spec_q = resolve_spec(base, q.shape, mesh)
    spec_kv = resolve_spec(base, k.shape, mesh)
    assert spec_q[1] == AXIS_CONTEXT and spec_kv[1] == AXIS_CONTEXT
    if spec_q[2] != spec_kv[2]:
        # q heads divide the tensor axis but kv heads don't (or vice
        # versa): a split would mispair GQA groups — replicate heads
        spec_q = P(spec_q[0], spec_q[1], None, None)
        spec_kv = P(spec_kv[0], spec_kv[1], None, None)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    use_flash = _flash_eligible(q.shape, k.shape, cp)
    from fms_fsdp_tpu.ops.pallas_mode import interpret_default

    interpret = interpret_default()
    s_local = q.shape[1] // cp
    bq = _pick_block(s_local, 512)
    bk = _pick_block(s_local, 512)

    def partial_fn(q_loc, k_cur, v_cur, diag: bool):
        if use_flash:
            # pin the same blocks the backward partials below use —
            # passing them explicitly also skips the tuning-table
            # lookup, so fwd and bwd ring steps always run the same
            # tiles/family (the per-ring-step local shapes would
            # otherwise nearest-match full-sequence table entries)
            return flash_attention(
                q_loc,
                k_cur,
                v_cur,
                causal=diag,
                scale=scale,
                block_q=bq,
                block_k=bk,
                interpret=interpret,
                return_lse=True,
            )
        return einsum_partial(q_loc, k_cur, v_cur, diag, scale)

    def partial_grads(qpack, k_cur, v_cur, diag: bool):
        if use_flash:
            # qpack carries the loop-invariant (B,N,S,H)-layout q/do/stats,
            # transposed ONCE outside the ring loop
            qt, dot, lset, deltat = qpack
            kt, vt = _bnsh(k_cur, v_cur)
            kw = dict(
                scale=scale, causal=diag, block_q=bq, block_k=bk,
                interpret=interpret,
            )
            # dq partials accumulate across ring steps: keep them fp32 so
            # per-step rounding doesn't compound
            dq = flash_dq(
                qt, kt, vt, dot, lset, deltat, out_dtype=jnp.float32, **kw
            )
            dk, dv = flash_dkv(qt, kt, vt, dot, lset, deltat, **kw)
            return (
                jnp.swapaxes(dq, 1, 2),
                jnp.swapaxes(dk, 1, 2),
                jnp.swapaxes(dv, 1, 2),
            )
        q_loc, do, lse, delta = qpack
        return _einsum_partial_grads(
            q_loc, k_cur, v_cur, do, lse, delta, diag, scale
        )

    lse_spec = P(spec_q[0], AXIS_CONTEXT, spec_q[2], None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv),
        out_specs=(spec_q, lse_spec),
        check_vma=False,
    )
    def fwd_inner(q, k, v):
        idx = lax.axis_index(AXIS_CONTEXT)
        b, s_loc, nq, h = q.shape

        def body(step, carry):
            acc, lse_run, k_cur, v_cur = carry
            src = (idx - step) % cp  # global chunk currently held

            def diag(_):
                o, lse = partial_fn(q, k_cur, v_cur, True)
                return merge_partial((acc, lse_run), o, lse)

            def visible(_):
                o, lse = partial_fn(q, k_cur, v_cur, False)
                return merge_partial((acc, lse_run), o, lse)

            def masked(_):
                return acc, lse_run

            if causal:
                # chunk relation decides everything: future chunks are
                # skipped outright, no per-element masks off the diagonal
                acc_n, lse_n = lax.cond(
                    src == idx,
                    diag,
                    lambda _: lax.cond(src < idx, visible, masked, None),
                    None,
                )
            else:
                acc_n, lse_n = visible(None)

            # rotate kv to the next device (last rotation restores state)
            k_cur = lax.ppermute(k_cur, AXIS_CONTEXT, perm)
            v_cur = lax.ppermute(v_cur, AXIS_CONTEXT, perm)
            return acc_n, lse_n, k_cur, v_cur

        acc = jnp.zeros((b, s_loc, nq, h), jnp.float32)
        lse0 = jnp.full((b, s_loc, nq, 1), NEG_INF, jnp.float32)
        acc, lse, _, _ = lax.fori_loop(0, cp, body, (acc, lse0, k, v))
        return acc.astype(q.dtype), lse

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv, spec_q, lse_spec, spec_q),
        out_specs=(spec_q, spec_kv, spec_kv),
        check_vma=False,
    )
    def bwd_inner(q, k, v, out, lse, do):
        idx = lax.axis_index(AXIS_CONTEXT)
        delta = jnp.sum(
            out.astype(jnp.float32) * do.astype(jnp.float32),
            axis=-1,
            keepdims=True,
        )
        # loop-invariant layouts: transpose once, not per ring step (XLA
        # does not hoist out of lax.cond branches)
        if use_flash:
            qpack = _bnsh(q, do) + _bnsh(lse, delta)
        else:
            qpack = (q, do, lse, delta)

        def body(step, carry):
            dq_acc, k_cur, v_cur, dk_cur, dv_cur = carry
            src = (idx - step) % cp

            def diag(_):
                return partial_grads(qpack, k_cur, v_cur, True)

            def visible(_):
                return partial_grads(qpack, k_cur, v_cur, False)

            def masked(_):
                return (
                    jnp.zeros_like(dq_acc),
                    jnp.zeros_like(dk_cur),
                    jnp.zeros_like(dv_cur),
                )

            if causal:
                dq_p, dk_p, dv_p = lax.cond(
                    src == idx,
                    diag,
                    lambda _: lax.cond(src < idx, visible, masked, None),
                    None,
                )
            else:
                dq_p, dk_p, dv_p = visible(None)

            dq_acc = dq_acc + dq_p
            # dk/dv accumulators travel WITH their kv chunk: after cp
            # rotations both are home, fully accumulated
            dk_cur = lax.ppermute(dk_cur + dk_p, AXIS_CONTEXT, perm)
            dv_cur = lax.ppermute(dv_cur + dv_p, AXIS_CONTEXT, perm)
            k_cur = lax.ppermute(k_cur, AXIS_CONTEXT, perm)
            v_cur = lax.ppermute(v_cur, AXIS_CONTEXT, perm)
            return dq_acc, k_cur, v_cur, dk_cur, dv_cur

        dq0 = jnp.zeros(q.shape, jnp.float32)
        dkv0 = jnp.zeros(k.shape, jnp.float32)
        dq, _, _, dk, dv = lax.fori_loop(
            0, cp, body, (dq0, k, v, dkv0, jnp.zeros_like(dkv0))
        )
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    @jax.custom_vjp
    def ring(q, k, v):
        out, _ = fwd_inner(q, k, v)
        return out

    def ring_fwd(q, k, v):
        out, lse = fwd_inner(q, k, v)
        return out, (q, k, v, out, lse)

    def ring_bwd(res, do):
        return bwd_inner(*res, do)

    ring.defvjp(ring_fwd, ring_bwd)
    return ring(q, k, v)
