"""Mamba2 selective scan — chunked SSD (state-space dual) formulation.

Replaces the mamba_ssm CUDA/Triton selective-scan kernels the reference
depends on (ref:main_training_mamba.py:8-13, config ssm_cfg layer=Mamba2
at ref:config_utils.py:162-185) with a TPU-native implementation.

The SSD algorithm re-expresses the per-token recurrence

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t x_t^T        (state (H, P, N))
    y_t = C_t . h_t + D * x_t

as chunked matmuls: inside a chunk the output is a masked (L, L)
attention-like product, and only one (P, N) state per head crosses chunk
boundaries via a short `lax.scan` over chunks (checkpointed body, fp32
state — `residual_in_fp32`-style numerics, ref:config_utils.py:181-183).
The intra-chunk hot path has two implementations selected by the
``kernel`` arg: group-factored XLA einsums (default; also the backward
for the kernel path) and a Pallas kernel (``"pallas"``) that keeps each
head's (L, L) decay/score product entirely in VMEM.

Shapes: x (B, S, H, P), dt (B, S, H) (post-softplus), A (H,) negative,
Bm/Cm (B, S, G, N) with H % G == 0.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fms_fsdp_tpu.obs.scopes import scoped

from fms_fsdp_tpu.ops.flash_attention import NEG_INF


def _segsum(a):
    """a: (..., L) -> (..., L, L) with out[i, j] = sum(a[j+1 .. i]),
    -inf above the diagonal (i < j)."""
    L = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]  # sum(a[j+1..i]) for i>=j
    mask = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (L, L), 1
    )
    return jnp.where(mask, diff, -jnp.inf)


def _fused_kernel(
    cum_ref, dt_ref, x_ref, B_ref, C_ref, y_ref, cb_ref, state_ref, *, R
):
    """Whole-sequence fused SSD: intra-chunk matmuls AND the inter-chunk
    recurrence in one kernel.

    Grid is (batch, group, chunk, head-in-group) with the chunk/head dims
    sequential: each head's (N, P) fp32 state lives in persistent VMEM
    scratch (``state_ref``, one slot per group member) and is carried
    across the chunk sweep — the round-2 design ran one pallas_call per
    chunk under ``lax.scan`` and paid a head-major relayout of every
    operand per chunk plus the scan/dispatch overhead; measured 2x
    slower than the XLA einsums (round 2). Fusing the scan
    into the grid removes both, and the (L, L) decay/score product still
    never leaves VMEM.

    Operands arrive head-major — x (B, H, S, P), B/C (B, G, S, N), and
    cum/dt (B, H, 1, S) where cum is the *chunk-local* cumsum of the
    per-token log-decay a (precomputed host-side: cumsum has no Pallas
    TPU lowering) — so every block's trailing two dims are whole or
    (8, 128)-divisible (the natural (B, L, H, P) layout puts a size-1
    head dim second-to-last and fails to lower; r2 hard-won fact).

    C@B^T is shared by every head in a GQA group; heads walk fastest, so
    it is computed once per (b, g, chunk) into ``cb_ref`` and reused by
    the group's other R-1 heads (the B/C input blocks themselves are
    fetched once per chunk — their index map is constant across heads).
    """
    L = x_ref.shape[2]
    ci = pl.program_id(2)
    r = pl.program_id(3)
    cum = cum_ref[0, 0]  # (1, L) fp32, chunk-local cumsum
    dt = dt_ref[0, 0]  # (1, L) fp32
    x = x_ref[0, 0]  # (L, P) input dtype
    B = B_ref[0, 0]  # (L, N)
    C = C_ref[0, 0]  # (L, N)
    od = x.dtype

    cum_col = jnp.transpose(cum)  # (L, 1)
    seg = cum_col - cum  # (L, L): cum_i - cum_j
    mask = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= (
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    )
    decay = jnp.exp(jnp.where(mask, seg, NEG_INF))

    @pl.when(r == 0)
    def _():
        cb_ref[...] = jax.lax.dot_general(
            C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (L, L)

    @pl.when(ci == 0)
    def _():
        state_ref[pl.ds(r, 1)] = jnp.zeros_like(state_ref[pl.ds(r, 1)])

    w = cb_ref[...] * decay * dt  # dt broadcasts over rows (j axis)
    y = jax.lax.dot_general(
        w.astype(od), x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (L, P) intra-chunk

    # inter-chunk output: exp(cum_i) * C_i . s_prev
    s_prev = state_ref[pl.ds(r, 1)][0]  # (N, P) fp32
    y = y + jnp.exp(cum_col) * jax.lax.dot_general(
        C,
        s_prev.astype(od),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # state update: s_new = exp(total) * s_prev + B^T (x * decay-to-end)
    total = cum[:, L - 1 :]  # (1, 1)
    rdec = (jnp.exp(total - cum) * dt).astype(od)  # (1, L)
    xs = x * jnp.transpose(rdec)  # (L, P)
    contrib = jax.lax.dot_general(
        B, xs, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (N, P)
    state_ref[pl.ds(r, 1)] = (jnp.exp(total) * s_prev + contrib)[None]

    y_ref[0, 0] = y


def _intra_and_states_xla(xc, dtc, ac, Bc, Cc, G):
    """Intra-chunk output + chunk state contribution, group-factored XLA
    einsums (the backward-pass / fallback path)."""
    Bsz, L, H, P = xc.shape
    R = H // G
    N = Bc.shape[-1]

    cum = jnp.cumsum(ac, axis=1)  # (B, L, H)
    total = cum[:, -1:, :]  # (B, 1, H)

    CB = jnp.einsum(
        "blgn,bmgn->blmg", Cc, Bc, preferred_element_type=jnp.float32
    )  # (B, L, L, G) fp32
    seg = _segsum(jnp.moveaxis(ac.reshape(Bsz, L, G, R), 1, -1))  # (B,G,R,L,L)
    w = CB[:, :, :, :, None] * jnp.moveaxis(
        jnp.exp(seg), (1, 2), (3, 4)
    )  # (B, L, L, G, R) fp32
    w = w * dtc.reshape(Bsz, 1, L, G, R)
    y = jnp.einsum(
        "blmgr,bmgrp->blgrp",
        w.astype(xc.dtype),
        xc.reshape(Bsz, L, G, R, P),
        preferred_element_type=jnp.float32,
    ).reshape(Bsz, L, H, P)

    r = jnp.exp(total - cum) * dtc  # (B, L, H) fp32
    xs = r.reshape(Bsz, L, G, R, 1).astype(xc.dtype) * xc.reshape(
        Bsz, L, G, R, P
    )
    states = jnp.einsum(
        "blgn,blgrp->bgrpn", Bc, xs, preferred_element_type=jnp.float32
    ).reshape(Bsz, H, P, N)
    return y, states


def _ssd_core_pallas_fwd(x, dtf, a, Bm, Cm, L, interpret):
    """Fused whole-sequence forward. x (B, S, H, P) input dtype; dtf/a
    (B, S, H) fp32; Bm/Cm (B, S, G, N) input dtype. Returns y (B, S, H, P)
    fp32 (no D term)."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    R = H // G
    C = S // L

    # chunk-local cumsum of the log-decay, then head-major views (one
    # relayout for the whole sequence — not one per chunk)
    cum = jnp.cumsum(a.reshape(Bsz, C, L, H), axis=2).reshape(Bsz, S, H)
    cum_rows = jnp.moveaxis(cum, 1, 2)[:, :, None, :]  # (B, H, 1, S) fp32
    dt_rows = jnp.moveaxis(dtf, 1, 2)[:, :, None, :]
    xh = jnp.moveaxis(x, 1, 2)  # (B, H, S, P)
    Bh = jnp.moveaxis(Bm, 1, 2)  # (B, G, S, N)
    Ch = jnp.moveaxis(Cm, 1, 2)

    y = pl.pallas_call(
        functools.partial(_fused_kernel, R=R),
        grid=(Bsz, G, C, R),
        in_specs=[
            pl.BlockSpec((1, 1, 1, L), lambda b, g, ci, r, R=R: (b, g * R + r, 0, ci)),
            pl.BlockSpec((1, 1, 1, L), lambda b, g, ci, r, R=R: (b, g * R + r, 0, ci)),
            pl.BlockSpec((1, 1, L, P), lambda b, g, ci, r, R=R: (b, g * R + r, ci, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, g, ci, r: (b, g, ci, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, g, ci, r: (b, g, ci, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, L, P), lambda b, g, ci, r, R=R: (b, g * R + r, ci, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, S, P), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((L, L), jnp.float32),  # shared C@B^T per (b,g,chunk)
            pltpu.VMEM((R, N, P), jnp.float32),  # per-head carried state
        ],
        compiler_params=pltpu.CompilerParams(
            # state/cb scratch carry across (chunk, head) — sequential;
            # batch/group cells are independent
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(cum_rows, dt_rows, xh, Bh, Ch)
    return jnp.moveaxis(y, 1, 2)  # (B, S, H, P) fp32


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_core_pallas(x, dtf, a, Bm, Cm, L, interpret):
    return _ssd_core_pallas_fwd(x, dtf, a, Bm, Cm, L, interpret)


def _ssd_core_pallas_fwd_rule(x, dtf, a, Bm, Cm, L, interpret):
    out = _ssd_core_pallas_fwd(x, dtf, a, Bm, Cm, L, interpret)
    return out, (x, dtf, a, Bm, Cm)


def _ssd_core_pallas_bwd_rule(L, interpret, res, cot):
    # backward recomputes through the XLA formulation — the checkpointed
    # chunk scan re-materializes one chunk's (L, L)-per-head
    # intermediates at a time; exact same math as the kernel
    x, dtf, a, Bm, Cm = res
    _, vjp = jax.vjp(
        lambda *args: _ssd_core_xla(*args, L), x, dtf, a, Bm, Cm
    )
    return vjp(cot)


_ssd_core_pallas.defvjp(_ssd_core_pallas_fwd_rule, _ssd_core_pallas_bwd_rule)


def _state_contribution(Cc, state, cum, G):
    """exp(cum)-decayed contribution of a carried state to the outputs:
    Cc (B, T, G, N) operand dtype, state (B, H, P, N) fp32, cum (B, T, H)
    fp32 (inclusive cumsum of a) -> (B, T, H, P) fp32. Shared by the
    chunk body's inter-chunk term and the context-parallel initial-state
    correction — their algebra (including the operand-dtype cast feeding
    the matmul) must stay identical for cp/single-device parity."""
    Bsz, T, G_, N = Cc.shape
    H = cum.shape[-1]
    R = H // G
    P = state.shape[-2]
    return (
        jnp.exp(cum).reshape(Bsz, T, G, R, 1)
        * jnp.einsum(
            "btgn,bgrpn->btgrp",
            Cc,
            state.reshape(Bsz, G, R, P, N).astype(Cc.dtype),
            preferred_element_type=jnp.float32,
        )
    ).reshape(Bsz, T, H, P)


def _ssd_chunk(s_prev, xc, dtc, ac, Bc, Cc, G):
    """One chunk of the SSD scan (XLA formulation; also the recompute
    backward of the fused Pallas kernel). Intra-chunk quadratic term and
    state contribution via group-factored einsums (heads carried as
    (G, R) dot_general batching — no head-repeated (L, H, N) or
    (L, L, H) tensor, the round-1 memory hog).

    Mixed precision mirrors the mamba_ssm CUDA kernels: matmul operands
    stay in the input dtype (bf16 under training — fp32 MXU matmuls run
    ~8x slower) with fp32 accumulation; the decay statistics, dt scaling,
    and the carried state are fp32.

    s_prev (B, H, P, N) fp32; xc (B, L, H, P) input dtype; dtc/ac
    (B, L, H) fp32; Bc/Cc (B, L, G, N) input dtype.
    Returns (y_c (B, L, H, P) fp32, s_new fp32).
    """
    Bsz, L, H, P = xc.shape
    R = H // G
    N = Bc.shape[-1]
    od = xc.dtype  # matmul operand dtype
    f32 = jnp.float32

    cum = jnp.cumsum(ac, axis=1)  # (B, L, H)
    total = cum[:, -1:, :]  # (B, 1, H)

    y, states = _intra_and_states_xla(xc, dtc, ac, Bc, Cc, G)

    # inter-chunk output: exp(cum_i) * C_i . s_prev, grouped over (b, g)
    y = y + _state_contribution(Cc, s_prev, cum, G)

    # state update: s_new = exp(total) * s_prev + chunk state contribution
    s_new = jnp.exp(total[:, 0, :])[:, :, None, None] * s_prev + states
    return y, s_new


@scoped("ssd_scan")
def ssd_scan(
    x, dt, A, Bm, Cm, D=None, chunk_size: int = 256, kernel: str = "auto",
    mesh=None,
):
    """Chunked selective scan: ``lax.scan`` over chunks with the fp32
    state carried across chunk boundaries; the chunk body is checkpointed
    so the backward pass recomputes one chunk's (L, L)-per-head
    intermediates at a time instead of saving them for the whole sequence.
    Returns y with x's shape, computed in fp32, cast back to x.dtype.

    ``mesh`` must be passed when the computation is jitted over a
    >1-device mesh AND the Pallas kernel is requested: a Mosaic kernel
    cannot be partitioned by GSPMD, so the fused core then runs
    per-device under shard_map with the batch over the data axes (the
    context-axis case is ``ssd_scan_cp``'s job). The XLA core needs no
    wrapping — GSPMD partitions it fine."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert kernel in ("auto", "reference", "xla", "pallas"), (
        f"unknown ssd kernel {kernel!r}"
    )
    if kernel == "reference":
        # sequential per-token recurrence — the exact math the serving
        # families' recurrent decode step replays one token at a time
        # (serve/families/mamba.py), which is what makes the dense
        # full-forward argmax walk a *bitwise* parity anchor for it
        # (tests/test_serving_families.py). Never the training path: the
        # S-step scan is the O(S) latency the chunked form exists to avoid.
        return ssd_scan_reference(x, dt, A, Bm, Cm, D)
    # chunk length: the tuning table may override the config's static
    # value (kernel_tuning="auto"); with tuning off (or no legal entry)
    # this is exactly min(chunk_size, S) — today's behavior
    from fms_fsdp_tpu.tune.lookup import resolve_ssd_chunk

    L = resolve_ssd_chunk(
        x.shape, G, N, str(x.dtype), requested=min(chunk_size, S)
    )
    assert S % L == 0, f"seq len {S} must be a multiple of chunk {L}"
    C = S // L

    dtf = dt.astype(jnp.float32)
    a = dtf * A.astype(jnp.float32)[None, None, :]  # (B, S, H), <= 0

    # "auto" resolves to the XLA formulation until the fused kernel is
    # re-measured on chip (the r2 per-chunk kernel measured 2x slower
    # than the einsums; the fused whole-sequence kernel above removes
    # the per-chunk relayouts + scan overhead it paid). The fused
    # kernel's v5e lowering is compiled on every test run
    # (tests/test_aot_compile.py, fwd+bwd), so the r2 "never lowered"
    # failure class cannot recur silently; the on-chip race that would
    # flip this default has not been run (ROADMAP D3(a)).
    mode = "xla" if kernel == "auto" else kernel

    if mode == "pallas":
        from fms_fsdp_tpu.ops.pallas_mode import interpret_default

        interpret = interpret_default()
        if mesh is not None and mesh.size > 1:
            from jax.sharding import PartitionSpec as P_

            from fms_fsdp_tpu.parallel.mesh import AXIS_TENSOR, DATA_AXES
            from fms_fsdp_tpu.parallel.sharding import resolve_spec

            # batch over the data axes, heads/groups over the tensor
            # axis — the per-shard head->group mapping h // (H/G) stays
            # contiguous when BOTH H and G divide the tensor extent;
            # when only one does, a split would mispair them, so
            # replicate the head dims (same guard as _flash_sharded)
            s_x = resolve_spec(
                P_(DATA_AXES, None, AXIS_TENSOR, None), x.shape, mesh
            )
            s_dt = resolve_spec(
                P_(DATA_AXES, None, AXIS_TENSOR), dtf.shape, mesh
            )
            s_bc = resolve_spec(
                P_(DATA_AXES, None, AXIS_TENSOR, None), Bm.shape, mesh
            )
            if s_x[2] != s_bc[2]:
                s_x = P_(s_x[0], None, None, None)
                s_dt = P_(s_dt[0], None, None)
                s_bc = P_(s_bc[0], None, None, None)

            def body(xl, dtl, al, Bl, Cl):
                return _ssd_core_pallas(xl, dtl, al, Bl, Cl, L, interpret)

            y = jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(s_x, s_dt, s_dt, s_bc, s_bc),
                out_specs=s_x,
                check_vma=False,
            )(x, dtf, a, Bm, Cm)
        else:
            y = _ssd_core_pallas(x, dtf, a, Bm, Cm, L, interpret)
    else:
        y = _ssd_core_xla(x, dtf, a, Bm, Cm, L)

    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)

    return y.astype(x.dtype)


def _ssd_core_xla(
    x, dtf, a, Bm, Cm, L, return_state: bool = False, init=None
):
    """Checkpointed chunk scan over the XLA einsum formulation.
    Returns y (B, S, H, P) fp32 (no D term); with ``return_state`` also
    the final carried state (B, H, P, N) fp32 — the context-parallel
    wrapper passes it across devices. ``init``: the state to go on from
    (zeros when None; ops/lightning_attention.py carries a prompt's state
    from chunk to chunk through it)."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    C = S // L

    # chunked views, chunk axis leading for the scan; matmul operands stay
    # in the input dtype, decay stats in fp32
    xc = jnp.moveaxis(x.reshape(Bsz, C, L, H, P), 1, 0)
    dtc = jnp.moveaxis(dtf.reshape(Bsz, C, L, H), 1, 0)
    ac = jnp.moveaxis(a.reshape(Bsz, C, L, H), 1, 0)
    Bc = jnp.moveaxis(Bm.reshape(Bsz, C, L, G, N), 1, 0)
    Cc = jnp.moveaxis(Cm.reshape(Bsz, C, L, G, N), 1, 0)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(s, inp):
        y_c, s_new = _ssd_chunk(s, *inp, G)
        return s_new, y_c

    if init is None:
        init = jnp.zeros((Bsz, H, P, N), jnp.float32)
    s_fin, ys = lax.scan(body, init, (xc, dtc, ac, Bc, Cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S, H, P)
    if return_state:
        return y, s_fin
    return y


@scoped("ssd_scan_cp")
def ssd_scan_cp(
    x, dt, A, Bm, Cm, D=None, *, mesh, chunk_size: int = 256, kernel: str = "auto"
):
    """Context-parallel chunked SSD: S sharded over the mesh's context
    axis, state passed explicitly across devices — long context for the
    Mamba family the way ring attention provides it for Llama (the
    reference has no context parallelism at all; without this, GSPMD
    partitions the chunk scan by gathering the sequence).

    Correctness rests on the linearity of the recurrence in the carried
    state: each device runs its local chunk scan with ZERO initial state
    (producing y0 and its final state Z_d), the per-device true initial
    state is the tiny linear recurrence

        IN_0 = 0;  IN_d = T_{d-1} * IN_{d-1} + Z_{d-1}

    over total local decays T_d = exp(sum_local a) (an unrolled cp-step
    loop over all_gather'd (Z, T) pairs — cp is small), and the initial
    state's contribution to outputs is the same grouped einsum the chunk
    body uses for its inter-chunk term:  y_t += exp(cumsum_t a) * C_t . IN.
    Differentiable end-to-end (shard_map + all_gather transpose); the
    local scan keeps its checkpointed body. The local core is always the
    XLA formulation — ``kernel`` is accepted for signature parity with
    ``ssd_scan`` but "pallas" does not apply here (and "auto" resolves
    to XLA on the single-device path too, by chip measurement).
    """
    from fms_fsdp_tpu.parallel.mesh import AXIS_CONTEXT, DATA_AXES
    from fms_fsdp_tpu.parallel.sharding import resolve_spec
    from jax.sharding import PartitionSpec as P

    cp = mesh.shape[AXIS_CONTEXT]
    if cp == 1:
        # no context axis: the plain path honors the kernel request in
        # full (including an explicit 'pallas', shard_map-wrapped there
        # if the mesh still spans devices on other axes)
        return ssd_scan(
            x, dt, A, Bm, Cm, D, chunk_size=chunk_size, kernel=kernel,
            mesh=mesh,
        )
    if kernel == "pallas":
        # don't silently relabel a benchmark: an explicit 'pallas' request
        # reaching the cp path still runs the XLA core under the context
        # axis (ADVICE r4) — warn so comparisons stay honest
        import warnings

        warnings.warn(
            "ssd_scan_cp: kernel='pallas' has no cp implementation; "
            "running the XLA core under the context axis",
            stacklevel=2,
        )
    S, G = x.shape[1], Bm.shape[2]
    assert S % cp == 0, f"context axis ({cp}) must divide sequence {S}"
    L = min(chunk_size, S // cp)
    assert (S // cp) % L == 0, (
        f"local sequence {S // cp} must be a multiple of chunk {L}"
    )
    od = x.dtype
    f32 = jnp.float32

    spec_x = resolve_spec(P(DATA_AXES, AXIS_CONTEXT, None, None), x.shape, mesh)
    spec_dt = P(spec_x[0], AXIS_CONTEXT, None)
    spec_bc = resolve_spec(
        P(spec_x[0], AXIS_CONTEXT, None, None), Bm.shape, mesh
    )

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec_x, spec_dt, P(None), spec_bc, spec_bc),
        out_specs=spec_x,
        check_vma=False,
    )
    def inner(x, dt, A, Bm, Cm):
        dtf = dt.astype(f32)
        a = dtf * A.astype(f32)[None, None, :]
        y0, z_fin = _ssd_core_xla(x, dtf, a, Bm, Cm, L, return_state=True)
        t_total = jnp.exp(jnp.sum(a, axis=1))  # (b, H) local decay product

        zs = lax.all_gather(z_fin, AXIS_CONTEXT)  # (cp, b, H, P, N)
        ts = lax.all_gather(t_total, AXIS_CONTEXT)  # (cp, b, H)
        idx = lax.axis_index(AXIS_CONTEXT)
        carry = jnp.zeros_like(z_fin)
        for d in range(cp - 1):  # unrolled: reverse-differentiable
            upd = ts[d][..., None, None] * carry + zs[d]
            carry = jnp.where(d < idx, upd, carry)

        # initial-state contribution to every local position (same
        # helper as the chunk body's inter-chunk term — shared algebra
        # is what the parity argument rests on)
        cum = jnp.cumsum(a, axis=1)  # (b, s_loc, H)
        return (y0 + _state_contribution(Cm, carry, cum, G)).astype(f32)

    y = inner(x, dt, A, Bm, Cm)
    if D is not None:  # skip-connection term, elementwise (GSPMD-sharded)
        y = y + D.astype(f32)[None, None, :, None] * x.astype(f32)
    return y.astype(od)


def ssd_scan_reference(x, dt, A, Bm, Cm, D=None):
    """Sequential per-token recurrence (ground truth for tests)."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2)
    Cf = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2)
    Af = A.astype(jnp.float32)

    def step(h, inp):
        xt, dtt, Bt, Ct = inp  # (B,H,P), (B,H), (B,H,N), (B,H,N)
        h = h * jnp.exp(dtt * Af)[:, :, None, None] + jnp.einsum(
            "bh,bhn,bhp->bhpn", dtt, Bt, xt
        )
        y = jnp.einsum("bhn,bhpn->bhp", Ct, h)
        return h, y

    init = jnp.zeros((Bsz, H, P, N), jnp.float32)
    _, ys = lax.scan(
        step,
        init,
        (
            jnp.moveaxis(xf, 1, 0),
            jnp.moveaxis(dtf, 1, 0),
            jnp.moveaxis(Bf, 1, 0),
            jnp.moveaxis(Cf, 1, 0),
        ),
    )
    y = jnp.moveaxis(ys, 0, 1)
    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :, None] * xf
    return y.astype(x.dtype)


@scoped("causal_conv1d")
def causal_conv1d(x, weight, bias=None, activation: str = "silu", init=None):
    """Depthwise causal conv over (B, S, C) with kernel (C, W), the
    mamba_ssm causal_conv1d equivalent. ``init`` (B, W-1, C) is the
    inputs that came before ``x`` (a sequence taken up where an earlier
    call left off); without it they are zeros.

    Expressed as W shifted fused multiply-adds instead of a grouped
    ``lax.conv``: XLA lowers a feature_group_count==C conv terribly on TPU
    (~29ms fwd+bwd per mamba layer at 9.8b shapes vs a few ms for the
    shifts, round-2 chip runs). The pad stays in the
    input dtype — materializing it in fp32 doubles the HBM traffic and
    measured ~2x slower; the per-slice upcast fuses into the multiply-add
    loop."""
    B, S, Cch = x.shape
    W = weight.shape[-1]
    wf = weight.astype(jnp.float32)
    if init is None:
        xt = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    else:
        xt = jnp.concatenate([init.astype(x.dtype), x], axis=1)
    out = sum(
        lax.dynamic_slice_in_dim(xt, w, S, axis=1).astype(jnp.float32)
        * wf[None, None, :, w]
        for w in range(W)
    )
    if bias is not None:
        out = out + bias.astype(jnp.float32)[None, None, :]
    if activation == "silu":
        out = jax.nn.silu(out)
    return out.astype(x.dtype)
