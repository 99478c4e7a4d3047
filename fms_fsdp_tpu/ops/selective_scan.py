"""Mamba-1 selective scan: a decay per (channel, state) pair.

``ops/ssd.py`` is Mamba-2's scan: one scalar decay per head, so a chunk
is a matrix product. Mamba-1 (Gu & Dao 2023, the mixer of the Jamba
hybrids) has ``A`` of shape (d_inner, N) and an input-dependent ``dt``
per channel, so nothing contracts: per channel c and state n, in float32,

    s_t[n, c] = exp(dt_t[c] A[n, c]) s_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n C_t[n] s_t[n, c] + D[c] u_t[c]

Three forms of that one arithmetic (the sum over n runs n = 0, 1, ... in
each, so they differ by the compiler's fusing alone):

- ``selective_scan_reference``: a ``lax.scan`` over positions. The CPU
  tests' anchor, the form that is differentiable, and what runs wherever
  the kernel does not.
- ``selective_scan_step``: one position (recurrent decode).
- ``selective_scan_kernel``: a Pallas kernel for a whole sequence. The
  grid is (row, block of 1024 channels, chunk of positions); the
  (N, 1024) float32 state stays in VMEM across the chunk walk and is
  written once, at the end. The (S, d_inner, N) history that the naive
  form writes (328 KB a position at Jamba's widths) never exists. Channels
  lie as whole (8, 128) tiles, one per state index n; a position's
  ``B_t[n]`` and ``C_t[n]`` are scalars read from SMEM, so every vector
  operation works on full registers and nothing crosses lanes.

The state is laid out ``(rows, N, d_inner)``: channels minor. The other
order would put 16 states on a 128-lane tile and take eight times the
memory and the bandwidth on a TPU.

A row's state freezes past its length when ``dt`` is zero there (decay
exp(0) = 1, input 0: exact), so ragged batches need nothing of the scan:
``freeze_past`` zeroes ``dt`` and the caller reads the final state.

``selective_scan`` picks the form from the shapes and the platform; no
option selects it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fms_fsdp_tpu.models.sequence_prefill import largest_divisor
from fms_fsdp_tpu.ops.pallas_mode import interpret_default

LANES = 128
SUBLANES = 8
MAX_CHUNK = 256  # positions a grid step walks: 1 MB of float32 a block


def freeze_past(dt, lengths):
    """``dt`` (B, S, C) with every position at or past a row's length set
    to 0, which freezes that row's state from there on."""
    pos = jnp.arange(dt.shape[1], dtype=jnp.int32)
    return jnp.where(pos[None, :, None] < lengths[:, None, None], dt, 0.0)


def selective_scan_step(u, dt, A, B, C, D, h):
    """One position. u, dt (rows, C) float32; A (N, C); B, C (rows, N);
    D (C,); h (rows, N, C) float32 -> (y (rows, C), new h)."""
    dA = jnp.exp(dt[:, None, :] * A[None])
    h = dA * h + (dt * u)[:, None, :] * B[:, :, None]
    y = D[None] * u
    for n in range(A.shape[0]):
        y = y + h[:, n] * C[:, n, None]
    return y, h


def selective_scan_reference(u, dt, A, B, C, D, h0):
    """The recurrence as a ``lax.scan`` over positions. u, dt (rows, S, C)
    float32; A (N, C); B, C (rows, S, N); D (C,); h0 (rows, N, C) ->
    (y (rows, S, C) float32, final state)."""

    def step(h, inp):
        u_t, dt_t, B_t, C_t = inp
        y, h = selective_scan_step(u_t, dt_t, A, B_t, C_t, D, h)
        return h, y

    seq_major = [jnp.moveaxis(x, 1, 0) for x in (u, dt, B, C)]
    h, y = lax.scan(step, h0, tuple(seq_major))
    return jnp.moveaxis(y, 0, 1), h


def _scan_kernel(b_ref, c_ref, u_ref, dt_ref, a_ref, d_ref, h0_ref,
                 y_ref, hT_ref, h_scr, *, T, N):
    """One (row, channel block, chunk) cell: T positions over a block of
    channels laid out (rows of 128). The state crosses chunks in
    ``h_scr``; B and C of the chunk are flat in SMEM, position-major."""
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _():
        h_scr[...] = h0_ref[0]

    A = [a_ref[n] for n in range(N)]
    Dv = d_ref[...]

    def body(t, h):
        dt = dt_ref[0, t]
        u = u_ref[0, t]
        dtu = dt * u
        y = Dv * u
        new = []
        for n in range(N):
            hn = jnp.exp(dt * A[n]) * h[n] + dtu * b_ref[0, t * N + n]
            y = y + hn * c_ref[0, t * N + n]
            new.append(hn)
        y_ref[0, t] = y
        return tuple(new)

    h = lax.fori_loop(0, T, body, tuple(h_scr[n] for n in range(N)))
    for n in range(N):
        h_scr[n] = h[n]

    @pl.when(s == pl.num_programs(2) - 1)
    def _():
        hT_ref[0] = h_scr[...]


def kernel_supports(channels: int) -> bool:
    """The kernel takes channels in whole 128-lane rows, and in blocks of
    8 rows or all of them."""
    if channels % LANES:
        return False
    rows = channels // LANES
    return rows % SUBLANES == 0 or rows < SUBLANES


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_kernel(u, dt, A, B, C, D, h0, interpret=False):
    """Same arguments and results as :func:`selective_scan_reference`."""
    rows, S, Cn = u.shape
    N = A.shape[0]
    R = Cn // LANES
    Rb = SUBLANES if R % SUBLANES == 0 else R
    T = largest_divisor(S, MAX_CHUNK)
    f32 = jnp.float32

    def tiles(x):  # (..., Cn) -> (..., R, 128)
        return x.astype(f32).reshape(x.shape[:-1] + (R, LANES))

    flat = lambda x: x.astype(f32).reshape(rows, S * N)  # noqa: E731
    seq = pl.BlockSpec((1, T, Rb, LANES), lambda b, j, s: (b, s, j, 0))
    smem = pl.BlockSpec(
        (1, T * N), lambda b, j, s: (b, s), memory_space=pltpu.SMEM)
    state = pl.BlockSpec((1, N, Rb, LANES), lambda b, j, s: (b, 0, j, 0))
    y, hT = pl.pallas_call(
        functools.partial(_scan_kernel, T=T, N=N),
        grid=(rows, R // Rb, S // T),
        in_specs=[
            smem, smem, seq, seq,
            pl.BlockSpec((N, Rb, LANES), lambda b, j, s: (0, j, 0)),
            pl.BlockSpec((Rb, LANES), lambda b, j, s: (j, 0)),
            state,
        ],
        out_specs=[seq, state],
        out_shape=[
            jax.ShapeDtypeStruct((rows, S, R, LANES), f32),
            jax.ShapeDtypeStruct((rows, N, R, LANES), f32),
        ],
        scratch_shapes=[pltpu.VMEM((N, Rb, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(flat(B), flat(C), tiles(u), tiles(dt), tiles(A), tiles(D), tiles(h0))
    return y.reshape(rows, S, Cn), hT.reshape(rows, N, Cn)


def selective_scan(u, dt, A, B, C, D, h0):
    """The sequence form that fits the platform and the shapes: the
    kernel where Pallas compiles for the device (a TPU) and the channels
    tile, the ``lax.scan`` form elsewhere. All float32; the caller puts
    the ``ssm_scan`` scope around it."""
    if not interpret_default() and kernel_supports(u.shape[-1]):
        return selective_scan_kernel(u, dt, A, B, C, D, h0)
    return selective_scan_reference(u, dt, A, B, C, D, h0)
