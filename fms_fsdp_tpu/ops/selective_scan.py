"""Mamba-1 selective scan: a decay per (channel, state) pair.

``ops/ssd.py`` is Mamba-2's scan: one scalar decay per head, so a chunk
is a matrix product. Mamba-1 (Gu & Dao 2023, the mixer of the Jamba
hybrids) has ``A`` of shape (d_inner, N) and an input-dependent ``dt``
per channel, so nothing contracts: per channel c and state n, in float32,

    s_t[n, c] = exp(dt_t[c] A[n, c]) s_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n C_t[n] s_t[n, c] + D[c] u_t[c]

Four forms of that one arithmetic (the sum over n runs n = 0, 1, ... in
each, so they differ by the compiler's fusing alone):

- ``selective_scan_reference``: a ``lax.scan`` over positions. The CPU
  tests' anchor, the form that is differentiable, and what runs wherever
  the kernel does not.
- ``selective_scan_step``: one position (recurrent decode) in plain
  ``jnp``: the body of the form above, and what a decode step runs
  wherever the one-position kernel does not.
- ``selective_scan_kernel``: a Pallas kernel for a whole sequence. The
  grid is (row, block of 1024 channels, chunk of positions); the
  (N, 1024) float32 state stays in VMEM across the chunk walk and is
  written once, at the end. The (S, d_inner, N) history that the naive
  form writes (328 KB a position at Jamba's widths) never exists. Channels
  lie as whole (8, 128) tiles, one per state index n; a position's
  ``B_t[n]`` and ``C_t[n]`` are scalars read from SMEM, so every vector
  operation works on full registers and nothing crosses lanes.
- ``selective_scan_step_kernel``: a Pallas kernel for one position of
  every slot, **one pass over a layer's state where it lies**. It takes
  the slab as a decode program holds it, ``(layers, slots, N, d_inner)``
  or one layer's ``(slots, N, d_inner)``, aliased to its output, and the
  layer's index as an operand (scalar prefetch into the block index
  maps): no slice before it, no ``dynamic_update_slice`` behind it, and
  every layer of a program is one lowering. A decode step does nothing
  with the state but read and write it (12 vector operations on 8 bytes
  moved), so the bytes bound it and a cell has to be large against a grid
  step's 0.35-0.8 us (PR 47's readings): a cell is all the slots (up to
  ``STEP_SLOTS``) by 128 channels, a megabyte at 128 slots where a cell
  of the sequence kernel holds 64 KB (the sweep is beside
  ``STEP_SLOTS``: 582 GB/s at a megabyte a cell, 260 at 64 KB). The
  block's rows are a slot's N
  states one under the other, as the slab's are; state n of a group of
  slots is a strided read (Mosaic takes one from a block 128 lanes wide
  and no wider, which is why a cell is not several lane tiles across),
  so slots lie down a register, channels across it, and ``u``, ``dt``,
  ``y`` come as they are. ``B_t[n]``, ``C_t[n]`` and ``live`` are a
  column a slot, spread over the lanes once a block of slots. A slot
  that is not live keeps its state to the bit: the cell writes back what
  it read there (a select in registers; the ``jnp`` form's select reads
  old and new state from memory once more).

The state is laid out ``(rows, N, d_inner)``: channels minor. The other
order would put 16 states on a 128-lane tile and take eight times the
memory and the bandwidth on a TPU.

A row's state freezes past its length when ``dt`` is zero there (decay
exp(0) = 1, input 0: exact), so ragged batches need nothing of the scan:
``freeze_past`` zeroes ``dt`` and the caller reads the final state.

``selective_scan`` (a sequence) and ``selective_scan_slab_step`` (one
position) pick the form from the shapes and the platform; no option
selects it, and ``scan_step_form`` says which the second picks.
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
# The one-position kernel's cell: STEP_SLOTS slots (all of them, up to
# that) by 128 channels, read STEP_SUB slots a strided load. Measured on a
# v5e over the phi4flash slab, (9, 128, 16, 5120), nine calls, ms (PR 48,
# chip_scratch/scan_step_alone.py; the bytes need 1.02 at the 819 GB/s
# peak and 1.28 at the 590 GB/s a plain elementwise pass reaches):
#   slots a cell   128     64     32     16      8    | the jnp form
#   ms            1.295  1.360  1.611  2.143  2.899   |    2.595
# (1 MB, 0.5 MB, ... 64 KB a cell: a grid step's overhead shows from 32
# down); at 128 slots a cell, 8, 16, 32, 64 or 128 slots a load read
# 1.298, 1.295, 1.298, 1.297, 1.304: the fetch binds, not the vector unit.
# Jamba's 26 states of (16, 16, 5120), a cell all 16 slots: 0.706 alone
# where the jnp form alone takes 0.635, and 0.79 where 1.26 inside the
# decode step (the step 10.105 ms where 10.161).
STEP_SLOTS = 128
STEP_SUB = 32


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


def _step_kernel(layer_ref, live_ref, b_ref, c_ref, u_ref, dt_ref, a_ref,
                 d_ref, h_ref, y_ref, h_out_ref, keep_scr, b_scr, c_scr,
                 *, N, rows, sub):
    """One (block of slots, 128 channels) cell of one position. The state
    block lies as the slab does, a slot's N rows one under the other
    (``rows * N`` rows of 128 lanes), so state n of ``sub`` slots is a
    strided read: slots down the registers, channels across them, ``u``,
    ``dt`` and ``y`` as they come. ``B_t[n]``, ``C_t[n]`` and ``live`` are
    a column a slot; the first channel block of a block of slots spreads
    them over the lanes into scratch, state n of every slot together, for
    the others to read whole."""
    del layer_ref  # the block index maps read it

    @pl.when(pl.program_id(1) == 0)
    def _():
        keep_scr[...] = jnp.broadcast_to(live_ref[...], keep_scr.shape)
        for n in range(N):
            b_scr[n] = jnp.broadcast_to(b_ref[:, n:n + 1], (rows, LANES))
            c_scr[n] = jnp.broadcast_to(c_ref[:, n:n + 1], (rows, LANES))

    for g in range(0, rows, sub):
        of_group = pl.ds(g, sub)
        dt, u = dt_ref[of_group, :], u_ref[of_group, :]
        keep = keep_scr[of_group, :] != 0
        dtu = dt * u
        y = d_ref[...] * u
        for n in range(N):
            slots = pl.ds(g * N + n, sub, stride=N)
            h = h_ref[slots, :]
            decay = jnp.exp(dt * a_ref[n:n + 1, :])
            hn = decay * h + dtu * b_scr[n, of_group, :]
            y = y + hn * c_scr[n, of_group, :]
            h_out_ref[slots, :] = jnp.where(keep, hn, h)
        y_ref[of_group, :] = y


def step_kernel_supports(slots: int, states: int, channels: int) -> bool:
    """The one-position kernel takes channels in whole 128-lane rows, a
    slot's states in whole 8-sublane tiles (the slab is then read as it
    lies, no relayout), and slots in blocks of 8 or all of them."""
    return (
        channels % LANES == 0
        and states % SUBLANES == 0
        and (slots % SUBLANES == 0 or slots < SUBLANES)
    )


def step_blocks(slots: int):
    """(slots of a cell, slots of a strided read) of the one-position
    kernel."""
    if slots % SUBLANES:
        return slots, slots
    tiles = largest_divisor(slots // SUBLANES, STEP_SLOTS // SUBLANES)
    sub = largest_divisor(tiles, STEP_SUB // SUBLANES)
    return SUBLANES * tiles, SUBLANES * sub


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_step_kernel(u, dt, A, B, C, D, slab, layer=0, live=None,
                               interpret=False):
    """One position over a slab where it lies. u, dt (slots, C) float32;
    A (N, C); B, C (slots, N); D (C,); ``slab`` (layers, slots, N, C) or
    one layer's (slots, N, C), float32; ``layer`` which layer's state to
    step (an operand: every layer of a program is one lowering); ``live``
    (slots,) bool, all where None: a row that is not live keeps its state
    to the bit. -> (y (slots, C), the slab with that layer stepped: the
    same buffer where the caller donates it)."""
    S, Cn = u.shape
    N = A.shape[0]
    f32 = jnp.float32
    rows, sub = step_blocks(S)
    live = jnp.ones((S,), jnp.int32) if live is None else live
    cell = lambda i, j, layer: (i, j)  # noqa: E731
    per_slot = lambda i, j, layer: (i, 0)  # noqa: E731
    of_channels = lambda i, j, layer: (0, j)  # noqa: E731
    state = pl.BlockSpec(
        (None, rows * N, LANES), lambda i, j, layer: (layer[0], i, j))
    y, out = pl.pallas_call(
        functools.partial(_step_kernel, N=N, rows=rows, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S // rows, Cn // LANES),
            in_specs=[
                pl.BlockSpec((rows, 1), per_slot),
                pl.BlockSpec((rows, N), per_slot),
                pl.BlockSpec((rows, N), per_slot),
                pl.BlockSpec((rows, LANES), cell),
                pl.BlockSpec((rows, LANES), cell),
                pl.BlockSpec((N, LANES), of_channels),
                pl.BlockSpec((1, LANES), of_channels),
                state,
            ],
            out_specs=[pl.BlockSpec((rows, LANES), cell), state],
            scratch_shapes=[
                pltpu.VMEM((rows, LANES), jnp.int32),
                pltpu.VMEM((N, rows, LANES), f32),
                pltpu.VMEM((N, rows, LANES), f32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, Cn), f32),
            jax.ShapeDtypeStruct((slab.size // (S * N * Cn), S * N, Cn), f32),
        ],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan_step",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        live.astype(jnp.int32).reshape(S, 1),
        B.astype(f32), C.astype(f32), u.astype(f32), dt.astype(f32),
        A.astype(f32), D.astype(f32).reshape(1, Cn),
        slab.reshape(-1, S * N, Cn),
    )
    return y, out.reshape(slab.shape)


def selective_scan(u, dt, A, B, C, D, h0):
    """The sequence form that fits the platform and the shapes: the
    kernel where Pallas compiles for the device (a TPU) and the channels
    tile, the ``lax.scan`` form elsewhere. All float32; the caller puts
    the ``ssm_scan`` scope around it."""
    if not interpret_default() and kernel_supports(u.shape[-1]):
        return selective_scan_kernel(u, dt, A, B, C, D, h0)
    return selective_scan_reference(u, dt, A, B, C, D, h0)


def scan_step_form(slots: int, states: int, channels: int) -> str:
    """Which one-position form ``selective_scan_slab_step`` runs at these
    shapes here: ``"kernel"`` where Pallas compiles for the device (a
    TPU) and the shapes tile, ``"jnp"`` elsewhere."""
    if not interpret_default() and step_kernel_supports(
            slots, states, channels):
        return "kernel"
    return "jnp"


def selective_scan_slab_step(u, dt, A, B, C, D, slab, layer=None, live=None):
    """One position of every slot over the state a decode program holds.
    ``slab`` is the layer's state (slots, N, C) or, with ``layer``, the
    stacked (layers, slots, N, C) of which layer ``layer`` is stepped;
    with ``live`` (slots,) bool a row that is not live keeps its state.
    All float32. -> (y (slots, C), the slab after the step, shaped as it
    came). The kernel form updates the slab in place; the ``jnp`` form
    slices, steps, selects and writes back. The caller puts the
    ``ssm_scan`` scope around it."""
    stacked = layer is not None
    if scan_step_form(u.shape[0], A.shape[0], u.shape[1]) == "kernel":
        return selective_scan_step_kernel(
            u, dt, A, B, C, D, slab, layer if stacked else 0, live)
    h0 = slab[layer] if stacked else slab
    y, h = selective_scan_step(u, dt, A, B, C, D, h0)
    if live is not None:
        h = jnp.where(live[:, None, None], h, h0)
    return y, slab.at[layer].set(h) if stacked else h
