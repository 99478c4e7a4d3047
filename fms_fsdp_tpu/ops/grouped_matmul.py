"""Forward grouped matmul: rows sorted by group, each group's rows times
its group's matrix (a Pallas TPU kernel; models/moe_held.py's prefill).

``x (M, k)`` holds ``sum(sizes)`` rows sorted by group, ``sizes (G,)`` of
them in each; ``stack (L, G, k, n)`` holds ``L`` layers of ``G`` matrices
and is handed over whole, layer ``l`` (a traced int) chosen by the block
index, so no layer's slice is copied out. Two stacks give
``silu(x w1) * (x w3)`` in one pass over the rows: both products in
float32, one cast.

The grid is ``(column tiles, meetings)``: a meeting is one (group, row
tile) pair, sorted by group, so a row tile that spans several groups is
met once by each and stores that group's rows alone. The schedule
(``schedule``) follows from the shapes:

- **k is whole.** A weight block is ``(k, tn)`` of one group and serves
  every meeting of its group while it is resident: it crosses HBM once a
  column tile, and there is no accumulator and no k axis.
- **A block is fetched a group ahead, by hand.** The weights stay in HBM
  and the kernel copies a group's block into one of two slots when the
  group before it meets its first row tile, so the copy runs beside all
  of that group's meetings; the grid's own pipeline would ask one meeting
  ahead, beside the last meeting alone (PERF.md section 6, PRs 43 and
  44, has both timed on the chip).
- **A row tile is the matrix unit's 128 rows.** A meeting loads the whole
  block into the matrix unit whatever the rows, so a tile of 16 rows
  costs what one of 128 does and only adds meetings, and one of 256
  costs twice as much for groups that seldom fill it.
- **Columns fill ``BLOCK_BYTES`` of vector memory**, and the call sets
  ``vmem_limit_bytes`` to what its blocks need.

Rows past ``sum(sizes)`` are not visited and hold whatever the buffer
held: the caller zeroes them before a product reads them.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fms_fsdp_tpu.ops.pallas_mode import interpret_default
from fms_fsdp_tpu.ops.selective_scan import largest_divisor

SUBLANES, LANES = 16, 128  # a bfloat16 tile
MXU_ROWS = 128  # rows the matrix unit takes against one load of a block
# what the blocks of a call may take of a v5e's 128 MiB of vector memory,
# and what the compiler is left beside them and the float32 products. A
# call's limit is their sum: past about 40 MB the compiler's own buffers
# move out to HBM (16-25 MB of a prefill program's temporaries,
# tests/test_aot_compile.py)
BLOCK_BYTES, HEADROOM_BYTES = 36 * 2**20, 4 * 2**20


def row_tile(M: int) -> int:
    """Rows of a tile for ``M`` rows: the matrix unit's height (or the
    most whole sublane tiles under it that divide M)."""
    whole = [
        t for t in range(SUBLANES, MXU_ROWS + 1, SUBLANES) if M % t == 0
    ]
    return whole[-1] if whole else largest_divisor(M, MXU_ROWS)


def block_bytes(tm, tn, k, stacks: int, itemsize: int) -> int:
    """Vector memory of a call's blocks: rows, weights and results, each
    double-buffered."""
    return 2 * itemsize * (tm * k + stacks * k * tn + tm * tn)


def schedule(M: int, k: int, n: int, stacks: int, itemsize: int):
    """-> (tm, tn) for ``stacks`` (1 or 2) products of (M, k) rows with
    (k, n) matrices."""
    tm = row_tile(M)
    # whole lane tiles that divide n (all of n where it is narrower)
    widths = [n // j for j in range(1, n // LANES + 1)
              if n % j == 0 and (n // j) % LANES == 0] or [n]
    fits = (w for w in widths
            if block_bytes(tm, w, k, stacks, itemsize) <= BLOCK_BYTES)
    return tm, next(fits, widths[-1])


def group_row_tiles(sizes, tm: int):
    """Row tiles of ``tm`` that each group's rows lie in (0 for an empty
    group): the (group, row tile) meetings a product's grid runs."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    return jnp.where(sizes > 0, -(-ends // tm) - starts // tm, 0)


def _meetings(sizes, M: int, tm: int):
    """-> (group offsets (G + 1,), each meeting's group and row tile
    (M / tm + G - 1,), the number of meetings)."""
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    met = group_row_tiles(sizes, tm)
    first = jnp.cumsum(met) - met  # a group's first meeting
    slots = M // tm + G - 1
    group = jnp.repeat(
        jnp.arange(G, dtype=jnp.int32), met, total_repeat_length=slots
    )
    tile = (ends - sizes)[group] // tm + jnp.arange(slots) - first[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # slots past the last meeting are never run; their tile stays in range
    tile = jnp.minimum(tile, M // tm - 1).astype(jnp.int32)
    return offsets.astype(jnp.int32), group, tile, jnp.sum(met)


def _fetch_order(sizes):
    """Of each group: the next group that has rows (-1 behind the last),
    its place among the groups that have rows; and how many have."""
    G = sizes.shape[0]
    has = sizes > 0
    ids = jnp.where(has, jnp.arange(G, dtype=jnp.int32), G)
    # the smallest id with rows behind each group
    behind = jnp.concatenate(
        [jax.lax.cummin(ids[::-1])[::-1][1:], jnp.full((1,), G, jnp.int32)]
    )
    place = jnp.cumsum(has, dtype=jnp.int32) - 1
    return jnp.where(behind < G, behind, -1), place, jnp.sum(has)[None]


def _kernel(
    offsets, group, tile, first, behind, place, count, x_ref, *refs,
    tm, tn, stacks,
):
    w_hbm, out_ref = refs[:stacks], refs[stacks]
    bufs, sems = refs[stacks + 1:-1], refs[-1]
    j, t = pl.program_id(0), pl.program_id(1)
    g = group[t]
    # the weight blocks are fetched by hand, a group ahead: a group's
    # block is asked for when the group before it meets its first row
    # tile, so the copy runs beside all of that group's meetings (the
    # pipeline's own prefetch would ask one meeting ahead, beside the
    # last meeting alone). Two slots a stack; the blocks take them in
    # turn in the order they are used, column tile after column tile.
    slot = (j * count[0] + place[g]) % 2

    def fetch(g, j, slot):
        return [
            pltpu.make_async_copy(
                w.at[first[0] + g, :, pl.ds(j * tn, tn)],
                buf.at[slot],
                sems.at[i, slot],
            )
            for i, (w, buf) in enumerate(zip(w_hbm, bufs))
        ]

    @pl.when((j == 0) & (t == 0))
    def _():
        for copy in fetch(g, j, slot):
            copy.start()

    @pl.when((t == 0) | (group[jnp.maximum(t - 1, 0)] != g))
    def _():
        for copy in fetch(g, j, slot):
            copy.wait()
        # behind the last group, the first one's next column tile
        wraps = behind[g] < 0
        then = j + wraps.astype(jnp.int32)

        @pl.when(then < pl.num_programs(0))
        def _():
            for copy in fetch(
                jnp.where(wraps, group[0], behind[g]), then, 1 - slot
            ):
                copy.start()

    x = x_ref[...]
    y = [
        jnp.dot(x, buf[slot], preferred_element_type=jnp.float32)
        for buf in bufs
    ]
    y = jax.nn.silu(y[0]) * y[1] if stacks == 2 else y[0]
    # of the tile's rows, this group's: the others belong to the meetings
    # before and after, which find the result's block resident
    row = tile[t] * tm + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    mine = (row >= offsets[g]) & (row < offsets[g + 1])
    out_ref[...] = jnp.where(mine, y.astype(out_ref.dtype), out_ref[...])


def grouped_matmul(x, stacks, sizes, l=0, *, tiles=None):
    """``x (M, k)`` sorted by group; ``stacks``: one stack ``(L, G, k, n)``
    or two (gate and up: ``silu(x w1) * (x w3)``); ``sizes (G,)`` int32;
    ``l`` the layer of the stacks (int or traced). -> (M, n) in x's
    dtype. ``tiles`` = (tm, tn) overrides the schedule (a sweep's, and
    the tests' small ones)."""
    return _grouped_matmul(
        x, tuple(stacks), sizes.astype(jnp.int32), jnp.asarray(l, jnp.int32),
        tiles=tiles, interpret=interpret_default(),
    )


# jitted, so that a program whose layers call it with one signature lowers
# the kernel once
@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _grouped_matmul(x, stacks, sizes, l, *, tiles, interpret):
    M, k = x.shape
    L, G, _, n = stacks[0].shape
    tm, tn = tiles or schedule(M, k, n, len(stacks), x.dtype.itemsize)
    assert M % tm == 0 and n % tn == 0, (M, tm, n, tn)
    offsets, group, tile, meetings = _meetings(sizes, M, tm)

    def rows(j, t, offsets, group, tile, *_):
        return tile[t], 0

    def result(j, t, offsets, group, tile, *_):
        return tile[t], j

    item = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, stacks=len(stacks)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(n // tn, meetings),
            in_specs=[pl.BlockSpec((tm, k), rows)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(stacks),
            out_specs=pl.BlockSpec((tm, tn), result),
            scratch_shapes=[pltpu.VMEM((2, k, tn), x.dtype)] * len(stacks)
            + [pltpu.SemaphoreType.DMA((len(stacks), 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((M, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            # the weights' slots go from step to step
            dimension_semantics=("arbitrary", "arbitrary"),
            # beside the blocks, the float32 products and their cast
            vmem_limit_bytes=block_bytes(tm, tn, k, len(stacks), item)
            + (len(stacks) + 1) * 4 * tm * tn + HEADROOM_BYTES,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * k * n * len(stacks),
            bytes_accessed=item * (
                M * k * (n // tn) + len(stacks) * G * k * n + M * n
            ),
            transcendentals=M * n if len(stacks) == 2 else 0,
        ),
        interpret=interpret,
        name="grouped_matmul",
    )(
        offsets, group, tile, jnp.reshape(l * G, (1,)),
        *_fetch_order(sizes), x, *(w.reshape(L * G, k, n) for w in stacks),
    )
