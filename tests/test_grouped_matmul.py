"""The held experts' grouped product (ops/grouped_matmul.py) alone, on the
CPU through the Pallas interpreter: against a plain product a group, at
small sizes with the three serving cells' proportions; its schedule as a
function of shapes; and the count of row tiles met that a prefill reports."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fms_fsdp_tpu.models import moe_held as H
from fms_fsdp_tpu.ops import grouped_matmul as G


def _plain(x, stacks, sizes, l):
    """-> (the rows' products group by group, NaN past the groups' end;
    the groups' end)."""
    x = np.asarray(x, np.float32)
    out = np.full((x.shape[0], stacks[0].shape[-1]), np.nan, np.float32)
    lo = 0
    for g, n in enumerate(np.asarray(sizes)):
        y = [x[lo:lo + n] @ np.asarray(w[l, g], np.float32) for w in stacks]
        out[lo:lo + n] = (
            y[0] / (1 + np.exp(-y[0])) * y[1] if len(y) == 2 else y[0]
        )
        lo += n
    return out, lo


def _operands(M, G_, k, n, L=1, seed=0, dtype=jnp.float32):
    kx, k1, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (M, k), dtype)
    w1 = (jax.random.normal(k1, (L, G_, k, n)) * k**-0.5).astype(dtype)
    w3 = (jax.random.normal(k3, (L, G_, k, n)) * k**-0.5).astype(dtype)
    return x, w1, w3


def _sizes(case, M, G_, tm):
    rng = np.random.default_rng(len(case))
    if case == "third-of-a-tile":  # sarvam, k-exaone
        return np.full(G_, tm // 3)
    if case == "tile-and-a-half":
        return np.full(G_, 3 * tm // 2)[: M // (3 * tm // 2)]
    if case == "short-groups":  # lfm2: 64 groups, each a part of a tile
        return rng.multinomial(M, [1 / G_] * G_)
    if case == "empty-groups":
        s = rng.multinomial(M // 2, [1 / G_] * G_)
        s[::3] = 0
        return s
    if case == "ends-on-a-tile-edge":
        return np.asarray([tm, 2 * tm, 5, 0, tm - 5] + [0] * (G_ - 5))
    if case == "short-of-the-slab":
        return np.asarray([7, 0, 3] + [0] * (G_ - 3))
    raise AssertionError(case)


CASES = {
    # name: (M, groups, tile rows)
    "third-of-a-tile": (192, 8, 48),  # as at megablox's tile of 256
    "tile-and-a-half": (192, 8, 16),
    "short-groups": (256, 64, 16),
    "empty-groups": (128, 8, 16),
    "ends-on-a-tile-edge": (128, 8, 16),
    "short-of-the-slab": (64, 4, 16),
}


@pytest.mark.parametrize("fused", [False, True], ids=["one", "gate-and-up"])
@pytest.mark.parametrize("case", list(CASES))
def test_grouped_product_is_the_plain_product_group_by_group(case, fused):
    """Each group's rows times its group's matrix, whatever the groups'
    sizes against the row tile; rows past ``sum(sizes)`` never reach a
    row before it (they are poisoned here)."""
    M, G_, tm = CASES[case]
    sizes = np.zeros(G_, np.int64)
    given = _sizes(case, M, G_, tm)
    sizes[: len(given)] = given
    assert sizes.sum() <= M
    x, w1, w3 = _operands(M, G_, 32, 256)
    end = int(sizes.sum())
    x = x.at[end:].set(jnp.nan)
    stacks = (w1, w3) if fused else (w1,)
    got = np.asarray(G.grouped_matmul(
        x, stacks, jnp.asarray(sizes, jnp.int32), tiles=(tm, 128)))
    want, _ = _plain(x, stacks, sizes, 0)
    assert not np.isnan(got[:end]).any()
    np.testing.assert_allclose(got[:end], want[:end], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("l", [0, 1, 2])
def test_only_layer_l_of_the_stack_is_read(l):
    """The whole (L, G, k, n) stack is handed over and layer ``l`` (a
    traced int) found by the block index: the other layers hold NaN."""
    M, G_ = 64, 4
    x, w1, w3 = _operands(M, G_, 32, 128, L=3, seed=l)
    keep = (jnp.arange(3) == l)[:, None, None, None]
    w1, w3 = (jnp.where(keep, w, jnp.nan) for w in (w1, w3))
    sizes = jnp.asarray([20, 0, 30, 14], jnp.int32)
    run = jax.jit(lambda l: (
        G.grouped_matmul(x, (w1,), sizes, l),
        G.grouped_matmul(x, (w1, w3), sizes, l)))
    for got, stacks in zip(run(jnp.int32(l)), ((w1,), (w1, w3))):
        want, _ = _plain(x, stacks, sizes, l)
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_gate_and_up_in_one_pass_is_the_two_products_form():
    """``silu(x w1) * (x w3)`` out of one call against the two products
    and the element-wise pass between them, in bfloat16: both products
    in float32 and one cast, so never further from the float32 result
    than the two casts are, and within ``tests/test_sarvam.py``'s
    tolerance for ``_moe_grouped`` in float32."""
    M, G_ = 128, 4
    sizes = jnp.asarray([40, 8, 0, 70], jnp.int32)
    end = 118
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, None)):
        x, w1, w3 = _operands(M, G_, 64, 128, dtype=dtype)
        one = G.grouped_matmul(x, (w1, w3), sizes)
        two = jax.nn.silu(G.grouped_matmul(x, (w1,), sizes)) * (
            G.grouped_matmul(x, (w3,), sizes))
        assert one.dtype == two.dtype == dtype
        one, two = (np.asarray(a[:end], np.float32) for a in (one, two))
        want = _plain(x, (w1, w3), sizes, 0)[0][:end]
        if tol:
            np.testing.assert_allclose(one, two, atol=tol)
        else:
            assert np.abs(one - want).mean() <= np.abs(two - want).mean()
            assert np.abs(one - want).max() <= 2.0**-7 * np.abs(want).max()


# the three cells' products at published widths: (rows of a slab, groups
# held, k, n, stacks) -> (tile rows, tile columns) as the chip sweep chose
# (PERF.md section 6, PRs 43 and 44)
CELL_SHAPES = {
    "sarvam-up": ((6144, 32, 4096, 2048, 2), (128, 1024)),
    "sarvam-down": ((6144, 32, 2048, 4096, 1), (128, 4096)),
    "k-exaone-up": ((3072, 16, 6144, 2048, 2), (128, 512)),
    "k-exaone-down": ((3072, 16, 2048, 6144, 1), (128, 3072)),
    "lfm2-up-2048": ((8192, 64, 2048, 1536, 2), (128, 1536)),
    "lfm2-down-2048": ((8192, 64, 1536, 2048, 1), (128, 2048)),
    "lfm2-up-512": ((2048, 64, 2048, 1536, 2), (128, 1536)),
    "lfm2-down-512": ((2048, 64, 1536, 2048, 1), (128, 2048)),
}


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_schedule_follows_from_the_shapes_alone(cell):
    """The tiles the chip sweep chose: the matrix unit's rows, whole lane
    tiles that divide the operands, k whole, blocks inside the
    vector-memory limit the call sets, and that limit where the
    compiler's own buffers stay in vector memory."""
    (M, G_, k, n, stacks), want = CELL_SHAPES[cell]
    tm, tn = G.schedule(M, k, n, stacks, 2)
    assert (tm, tn) == want
    assert tm % G.SUBLANES == 0 and tn % G.LANES == 0
    assert M % tm == 0 and n % tn == 0
    blocks = G.block_bytes(tm, tn, k, stacks, 2)
    assert blocks <= G.BLOCK_BYTES
    limit = blocks + (stacks + 1) * 4 * tm * tn + G.HEADROOM_BYTES
    assert limit < 44 * 2**20 < 128 * 2**20


def test_schedule_reads_shapes_and_nothing_else():
    """No config field, environment variable or family's name: the
    schedule's functions take integers and their source names none."""
    for fn in (G.schedule, G.row_tile, G.block_bytes):
        assert set(inspect.signature(fn).parameters) <= {
            "M", "k", "n", "stacks", "itemsize", "tm", "tn"}
    source = inspect.getsource(G)
    for word in ("environ", "getenv", "cfg", "config", "KERNEL_TUNING",
                 "sarvam", "exaone", "lfm2", "family"):
        assert word not in source.replace("ops/pallas_mode", ""), word
    # the sweep's table in _gmm's comment names megablox; nothing imports it
    assert "pallas.ops.tpu.megablox" not in inspect.getsource(H)


def _slabs_by_hand(idx, first, held, slab):
    """Each slab's group sizes from a chunk's chosen ids."""
    local = np.sort(idx.reshape(-1) - first)
    local = local[(local >= 0) & (local < held)]
    return [np.bincount(local[lo:lo + slab], minlength=held)
            for lo in range(0, len(local), slab)]


def _tiles_by_hand(sizes, tm):
    """Row tiles that each group of a slab lies in."""
    ends = np.cumsum(sizes)
    return sum((e - 1) // tm - (e - n) // tm + 1
               for e, n in zip(ends, sizes) if n)


class _Cfg:
    def __init__(self, held, num_experts):
        self.held, self.num_experts = held, num_experts


@pytest.mark.parametrize("held,num_experts,T", [
    ((0, 4), 16, 128), ((4, 4), 16, 256), ((0, 16), 16, 64), ((2, 3), 8, 96),
], ids=["a-quarter-held", "skewed-onto-the-held", "all-held", "odd"])
def test_row_tiles_met_are_counted_from_each_slabs_group_sizes(
        held, num_experts, T, monkeypatch):
    """``_moe_grouped``'s count of the row tiles met equals the count by
    hand, which is also what the kernel's own grid runs over the same
    slabs (its meetings)."""
    cfg = _Cfg(held, num_experts)
    rng = np.random.default_rng(T)
    idx = np.stack([rng.permutation(num_experts)[:4] for _ in range(T)])
    if num_experts == 16 and held == (4, 4):  # more than a slab lands
        idx[:, :3] = np.asarray([4, 5, 6])
    w = jnp.full(idx.shape, 0.25, jnp.float32)
    monkeypatch.setattr(
        H, "_router", lambda h, layer, cfg: (jnp.asarray(idx), w))
    h, w1, w3 = _operands(T, held[1], 32, 128)
    layer = {"w1": w1[0], "w3": w3[0], "w2": jnp.swapaxes(w1[0], 1, 2)}
    slab = H.grouped_slab(cfg, T * 4)
    tm = H.grouped_tile_rows(cfg, T * 4)
    slabs = _slabs_by_hand(idx, *held, slab)
    want = sum(_tiles_by_hand(sizes, tm) for sizes in slabs)
    _, n, trips, met = jax.jit(lambda h: H._moe_grouped(h, layer, cfg))(h)
    assert (int(n), int(trips)) == (sum(map(sum, slabs)), len(slabs))
    assert int(met) == want > 0
    # the kernel's grid: the meetings of each slab's sizes
    grid = sum(
        int(G._meetings(jnp.asarray(sizes, jnp.int32), slab, tm)[3])
        for sizes in slabs)
    assert grid == want
