"""The ragged paged decode kernel (``ops/paged_attention.py``) against the
gathered reference at the five callers' published head geometries, over
the lengths at which a walk of a stream's own live blocks can go wrong,
on the CPU in interpret mode. A cell of the kernel is a stream; it walks
``seq_len // block + 1`` blocks, fetches a block's live pages by hand and
multiplies a word's worth of heads a product (two in bfloat16)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fms_fsdp_tpu.ops.paged_attention import (
    _heads_per_product,
    packed_pages_attention_kernel,
    paged_attention_kernel,
    paged_attention_reference,
)
from fms_fsdp_tpu.ops.quant import kv_dequantize, kv_quantize

B = 4  # rows of the batch: streams, or (stream, kv head) in the sala call

# name: query heads, kv heads, head, positions a page, pages a block,
# pages a row's table holds. Heads as published, pages short enough for
# the interpreter except Mixtral's, which stand as its engine builds them.
GEOMETRIES = {
    # differential attention's rows: 40 query rows over 10 pairs of heads
    # of 64 in 128 lanes, the accumulator handed out in float32
    "phi4flash_pages": (40, 10, 128, 16, 4, 12),
    # a slot's ring is four pages under a table in slot order
    "phi4flash_ring": (40, 10, 128, 16, 4, 4),
    "kexaone": (64, 8, 128, 16, 4, 12),
    # 32 query heads over 8 kv heads of 64, two heads a row of 128 lanes
    "lfm2_packed": (32, 8, 64, 16, 4, 12),
    # one kv head a page; a row is a (slot, kv head) with 16 query heads
    "sala_rows": (16, 1, 128, 16, 8, 16),
    "mixtral": (32, 8, 128, 64, 4, 8),
}


def _lengths(case, block, longest):
    """(B,) query positions; row b sees cache positions <= lens[b]."""
    return {
        "dead_slot": [0, 0, 0, 0],
        "one": [1, 1, 1, 1],
        "block_last": [block - 1] * B,
        "block_first": [min(block, longest)] * B,
        "longest": [longest] * B,
        "ragged": [-1, block - 1, min(block + 3, longest), longest],
    }[case]


LENGTHS = ("dead_slot", "one", "block_last", "block_first", "longest", "ragged")


def _pools(key, pages, ps, nkv, hd, dtype=jnp.bfloat16):
    kk, kv = jax.random.split(key)
    k = jax.random.normal(kk, (pages, ps, nkv, hd), jnp.float32)
    v = jax.random.normal(kv, (pages, ps, nkv, hd), jnp.float32)
    # page 0 is the allocator's zero page
    return k.at[0].set(0).astype(dtype), v.at[0].set(0).astype(dtype)


def _check(out, ref, lens, case, atol, zero_rows=2):
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all(), case
    live = np.asarray(lens) >= 0
    diff = np.abs(out[live] - ref[live]).max()
    assert diff <= atol, (case, float(diff))
    # a row at a negative position attends nothing: zeros
    assert (out[~live] == 0).all(), case
    if case == "dead_slot":
        # rows 0 and 1 point at the zero page
        assert (out[:zero_rows] == 0).all()


@pytest.mark.parametrize("case", LENGTHS)
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_kernel_equals_gather_at_published_heads(geometry, case):
    nq, nkv, hd, ps, ppb, maxp = GEOMETRIES[geometry]
    block, longest = ps * ppb, ps * maxp - 1
    lens = jnp.asarray(_lengths(case, block, longest), jnp.int32)
    ring = geometry == "phi4flash_ring"
    pages = 2 * B * maxp if ring else B * maxp + 2
    k, v = _pools(jax.random.PRNGKey(11), pages, ps, nkv, hd)
    q = jax.random.normal(jax.random.PRNGKey(12), (B, nq, hd), jnp.bfloat16)
    if ring:
        # window layer 1 of two: slot b's ring is pages (B + b) * 4 + i
        table = (B + np.arange(B))[:, None] * maxp + np.arange(maxp)
        k, v = (a.at[0].set(a[1]) for a in (k, v))  # no zero page in a ring
    else:
        table = 2 + np.arange(B * maxp).reshape(B, maxp)
        if case == "dead_slot":
            table[:2] = 0
    table = jnp.asarray(table, jnp.int32)
    ref = paged_attention_reference(q, k, v, table, jnp.maximum(lens, 0))
    if geometry == "lfm2_packed":
        rows = (pages, ps * 4, 128)
        out = packed_pages_attention_kernel(
            q, k.reshape(rows), v.reshape(rows), table, lens, nkv=nkv,
            block_kv=block, interpret=True)
    elif geometry.startswith("phi4flash"):
        # the pages as the cells read them, the accumulator unrounded
        flat = (pages, ps * nkv, hd)
        out = paged_attention_kernel(
            q, k.reshape(flat), v.reshape(flat), table, lens, nkv=nkv,
            block_kv=block, out_dtype=jnp.float32, interpret=True)
        assert out.dtype == jnp.float32
    else:
        out = paged_attention_kernel(
            q, k, v, table, lens, block_kv=block, interpret=True)
    _check(out.reshape(B, -1), ref, lens, case, 3e-2, 0 if ring else 2)


@pytest.mark.parametrize("case", LENGTHS)
@pytest.mark.parametrize("wire", ["int8", "fp8"])
def test_quantized_pools_with_scales_on_the_llama_shape(wire, case):
    """llama3's 16 query heads over 8 kv heads of 128, pages of 16 and
    blocks of four: the scales are fetched beside the pages and applied
    in VMEM; every head at once, a page a product."""
    nq, nkv, hd, ps, ppb, maxp = 16, 8, 128, 16, 4, 12
    block, longest = ps * ppb, ps * maxp - 1
    lens = jnp.asarray(_lengths(case, block, longest), jnp.int32)
    k, v = _pools(jax.random.PRNGKey(21), B * maxp + 2, ps, nkv, hd, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(22), (B, nq, hd), jnp.float32)
    table = 2 + np.arange(B * maxp).reshape(B, maxp)
    if case == "dead_slot":
        table[:2] = 0
    table = jnp.asarray(table, jnp.int32)
    kq, ks = kv_quantize(k, wire)
    vq, vs = kv_quantize(v, wire)
    ref = paged_attention_reference(
        q, kv_dequantize(kq, ks, jnp.float32),
        kv_dequantize(vq, vs, jnp.float32), table, jnp.maximum(lens, 0))
    out = paged_attention_kernel(
        q, kq, vq, table, lens, k_scales=ks, v_scales=vs, block_kv=block,
        interpret=True)
    _check(out, ref, lens, case, atol=2e-4)


def test_scattered_pages_and_a_zero_page_at_a_rows_end():
    """One stream's pages lie scattered and out of order through the
    pool; another's last table entries are the zero page (its allocation
    ends before its block does); a third has no position at all between
    two that walk, so the fetch ahead skips it."""
    nq, nkv, hd, ps, ppb, maxp = 8, 4, 128, 8, 2, 6
    k, v = _pools(jax.random.PRNGKey(31), 32, ps, nkv, hd)
    q = jax.random.normal(jax.random.PRNGKey(32), (B, nq, hd), jnp.bfloat16)
    table = jnp.asarray([
        [29, 3, 17, 8, 22, 5],
        [9, 10, 11, 0, 0, 0],
        [0, 0, 0, 0, 0, 0],
        [31, 2, 30, 4, 0, 0],
    ], jnp.int32)
    lens = jnp.asarray([47, 20, -1, 27], jnp.int32)
    ref = paged_attention_reference(q, k, v, table, jnp.maximum(lens, 0))
    out = paged_attention_kernel(
        q, k, v, table, lens, block_kv=ps * ppb, interpret=True)
    _check(out, ref, lens, "scattered", atol=3e-2)


@pytest.mark.parametrize("nkv,itemsize,quantized,heads", [
    (10, 2, False, 2),  # phi4flash: a pair of rows a 32-bit word
    (8, 2, False, 2),
    (8, 4, False, 1),  # float32 (the tests' engines): a head a product
    (1, 2, False, 1),  # sala: the one head there is
    (3, 2, False, 3),  # odd in bfloat16: every head at once
    (8, 1, True, 8),  # scales lie a position and head along the lanes
])
def test_heads_a_product_follow_the_words_of_a_row(nkv, itemsize, quantized, heads):
    assert _heads_per_product(nkv, itemsize, quantized) == heads
