"""Flash-attention kernel tests (interpreter mode on CPU): forward/backward
numerics vs the XLA reference across GQA configs, causal and full, plus
dispatcher eligibility."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fms_fsdp_tpu.ops.attention import attention, xla_attention
from fms_fsdp_tpu.ops.flash_attention import flash_attention, supports


def _rand_qkv(b, s, nq, nkv, h, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, nq, h)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, nkv, h)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, nkv, h)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("nq,nkv", [(4, 4), (4, 2), (4, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla(nq, nkv, causal):
    q, k, v = _rand_qkv(2, 256, nq, nkv, 128)
    ref = xla_attention(q, k, v, causal=causal)
    out = flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("variant", ["resident", "kvgrid"])
def test_flash_bf16_parity(monkeypatch, variant):
    """Production dtype parity (ADVICE r3): the base-2 rewrite folds
    scale*log2(e) into q and casts back to bf16 before the MXU — one
    extra bf16 rounding of q vs a fp32 post-matmul scale. Both kernel
    families must track the fp32-softmax XLA oracle on bf16 inputs, for
    the output AND the gradients, at bf16-appropriate tolerance."""
    from fms_fsdp_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_VARIANT", variant)
    q, k, v = _rand_qkv(2, 256, 4, 2, 128, seed=11)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = xla_attention(qb, kb, vb, causal=True)
    out = flash_attention(
        qb, kb, vb, causal=True, block_q=128, block_k=64, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref, np.float32),
        atol=2e-2,
        rtol=2e-2,
    )

    def mk_loss(fn):
        def loss(q, k, v):
            o = fn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * (o.shape[-1] ** -0.5))

        return loss

    ref_g = jax.grad(
        mk_loss(lambda q, k, v: xla_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2),
    )(qb, kb, vb)
    out_g = jax.grad(
        mk_loss(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=128, block_k=64, interpret=True
            )
        ),
        argnums=(0, 1, 2),
    )(qb, kb, vb)
    for a, b in zip(out_g, ref_g):
        np.testing.assert_allclose(
            np.asarray(a, np.float32),
            np.asarray(b, np.float32),
            atol=4e-2,
            rtol=4e-2,
        )


def test_flash_return_lse_differentiable():
    """flash_attention(return_lse=True): both outputs carry gradients —
    the lse cotangent folds into the backward's delta (delta - dlse)."""
    q, k, v = _rand_qkv(1, 256, 4, 2, 128, seed=7)

    def f_loss(q, k, v):
        o, lse = flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            interpret=True, return_lse=True,
        )
        return (o**2).mean() + (lse**2).mean()

    # reference: explicit softmax attention + logsumexp
    def ref_loss(q, k, v):
        b, s, nq, h = q.shape
        nkv = k.shape[2]
        qg = q.reshape(b, s, nkv, nq // nkv, h)
        scores = (
            jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
            * h**-0.5
        )
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
        lse = jax.scipy.special.logsumexp(scores, axis=-1)  # (b,nkv,g,q)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bkgqs,bskh->bkgqh", p, v)
        o = jnp.moveaxis(o, 3, 1).reshape(b, s, nq, h)
        lse = jnp.moveaxis(lse, 3, 1).reshape(b, s, nq, 1)
        return (o**2).mean() + (lse**2).mean()

    gf = jax.grad(f_loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_flash_grads_cross_length_causal():
    """seq_k > seq_q, causal: k-blocks wholly past the q sequence must get
    zero dk/dv (regression: stale-scratch write in the streamed-q kernel)."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 256, 4, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 512, 4, 128)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 512, 4, 128)), jnp.float32)

    def f_loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True
        )
        return (o**2).mean()

    def r_loss(q, k, v):
        return (xla_attention(q, k, v, causal=True) ** 2).mean()

    gf = jax.grad(f_loss, argnums=(1, 2))(q, k, v)
    gr = jax.grad(r_loss, argnums=(1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_flash_grads_match_xla():
    q, k, v = _rand_qkv(1, 256, 4, 2, 128)

    def f_loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True
        )
        return (o**2).mean()

    def r_loss(q, k, v):
        return (xla_attention(q, k, v, causal=True) ** 2).mean()

    gf = jax.grad(f_loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_block_size_rounding():
    """Sequences not divisible by the default block fall to smaller blocks."""
    q, k, v = _rand_qkv(1, 384, 2, 2, 128)  # 384 = 3 * 128
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_supports_eligibility(monkeypatch):
    assert supports((2, 4096, 32, 128), (2, 4096, 8, 128))
    assert not supports((2, 4096, 32, 64), (2, 4096, 8, 64))  # head dim
    # the forward kernels take heads of 64 (serving's prefill says so)
    assert supports((1, 2048, 32, 64), (1, 2048, 8, 64), forward_only=True)
    assert not supports((1, 2048, 32, 32), (1, 2048, 8, 32), forward_only=True)
    assert not supports((2, 100, 4, 128), (2, 100, 4, 128))  # seq align
    # past the resident cap: the kv-streamed kernels engage, no limit
    assert supports((1, 32768, 8, 128), (1, 32768, 2, 128))
    from fms_fsdp_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_VARIANT", "resident")
    assert not supports((1, 32768, 8, 128), (1, 32768, 2, 128))


def test_dispatcher_fallback_small_heads():
    """Ineligible shapes silently use the XLA path under impl='auto'."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 16, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 16, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 16, 2, 16)), jnp.float32)
    out = attention(q, k, v, causal=True, impl="auto")
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    with pytest.raises(NotImplementedError):
        attention(q, k, v, impl="pallas")


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("nq,nkv", [(4, 4), (4, 2)])
def test_kvgrid_fwd_matches_resident(monkeypatch, causal, nq, nkv):
    """The kv-streamed forward grid kernel is exactly the resident
    kernel's math (same base-2 online softmax) — o and lse must agree to
    float tolerance, including the causal skip/clamp cells and GQA
    index maps, and at block_q != block_k."""
    from fms_fsdp_tpu.ops import flash_attention as fa

    q, k, v = _rand_qkv(2, 256, nq, nkv, 128, seed=3)
    ref_o, ref_lse = flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=64, interpret=True,
        return_lse=True,
    )
    monkeypatch.setattr(fa, "_VARIANT", "kvgrid")
    out_o, out_lse = flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=64, interpret=True,
        return_lse=True,
    )
    np.testing.assert_allclose(np.asarray(out_o), np.asarray(ref_o), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(out_lse), np.asarray(ref_lse), atol=2e-5
    )


def test_kvgrid_grads_match_resident(monkeypatch):
    """With the kvgrid variant selected the full VJP (streamed fwd +
    streamed dq + the shared dkv kernel) must produce the same gradients
    as the resident kernels."""
    from fms_fsdp_tpu.ops import flash_attention as fa

    q, k, v = _rand_qkv(1, 256, 4, 2, 128, seed=5)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, block_q=128, block_k=64, interpret=True
            ).astype(jnp.float32)
        )

    ref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(fa, "_VARIANT", "kvgrid")
    out = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(out, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_auto_kvgrid_dispatch_past_cap(monkeypatch):
    """With the resident cap lowered, the dispatcher auto-selects the
    kv-streamed kernels and still matches the resident result."""
    from fms_fsdp_tpu.ops import flash_attention as fa

    q, k, v = _rand_qkv(1, 256, 4, 2, 128, seed=7)
    ref = flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True
    )
    monkeypatch.setattr(fa, "MAX_KERNEL_SEQ", 128)
    assert fa._use_kvgrid(256)
    out = flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# -- values of another width than queries and keys (latent attention) ---------


def _einsum_attention(q, k, v, causal):
    """The einsum form for any value width, GQA by repeating kv heads,
    fp32 softmax -> (o (B, S, Nq, Hv), lse (B, S, Nq, 1))."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum(
        "bqnd,bsnd->bnqs", q, k, preferred_element_type=jnp.float32
    ) * q.shape[-1] ** -0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bnqs,bsnd->bqnd", p, v.astype(jnp.float32))
    return o, jnp.moveaxis(lse, 1, 2)[..., None]


def _rand_two_widths(nq, nkv, h, hv, seed, dtype=jnp.float32):
    q, k, _ = _rand_qkv(2, 256, nq, nkv, h, seed=seed)
    _, _, v = _rand_qkv(2, 256, nq, nkv, hv, seed=seed + 1)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


@pytest.mark.parametrize("variant", ["resident", "kvgrid"])
@pytest.mark.parametrize("nq,nkv", [(8, 8), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("h,hv", [(192, 128), (128, 256), (64, 64)])
def test_flash_two_widths_match_einsum(h, hv, causal, nq, nkv, variant):
    """Values narrower (latent attention's 192 and 128) or wider than
    queries and keys, and heads 64 wide (a block of 64 lanes, no head
    padded), in both forward families: the output is as wide as the
    values, and it and the log-sum-exp are the einsum form's."""
    q, k, v = _rand_two_widths(nq, nkv, h, hv, seed=13)
    ref_o, ref_lse = _einsum_attention(q, k, v, causal)
    o, lse = flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=64, interpret=True,
        return_lse=True, variant=variant,
    )
    assert o.shape == (2, 256, nq, hv) and lse.shape == (2, 256, nq, 1)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref_o), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(ref_lse), atol=2e-5
    )
    only_o = flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=64, interpret=True,
        variant=variant,
    )
    np.testing.assert_array_equal(np.asarray(only_o), np.asarray(o))


@pytest.mark.parametrize("variant", ["resident", "kvgrid"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_heads_of_64_thirty_two_on_eight(causal, variant):
    """The lfm2 geometry as published, 32 query heads on 8 kv heads of
    64, a diagonal partial and an earlier block's as
    ``ops/attention.py::chunk_attention`` asks for them."""
    q, k, v = _rand_two_widths(32, 8, 64, 64, seed=23)
    ref_o, ref_lse = _einsum_attention(q, k, v, causal)
    o, lse = flash_attention(
        q, k, v, causal=causal, interpret=True, return_lse=True,
        variant=variant,
    )
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5)


@pytest.mark.parametrize("variant", ["resident", "kvgrid"])
@pytest.mark.parametrize("h,hv", [(192, 128), (128, 256), (64, 64)])
def test_flash_two_widths_bf16_parity(h, hv, variant):
    """bfloat16 inputs at the tolerance of the one-width parity test."""
    q, k, v = _rand_two_widths(8, 2, h, hv, seed=17, dtype=jnp.bfloat16)
    ref_o, ref_lse = _einsum_attention(q, k, v, True)
    o, lse = flash_attention(
        q, k, v, causal=True, block_q=128, block_k=64, interpret=True,
        return_lse=True, variant=variant,
    )
    assert o.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref_o), atol=2e-2, rtol=2e-2
    )
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(ref_lse), atol=2e-2, rtol=2e-2
    )


@pytest.mark.parametrize("return_lse", [False, True])
def test_flash_two_widths_have_no_backward(return_lse):
    """The dq and dk/dv kernels take one head width: a gradient through
    unequal widths is refused, and the message says what is not built."""
    q, k, v = _rand_two_widths(4, 2, 192, 128, seed=19)

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True,
            return_lse=return_lse,
        )
        return sum(jnp.sum(x) for x in jax.tree.leaves(out))

    assert np.isfinite(float(loss(q, k, v)))  # the forward alone runs
    with pytest.raises(
        NotImplementedError, match="backward with a value width.*not built"
    ):
        jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
