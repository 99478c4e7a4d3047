"""Rotary position embeddings.

The reference precomputes RoPE tables once up to ``max_expected_seq_len``
(ref:main_training_llama.py:93-96) with per-variant ``rope_theta``
(ref:fms_fsdp/utils/config_utils.py:43,74). We do the same: tables are a
small (S, head_dim/2) cos/sin pair computed in fp32 at trace time (constant-
folded by XLA) and applied with the half-split ("rotate_half") convention —
the same layout HF Llama uses, so weight export needs no q/k permutation
(the reference needs one because fms stores interleaved pairs,
ref:fms_to_hf_llama.py:69-124).
"""

import math

import jax.numpy as jnp


def rope_table(seq_len: int, head_dim: int, theta: float = 10000.0):
    """Return (cos, sin), each (seq_len, head_dim // 2), fp32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    angles = jnp.outer(pos, freqs)  # (S, half)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x, cos, sin, positions=None):
    """Apply half-split rotary embedding.

    x: (..., S, n_heads, head_dim); cos/sin: (S_table, head_dim/2) fp32.
    positions: optional (..., S) int positions into the table (for packed or
    decode-time use); default = arange(S).
    """
    seq_len = x.shape[-3]
    if positions is None:
        c = cos[:seq_len]  # (S, half)
        s = sin[:seq_len]
        c = c[:, None, :]  # (S, 1, half) broadcasting over heads
        s = s[:, None, :]
    else:
        c = cos[positions][..., None, :]
        s = sin[positions][..., None, :]
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def rotate_halves(x, positions, theta: float):
    """x (B, S, N, H) turned at ``positions`` (B, S): the two halves of a
    head paired, angles in float32 from the positions themselves (no
    table: a position is as far out as its stream has got)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


def yarn_rope_table(
    seq_len: int,
    dim: int,
    theta: float,
    *,
    factor: float,
    original_max_position: int,
    beta_fast: float,
    beta_slow: float,
    mscale: float = 1.0,
    mscale_all_dim: float = 0.0,
):
    """(cos, sin), each (seq_len, dim // 2) fp32, with the ``deepseek_yarn``
    frequencies (Peng et al. 2023, "YaRN", as DeepSeek-V2's
    ``config.json`` keys state it): pair ``i`` of ``dim // 2`` keeps its
    plain frequency ``theta^(-2i/dim)`` where it turns more than
    ``beta_fast`` times inside the original context, takes that over
    ``factor`` where it turns less than ``beta_slow`` times, and a
    linear blend between. cos and sin carry
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``
    (1 where the two are equal)."""
    half = dim // 2
    exponent = jnp.arange(0, half, dtype=jnp.float32) / half
    extra = 1.0 / (theta**exponent)
    inter = extra / factor

    def correction_dim(turns):
        return (
            dim
            * math.log(original_max_position / (turns * 2 * math.pi))
            / (2 * math.log(theta))
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
    )
    freqs = inter * ramp + extra * (1.0 - ramp)
    scale = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    angles = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), freqs)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``
    (1 for ``factor <= 1``). A yarn model multiplies its softmax scale by
    the square of ``yarn_mscale(factor, mscale_all_dim)``."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def deinterleave(x):
    """Pairs ``(x[2i], x[2i+1])`` of the last axis -> the half-split
    layout ``apply_rotary`` turns (``x[i]`` with ``x[i + half]``): a
    model whose checkpoint pairs neighbours rotates through this. Applied
    to queries and keys alike, it leaves their products as they were."""
    half = x.shape[-1] // 2
    return jnp.swapaxes(
        x.reshape(x.shape[:-1] + (half, 2)), -1, -2
    ).reshape(x.shape)
