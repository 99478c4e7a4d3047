"""Lightning attention (Lightning Attention-2, arXiv:2401.04658): linear
attention with a constant decay a head.

By head ``h``: ``S_t = lam_h S_{t-1} + k_t v_t^T``, ``o_t = q_t^T S_t *
scale``, ``lam_h = exp(-slope_h)``. Three forms, one arithmetic:

- ``lightning_recurrent``: the recurrence position by position (the
  parity form);
- ``lightning_step``: one position a row from a carried state (the
  decode step);
- ``lightning_chunked``: a sequence in chunks of ``L`` positions, inside
  a chunk a masked (L, L) product with the decay between two positions,
  between chunks the state alone. This is ops/ssd.py's chunked
  recurrence with a constant scalar decay a head, ``dt`` = 1 and one
  group a head (``x`` = v, ``B`` = k, ``C`` = q, ``A`` = -slope), and it
  is that code that runs (``_ssd_core_xla``, given the state to go on
  from): operands in the input dtype, decay statistics and state float32.

The state is kept as ops/ssd.py keeps it: (B, heads, P, N) float32 with
``P`` the value's axis and ``N`` the key's (``S^T`` of the equation).
``live`` masks positions past a row's end: they decay nothing, add
nothing, and what they return is not used.
"""

import jax.numpy as jnp
from jax import lax

from fms_fsdp_tpu.ops.selective_scan import largest_divisor
from fms_fsdp_tpu.ops.ssd import _ssd_core_xla

CHUNK = 256  # positions of one masked product


def slopes(n_heads: int):
    """``2^(-8 (h + 1) / heads)`` for head ``h``: ALiBi's slopes, the
    negative logarithm of the decay."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return 2.0 ** (-8.0 * h / n_heads)


def lightning_step(q, k, v, state, scale):
    """One position a row. q, k, v (B, heads, H); state (B, heads, P, N)
    float32 -> (o (B, heads, P) float32, the state after)."""
    f32 = jnp.float32
    lam = jnp.exp(-slopes(q.shape[1]))[None, :, None, None]
    state = lam * state + (
        v.astype(f32)[..., :, None] * k.astype(f32)[..., None, :]
    )
    o = jnp.einsum("bhn,bhpn->bhp", q.astype(f32), state)
    return o * scale, state


def lightning_recurrent(q, k, v, scale, state=None):
    """q, k, v (B, S, heads, H) -> (o (B, S, heads, H) float32, the
    state after the last position): ``lightning_step`` scanned."""
    B, S, n, H = q.shape
    if state is None:
        state = jnp.zeros((B, n, H, H), jnp.float32)

    def step(s, qkv):
        o, s = lightning_step(*qkv, s, scale)
        return s, o

    state, o = lax.scan(
        step, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v))
    )
    return jnp.moveaxis(o, 0, 1), state


def lightning_chunked(q, k, v, scale, state=None, live=None):
    """q, k, v (B, S, heads, H); ``state`` (B, heads, P, N) float32 to go
    on from (zeros when None); ``live`` (B, S) bool, the positions that
    count (all when None) -> (o (B, S, heads, H) float32, the state after
    the last live position)."""
    B, S, n, _ = q.shape
    ones = jnp.ones((B, S, 1), jnp.float32)
    dt = ones if live is None else live[..., None].astype(jnp.float32)
    dt = jnp.broadcast_to(dt, (B, S, n))
    y, state = _ssd_core_xla(
        v, dt, -slopes(n)[None, None, :] * dt, k, q,
        largest_divisor(S, CHUNK), return_state=True, init=state,
    )
    return y * scale, state
