"""A prompt prefilled as a sequence, a chunk at a time: the one loop the
serving families' prefill programs fill in (models/mamba.py's Mamba-1
stack, sarvam.py, kexaone.py, minicpm_sala.py, lfm2.py, phi4flash.py), and what stands
around it in each. No family and no config class is imported here.

``chunk_loop`` takes ``chunk_of(S_pad, PREFILL_CHUNK)`` positions at a
time up to the longest row's end and no further, tells the family's
``body`` what a chunk is (``Chunk``) and keeps each row's residual at its
last real position for the head. The body is the family's: its slice of
the tokens, its embedding, its layers and counters, over a carry of its
own (the buffers a later chunk reads: ``write_live``, ``next_tail``). The
programs are held to their text and scopes in
tests/test_sequence_prefill.py.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from fms_fsdp_tpu.obs.scopes import scoped


def largest_divisor(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is at most ``cap``: the chunk
    that tiles ``n`` positions."""
    return max(t for t in range(1, min(n, cap) + 1) if n % t == 0)


def chunk_of(p_pad: int, limit: int, unit: int = 1) -> int:
    """The chunk of a prompt padded to ``p_pad``: the most whole ``unit``s
    up to ``limit`` positions that tile it."""
    assert p_pad % unit == 0, (p_pad, unit)
    return unit * largest_divisor(p_pad // unit, max(1, limit // unit))


def positions_computed(p: int, c: int) -> int:
    """Positions a loop of chunks of ``c`` computes for a prompt of ``p``
    tokens: whole chunks up to the prompt's end."""
    return -(-p // c) * c


def kernel_wanted(attn_impl: str) -> bool:
    """Whether a sequence prefill told ``attn_impl`` (``"auto"``,
    ``"pallas"``, ``"xla"``) takes its attention kernels where the
    chunk's shape allows them: asked for by name, or left to a TPU."""
    return attn_impl == "pallas" or (
        attn_impl == "auto" and jax.default_backend() == "tpu"
    )


class Chunk:
    """What a trip of the loop knows of itself: ``c`` positions from
    ``start``; ``ahead`` (B,), what each row has left from there on (past
    its end: zero or less); ``live`` (B, c), the positions that are real;
    ``positions`` (B, c), made when a family first asks for them."""

    def __init__(self, j, c: int, lengths):
        self.c = c
        self.start = j * c
        self.ahead = lengths - self.start
        self.live = (
            jnp.arange(c, dtype=jnp.int32)[None, :] < self.ahead[:, None]
        )

    @functools.cached_property
    def positions(self):
        return jnp.broadcast_to(
            self.start + jnp.arange(self.c, dtype=jnp.int32),
            (self.ahead.shape[0], self.c),
        )


def chunk_loop(lengths, c: int, body, carry, last: int):
    """The chunks of ``c`` positions up to the longest of ``lengths`` (B,)
    int32, in one loop. ``body(chunk, carry) -> (x, carry)`` takes a
    ``Chunk`` through the family's layers: ``x`` (B, c, D) is the residual
    behind the last of them. ``carry()`` makes the tuple that goes from
    chunk to chunk (called once the trip count is read: the order the
    programs have); its entry ``last`` (B, D) is the loop's own, each
    row's ``x`` at its last real position, which the head reads alone.
    Returns the carry behind the last chunk."""

    def step(j, carry):
        chunk = Chunk(j, c, lengths)
        x, carry = body(chunk, carry)
        at = chunk.ahead - 1
        row = jnp.take_along_axis(
            x, jnp.clip(at, 0, c - 1)[:, None, None], axis=1
        )[:, 0]
        ends = ((at >= 0) & (at < c))[:, None]
        return (
            *carry[:last], jnp.where(ends, row, carry[last]),
            *carry[last + 1:],
        )

    return lax.fori_loop(0, (jnp.max(lengths) + c - 1) // c, step, carry())


def write_live(buf, new, live, start, layer=None):
    """A chunk's new rows ``new`` (B, c, ...) into ``buf`` (B, kv_len,
    ...) from position ``start`` on, zeros where ``live`` (B, c) says a
    position is not real: what a later chunk reads, and the pages' zeros
    past a row's length. Rows narrower than the buffer's are filled with
    zeros behind their last axis. With ``layer``, ``buf`` holds every
    layer's (L, B, kv_len, ...) and that one's is written. ``buf`` and
    ``new`` may be matching tuples (keys and values): one mask for all."""
    ndim = jax.tree.leaves(new)[0].ndim
    keep = live[(slice(None), slice(None)) + (None,) * (ndim - 2)]

    def write(buf, new):
        new = jnp.where(keep, new, jnp.zeros_like(new))
        pad = buf.shape[-1] - new.shape[-1]
        if pad:
            new = jnp.pad(new, [(0, 0)] * (ndim - 1) + [(0, pad)])
        at = (0, start) + (0,) * (ndim - 2)
        if layer is None:
            return lax.dynamic_update_slice(buf, new, at)
        return lax.dynamic_update_slice(buf, new[None], (layer,) + at)

    return jax.tree.map(write, buf, new)


@scoped("win_write")
def next_tail(tail, new, ahead, window):
    """The ``window`` positions that end where each row's prompt has got
    to after this chunk: of tail (B, window, ...) then new (B, c, ...),
    the ``window`` rows that end at ``min(ahead, c)`` of the chunk
    (``ahead`` (B,): what each row had left at the chunk's start). A row
    that goes on takes the chunk's last ``window``; a row that ends here
    the last ``window`` of its prompt; a row that ended keeps its own."""
    ext = jnp.concatenate([tail, new], axis=1)
    at = jnp.clip(ahead, 0, new.shape[1])
    return jax.vmap(
        lambda e, a: lax.dynamic_slice_in_dim(e, a, window, axis=0)
    )(ext, at)


def stack_or_empty(parts, shape, dtype):
    """The layers' buffers ``parts`` (each of ``shape``) as one array with
    the layers leading; (0, *shape) where the stack has no such layer."""
    return jnp.stack(parts) if parts else jnp.zeros((0,) + shape, dtype)
