"""Weights from ``--seed``: one generator for the program and the reference.

A family's ``param_spec(config)`` (benchmark/reference/<family>.py) lists
every leaf of the parameter tree as ``path -> (shape, kind, scale)``. Each
leaf draws from a key folded from the seed and the leaf's path alone, and
a leaf whose spec says ``stacked`` draws each slice of its first axis from
a key folded once more with the slice's index. So the whole tree can be
made in one jitted call on the device (the program's weights), and any
single leaf or layer can be made again later, bit for bit, without the
rest (the reference, layer by layer, after the program's state is freed).

Kinds: ``normal`` (mean 0, std ``scale``, bell-shaped: see ``_draw``),
``ones``, ``zeros``.
"""

import zlib

import jax
import jax.numpy as jnp


_BYTES4_STD = (4 * (256**2 - 1) / 12) ** 0.5


def seed_key(seed: int):
    """``--seed`` is any whole number up to a little over 2**31."""
    return jax.random.PRNGKey(int(seed) % (2**32))


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, shape, kind, scale):
    if kind == "normal":
        # the sum of the four bytes of a random word: whole numbers, so
        # that a leaf made alone, or in another program, is the same to
        # the bit whatever the compiler fuses (an inverse error function
        # is not). Mean 510, variance 4 * (256^2 - 1) / 12; within 3.5 std
        bits = jax.random.bits(key, shape, jnp.uint32)
        total = sum((bits >> s) & 0xFF for s in (0, 8, 16, 24))
        centred = total.astype(jnp.int32) - 510
        return centred.astype(jnp.float32) * jnp.float32(scale / _BYTES4_STD)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    raise ValueError(f"unknown init kind {kind!r}")


def make_leaf(key, path, spec, dtype, index=None):
    """One leaf (``index=None``) or one slice of a stacked leaf."""
    return make_leaf_from(leaf_key(key, path), spec, dtype, index)


def make_leaf_from(k, spec, dtype, index=None):
    """As :func:`make_leaf`, from the leaf's own key."""
    shape, kind, scale = spec["shape"], spec["kind"], spec.get("scale", 1.0)
    if not spec.get("stacked"):
        return _draw(k, tuple(shape), kind, scale).astype(dtype)
    if index is not None:
        return _draw(
            jax.random.fold_in(k, index), tuple(shape[1:]), kind, scale
        ).astype(dtype)
    return jnp.stack([
        _draw(jax.random.fold_in(k, i), tuple(shape[1:]), kind, scale)
        .astype(dtype)
        for i in range(shape[0])
    ])


def unflatten(flat):
    """``{"a/b": x}`` -> nested dicts."""
    root = {}
    for path, leaf in flat.items():
        node = root
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return root


def require_same_tree(mine, theirs, family):
    """The seeded tree has to be the program's own, leaf for leaf."""
    if jax.tree.structure(mine) != jax.tree.structure(theirs) or any(
        a.shape != b.shape
        for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs))
    ):
        raise SystemExit(
            "the program's parameter tree no longer matches "
            f"benchmark/reference/{family}.py::param_spec")


def make_tree(key, spec, dtype):
    """The whole tree; call it under ``jax.jit`` to make it on the device."""
    return unflatten(
        {path: make_leaf(key, path, s, dtype) for path, s in spec.items()}
    )
