"""Weights from `--seed`, made by the benchmark and never by the
program: the system under test and the plain reference each call
`leaf` for the tensors they need, with the same key, name, shape and
stored type, and so hold the same values without either taking anything
from the other.
"""

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed):
    """A PRNG key for any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed >> 31), seed & 0x7FFFFFFF
    )


def leaf(key, name, shape, kind, std, dtype, layer=None):
    """One tensor. ``kind``: "normal" (std * N(0,1)), "ln_weight"
    (1 + 0.02 N), "small" (0.02 N: biases, so that a dropped bias shows).
    ``layer`` may be traced, so one compiled program serves every layer.
    The value is rounded to ``dtype``, the type the configuration stores
    it in; the reference up-casts that and so starts from the same
    numbers."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    x = jax.random.normal(k, shape, jnp.float32)
    if kind == "normal":
        x = std * x
    elif kind == "ln_weight":
        x = 1.0 + 0.02 * x
    elif kind == "small":
        x = 0.02 * x
    else:
        raise ValueError(f"unknown kind {kind!r}")
    return x.astype(dtype)


def nest(flat):
    """{"a/b/c": x} -> {"a": {"b": {"c": x}}}: the shape of a flax
    parameter tree."""
    tree = {}
    for path, value in flat.items():
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return tree
