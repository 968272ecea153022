"""Parameter templates.

A model family defines ONE function returning a pytree of :class:`ParamSpec`.
From that single template we derive:

* ``init(template, key)``        -> materialized params (CPU smoke tests)
* ``abstract(template)``         -> ShapeDtypeStruct tree (dry-run, no alloc)
* ``logical_axes(template)``     -> tree of logical-axis tuples (sharding rules)

This keeps shapes, initializers and sharding axes from drifting apart.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]      # logical axis name per dim (None = never sharded)
    init: str = "normal"                 # normal | zeros | ones | scaled | a_log
    scale: float = 1.0
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def spec(shape: Sequence[int], axes: Sequence[Optional[str]], init: str = "normal",
         scale: float = 1.0, dtype: str = "bfloat16") -> ParamSpec:
    return ParamSpec(tuple(int(s) for s in shape), tuple(axes), init, scale, dtype)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw_normal(key: jax.Array, shape: tuple[int, ...], dtype: str,
                 scale: jax.Array, denom: jax.Array) -> jax.Array:
    """One leaf's normal draw, scaled and cast inside one jit: the float32
    draw never exists as a whole array (XLA fuses it into the cast), so a
    stacked full-width leaf needs only its own bytes on the device.
    ``scale`` and ``denom`` are traced so that XLA keeps the division
    (a constant divisor would become a reciprocal multiply and change the
    values)."""
    x = jax.random.normal(key, shape, jnp.float32)
    return (x * scale / denom).astype(jnp.dtype(dtype))


def init(template, key: jax.Array):
    """Materialize a template into real arrays."""
    leaves, treedef = jax.tree.flatten(template, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, s in zip(keys, leaves):
        dt = jnp.dtype(s.dtype)
        if s.init == "zeros":
            arr = jnp.zeros(s.shape, dt)
        elif s.init == "neg_ones_i32":
            arr = jnp.full(s.shape, -1, dt)
        elif s.init == "ones":
            arr = jnp.ones(s.shape, dt)
        elif s.init == "a_log":
            # mamba A_log init: log(1..N) broadcast over channels
            n = s.shape[-1]
            a = jnp.tile(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), s.shape[:-1] + (1,))
            arr = a.astype(dt)
        elif s.init == "scaled":
            fan_in = s.shape[0] if len(s.shape) >= 2 else max(int(np.prod(s.shape)), 1)
            arr = _draw_normal(k, s.shape, s.dtype, jnp.float32(1.0),
                               jnp.float32(np.sqrt(fan_in)))
        else:  # normal
            fan_in = s.shape[-2] if len(s.shape) >= 2 else max(s.shape[-1], 1)
            arr = _draw_normal(k, s.shape, s.dtype, jnp.float32(s.scale),
                               jnp.float32(np.sqrt(fan_in)))
        out.append(arr)
    return jax.tree.unflatten(treedef, out)


def abstract(template):
    """ShapeDtypeStruct tree — used by the dry-run (never allocates)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        template, is_leaf=_is_spec)


def logical_axes(template):
    """Tree of logical-axis tuples, same structure as the params."""
    return jax.tree.map(lambda s: s.axes, template, is_leaf=_is_spec)


def param_bytes(template) -> int:
    total = 0
    for s in jax.tree.leaves(template, is_leaf=_is_spec):
        total += int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
    return total


def stacked(n: int, s: ParamSpec) -> ParamSpec:
    """Stack a per-layer spec along a leading (never-sharded) 'layers' dim."""
    return dataclasses.replace(s, shape=(n,) + s.shape, axes=("layers",) + s.axes)


def stack_tree(n: int, tree):
    return jax.tree.map(lambda s: stacked(n, s), tree, is_leaf=_is_spec)
