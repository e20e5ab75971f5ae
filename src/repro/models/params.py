"""Parameter definition trees.

A model is described by a pytree of :class:`ParamDef` leaves. From one defs
tree we derive three things:

* ``init_params``   — materialized arrays (smoke tests, real training)
* ``param_structs`` — ``jax.ShapeDtypeStruct`` with ``NamedSharding`` attached
                      (dry-run lowering: zero allocation)
* ``param_pspecs``  — ``PartitionSpec`` tree (``in_shardings`` for pjit)

Keeping the defs symbolic is what lets the multi-pod dry-run lower a 34B
model on a 1-CPU container.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.specs import LogicalRules, to_pspec


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    logical: tuple[str | None, ...]
    init: str = "normal"          # normal | zeros | ones | embed
    # fan-in axis (or axes, multiplied) for 'normal'; default -2
    scale_axis: int | tuple[int, ...] | None = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if len(self.shape) != len(self.logical):
            raise ValueError(f"ParamDef rank mismatch: {self.shape} vs {self.logical}")


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _fan_in(d: ParamDef) -> int:
    if not d.shape:
        return 1
    ax = d.scale_axis
    if ax is None:
        ax = -2 if len(d.shape) >= 2 else 0
    axes = ax if isinstance(ax, tuple) else (ax,)
    return max(1, math.prod(d.shape[a] for a in axes))


@functools.partial(jax.jit, static_argnums=0)
def _draw_leaf(d: ParamDef, key: jax.Array) -> jax.Array:
    """One random leaf as one compiled program: the float32 draw, scale and
    cast fuse, so only the ``d.dtype`` output is ever materialized (an
    eager draw would hold a float32 copy of the whole leaf first)."""
    std = 1.0 if d.init == "embed" else 1.0 / math.sqrt(_fan_in(d))
    return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(d.dtype)


def _init_leaf(d: ParamDef, key: jax.Array) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    return _draw_leaf(d, key)


def init_params(defs, rng: jax.Array):
    leaves, treedef = jax.tree_util.tree_flatten(defs, is_leaf=is_def)
    keys = jax.random.split(rng, max(1, len(leaves)))
    out = [_init_leaf(d, k) for d, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def param_pspecs(defs, rules: LogicalRules):
    return jax.tree_util.tree_map(
        lambda d: to_pspec(d.shape, d.logical, rules, strict=True), defs, is_leaf=is_def
    )


def param_structs(defs, mesh, rules: LogicalRules):
    from jax.sharding import NamedSharding

    def one(d: ParamDef):
        # strict: array shardings must divide exactly (uneven dims — e.g. a
        # 50280 vocab on a 16-way axis — drop that axis instead)
        return jax.ShapeDtypeStruct(
            d.shape, d.dtype,
            sharding=NamedSharding(mesh, to_pspec(d.shape, d.logical, rules, strict=True)),
        )

    return jax.tree_util.tree_map(one, defs, is_leaf=is_def)


def param_count(defs) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=is_def)
    return int(sum(int(np.prod(d.shape)) for d in leaves))


def param_bytes(defs) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=is_def)
    return int(sum(int(np.prod(d.shape)) * jnp.dtype(d.dtype).itemsize for d in leaves))


def map_defs(fn: Callable[[ParamDef], ParamDef], defs):
    return jax.tree_util.tree_map(fn, defs, is_leaf=is_def)


def _shift_axis(ax):
    if ax is None:
        return None
    if isinstance(ax, tuple):
        return tuple(_shift_axis(a) for a in ax)
    return ax if ax < 0 else ax + 1


def stack_defs(defs, n: int, logical: str = "layers"):
    """Prepend a stacking axis (for scan-over-layers stacked params)."""
    return map_defs(
        lambda d: dataclasses.replace(
            d,
            shape=(n, *d.shape),
            logical=(logical, *d.logical),
            scale_axis=_shift_axis(d.scale_axis),
        ),
        defs,
    )
