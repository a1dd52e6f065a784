"""Seeded random weights, made on the device: the pieces every model module
(``servbench/models/<name>.py``) shares.

A module lists its leaves as ``(path, shape, scale)`` and makes them all in
one jitted call, already stacked over layers and in the type they are served
in, so no host copy and no second stacked copy is ever held.  Scales follow
the usual fan-in rule (``N(0, 1/fan_in)`` truncated at 3σ); a scale of None
marks a norm, whose scales are ``1 + 0.1·N(0, 1)``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], Optional[float]]


def seed_key(seed: int) -> jnp.ndarray:
    """A threefry key from a seed of any size (``PRNGKey`` keeps only its
    low 32 bits)."""
    hi, lo = np.random.SeedSequence(int(seed)).generate_state(2)
    return jnp.asarray([hi, lo], jnp.uint32)


def put(tree: Dict, path: Sequence[str], value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def draw(leaves: Sequence[Leaf], keys, dtype) -> Dict[str, Any]:
    """The tree of ``leaves``, leaf ``i`` drawn from ``keys[i]`` (call inside
    a jit)."""
    params: Dict[str, Any] = {}
    for k, (path, shape, scale) in zip(keys, leaves):
        if scale is None:
            v = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            v = jax.random.truncated_normal(k, -3.0, 3.0, shape,
                                            jnp.float32) * scale
        put(params, path, v.astype(dtype))
    return params


def count(leaves: Sequence[Leaf]) -> int:
    return sum(int(np.prod(s)) for _, s, _ in leaves)
