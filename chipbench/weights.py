"""Seeded random weights, one leaf at a time, by the leaf's path.

Leaf ``path`` of a layer ``layer`` is drawn from a key folded from the
seed, a hash of the path and the layer index, so the served model (all
layers stacked, in one jitted call on the device) and the reference
(one layer at a time, in float32) draw the same numbers without either
handing its arrays to the other.

Scales: norm scales are 1; the embedding table and the MoE router are
N(0, 0.02^2); every other matrix is N(0, 1/fan_in) with fan_in its
second-to-last axis, so activations keep unit scale through depth.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key for any whole-number seed, including ones past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _scale(path: str, shape: Tuple[int, ...]) -> float:
    name = path.rsplit("/", 1)[-1]
    if name in ("table", "router"):
        return 0.02
    return float(shape[-2]) ** -0.5


def _path_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(k, path: str, shape: Tuple[int, ...], dtype):
    if path.rsplit("/", 1)[-1] == "scale":
        return jnp.ones(shape, dtype)
    x = jax.random.normal(k, shape, jnp.float32) * _scale(path, shape)
    return x.astype(dtype)


def leaf(key, path: str, shape: Tuple[int, ...], dtype):
    """An unstacked leaf (embedding, final norm, output head)."""
    return _draw(_path_key(key, path), path, shape, dtype)


def layer_leaf(key, path: str, layer, shape: Tuple[int, ...], dtype):
    """Layer ``layer`` of a stacked leaf; ``shape`` excludes the layer
    axis."""
    return _draw(jax.random.fold_in(_path_key(key, path), layer), path,
                 shape, dtype)


def stacked_leaf(key, path: str, shape: Tuple[int, ...], dtype):
    """All layers of a stacked leaf; ``shape[0]`` is the layer axis."""
    return jax.vmap(lambda i: layer_leaf(key, path, i, shape[1:], dtype))(
        jnp.arange(shape[0]))


def path_of(key_path) -> str:
    """'segments/0/attn/wq' from a ``jax.tree_util`` key path."""
    parts = []
    for k in key_path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
    return "/".join(parts)


def is_stacked(path: str) -> bool:
    """Leaves under ``segments/<i>/`` carry a leading layer axis."""
    return path.startswith("segments/")


def build(shapes: Dict, seed: int):
    """Every leaf of a parameter tree of ``jax.ShapeDtypeStruct`` leaves,
    in one jitted call, on the default device, in each leaf's dtype."""
    def make(key):
        def one(kp, s):
            p = path_of(kp)
            if is_stacked(p):
                return stacked_leaf(key, p, s.shape, s.dtype)
            return leaf(key, p, s.shape, s.dtype)
        return jax.tree_util.tree_map_with_path(one, shapes)
    return jax.jit(make)(base_key(seed))
