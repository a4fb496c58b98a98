"""Weights from ``--seed``, leaf by leaf, made where they are used.

Every leaf is a pure function of ``(base_key(seed), its group, its place in
the group)``: the program's tree is filled from these inside one jitted call,
and the plain reference asks for the same leaves one group (one layer) at a
time, so neither side ever takes numbers the other has made.  Names are the benchmark's own
(``L3.q.w``, ``embed``, ``draft.L0.up.w`` ...), and which leaves a model has
is its architecture's to say: ``shapes`` is the name -> shape of
``cell.family.leaf_shapes(arch, prefix)``, in its order.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def base_key(seed: int) -> jax.Array:
    """A key for any whole number a little over 2**31 (the driver's seeds
    do not fit 32 signed bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def group_of(name: str, prefix: str = "") -> str:
    """The group a leaf is drawn in: its layer (``L3``), else ``top``
    (embedding, positions, final norm, head)."""
    short = name[len(prefix):]
    return prefix + (short.split(".", 1)[0] if short.startswith("L") else "top")


def groups(shapes: Dict[str, Tuple[int, ...]],
           prefix: str = "") -> Dict[str, Dict[str, Tuple[int, ...]]]:
    """Group -> (leaf name -> shape), in the order of ``shapes``."""
    out: Dict[str, Dict[str, Tuple[int, ...]]] = {}
    for name, shape in shapes.items():
        out.setdefault(group_of(name, prefix), {})[name] = shape
    return out


def group_leaves(key: jax.Array, group_id: jax.Array,
                 shapes: Dict[str, Tuple[int, ...]]) -> Dict[str, jax.Array]:
    """The float32 leaves of one group: one normal draw for the whole group
    (one random op compiles in a fraction of the time that one a leaf
    does), cut into the leaves in order.  ``key = base_key(seed)`` and
    ``group_id = group_key(name)`` are arguments, never constants, so one
    compiled maker serves every seed and every layer of the same shapes.
    Matrices and biases are normal(0, 0.02); a norm's scale is
    1 + normal(0, 0.02), so that no path through a scale or a bias is
    multiplied by exactly one or added as exactly nought."""
    import math

    key = jax.random.fold_in(key, group_id)
    sizes = [math.prod(shape) for shape in shapes.values()]
    flat = INIT_STD * jax.random.normal(key, (sum(sizes),), jnp.float32)
    out, lo = {}, 0
    for (name, shape), size in zip(shapes.items(), sizes):
        leaf = flat[lo:lo + size].reshape(shape)
        out[name] = 1.0 + leaf if name.endswith(".scale") else leaf
        lo += size
    return out


def group_key(group: str) -> jax.Array:
    return jnp.uint32(zlib.crc32(group.encode()) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _maker(shapes: Tuple, dtype: str):
    """One compiled maker per (shapes, dtype); leaves come back under their
    short names, so every layer of a model shares one program."""
    # a scale keeps its suffix: it is 1 + noise, the rest is noise
    short = {f"{i:03d}" + (".scale" if name.endswith(".scale") else ""): shape
             for i, (name, shape) in enumerate(shapes)}

    def make(key, group_id):
        return {k: v.astype(dtype)
                for k, v in group_leaves(key, group_id, short).items()}

    return jax.jit(make)


def make_group(key: jax.Array, group: str, shapes: Dict[str, Tuple[int, ...]],
               dtype: str = "float32") -> Dict[str, jax.Array]:
    """One group's leaves on the device, by name, in ``dtype``."""
    made = _maker(tuple(shapes.items()), str(dtype))(key, group_key(group))
    return {name: made[f"{i:03d}" + (".scale" if name.endswith(".scale")
                                     else "")]
            for i, name in enumerate(shapes)}


def release() -> None:
    """Drop the compiled makers.  A loaded program keeps its scratch space
    reserved on the device (the makers' float32 draws are a gigabyte each at
    Mistral's widths); a server whose largest admission needs nearly all of
    what is left has to have that back."""
    _maker.cache_clear()
    jax.clear_caches()


def all_leaves(key: jax.Array, shapes: Dict[str, Tuple[int, ...]],
               prefix: str = "", dtype: str = "float32"
               ) -> Dict[str, jax.Array]:
    """Every leaf of a model, one jitted call a group."""
    out: Dict[str, jax.Array] = {}
    for group, members in groups(shapes, prefix).items():
        out.update(make_group(key, group, members, dtype))
    return out

