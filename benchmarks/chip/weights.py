"""Weights of a model, made from a seed by the benchmark.

The benchmark, not the program, makes the weights: the program is served
what this module makes, and the plain reference regenerates the same
values layer by layer, so neither side reads anything the other made.

Every leaf of a layer is drawn from its own key, ``fold_in(fold_in(root,
100 + leaf), layer)``, ``layer`` being the layer's index across all
segments, so one layer's weights can be made alone; the outer leaves
(embedding, final norm, head) from ``fold_in(root, i)``, ``i`` their
place in the family's list.  Matrices are N(0, 1/fan_in); norm gains
are 1 + N(0, 0.1^2), so a gain that the program ignored would show.  The
served weights are these float32 draws rounded to the served dtype; the
reference upcasts the rounded values.

Which leaves there are, and their shapes, is the family's
(``families/<family>.py``, named by the configuration): the tree is the
family's outer leaves and ``segments``, one tree stacked over layers per
segment.  :func:`check_layout` compares it with the shapes the program's
own init would make, so a layout change fails loudly.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

import families

GAIN_STD = 0.1


def root_key(seed: int) -> jax.Array:
    """A key for any whole ``seed``, also one wider than 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _draw(key, shape, fan_in) -> jax.Array:
    z = jax.random.normal(key, shape, jnp.float32)
    if fan_in:
        return z * (1.0 / math.sqrt(fan_in))
    return 1.0 + GAIN_STD * z


def _set(tree: Dict, path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _layer_f32(leaves, root, layer) -> Dict:
    tree: Dict = {}
    for i, (path, shape, fan_in) in enumerate(leaves):
        key = jax.random.fold_in(jax.random.fold_in(root, 100 + i), layer)
        _set(tree, path, _draw(key, shape, fan_in))
    return tree


def make_params(m: Dict, seed: int, dtype: str):
    """The whole served tree in ``dtype``, made on the device in one jit."""
    dt = jnp.dtype(dtype)
    fam = families.load(m)

    def build(root):
        tree = {name: _draw(jax.random.fold_in(root, i), shape, fan_in)
                for i, (name, shape, fan_in) in enumerate(fam.outer_leaves(m))}
        tree["segments"], first = [], 0
        for count, leaves in fam.segments(m):
            tree["segments"].append(jax.vmap(
                lambda i, leaves=leaves: _layer_f32(leaves, root, i))(
                    jnp.arange(first, first + count)))
            first += count
        return jax.tree.map(lambda a: a.astype(dt), tree)

    return jax.jit(build)(root_key(seed))


def _rounded(a: jax.Array, dtype) -> jax.Array:
    return a.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("leaves", "dtype"))
def _layer_rounded(root, layer, *, leaves, dtype):
    return jax.tree.map(lambda a: _rounded(a, jnp.dtype(dtype)),
                        _layer_f32(leaves, root, layer))


@functools.partial(jax.jit, static_argnames=("i", "shape", "fan_in", "dtype"))
def _outer_rounded(root, *, i, shape, fan_in, dtype):
    return _rounded(_draw(jax.random.fold_in(root, i), shape, fan_in),
                    jnp.dtype(dtype))


def reference_layer(m: Dict, seed: int, layer: int, dtype: str) -> Dict:
    """Layer ``layer``'s served weights (its index across all segments),
    upcast to float32."""
    first = 0
    for count, leaves in families.load(m).segments(m):
        if layer < first + count:
            return _layer_rounded(root_key(seed), jnp.int32(layer),
                                  leaves=tuple(leaves), dtype=dtype)
        first += count
    raise IndexError(f"layer {layer} of a {first}-layer model")


def reference_outer(m: Dict, seed: int, name: str, dtype: str) -> jax.Array:
    """An outer leaf (``embed``, ``final_norm``, ...) as served, in float32."""
    for i, (n, shape, fan_in) in enumerate(families.load(m).outer_leaves(m)):
        if n == name:
            return _outer_rounded(root_key(seed), i=i, shape=shape,
                                  fan_in=fan_in, dtype=dtype)
    raise KeyError(f"the {m['family']} family has no outer leaf {name!r}")


def check_layout(params, program_shapes) -> None:
    """Raise unless ``params`` has the program's tree, shapes and dtypes."""
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), program_shapes)
    if jax.tree.structure(got) != jax.tree.structure(want) or got != want:
        raise SystemExit(f"weights layout differs from the program's: "
                         f"benchmark {got} vs program {want}")
