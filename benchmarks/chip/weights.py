"""Weights of a dense decoder, made from a seed by the benchmark.

The benchmark, not the program, makes the weights: the program is served
what this module makes, and the plain reference regenerates the same
values layer by layer, so neither side reads anything the other made.

Every leaf is drawn from its own key, ``fold_in(fold_in(root, leaf),
layer)``, so one layer's weights can be made alone.  Matrices are
N(0, 1/fan_in); norm gains are 1 + N(0, 0.1^2), so a gain that the
program ignored would show.  The served weights are these float32 draws
rounded to the served dtype; the reference upcasts the rounded values.

The tree follows the program's parameter layout for the dense family
(``embed``, ``segments[0]`` stacked over layers, ``final_norm``,
``unembed``); :func:`check_layout` compares it with the shapes the
program's own init would make, so a layout change fails loudly.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

GAIN_STD = 0.1


def root_key(seed: int) -> jax.Array:
    """A key for any whole ``seed``, also one wider than 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def layer_leaves(m: Dict) -> List[Tuple[Tuple[str, ...], Tuple[int, ...], int]]:
    """(path, shape, fan_in) of one layer's leaves; fan_in 0 = norm gain."""
    d, hd, h, kvh = m["d_model"], m["head_dim"], m["n_heads"], m["n_kv_heads"]
    out = [(("ln1",), (d,), 0),
           (("attn", "wq"), (d, h * hd), d),
           (("attn", "wk"), (d, kvh * hd), d),
           (("attn", "wv"), (d, kvh * hd), d),
           (("attn", "wo"), (h * hd, d), h * hd)]
    if m["qk_norm"]:
        out += [(("attn", "q_norm"), (hd,), 0), (("attn", "k_norm"), (hd,), 0)]
    out.append((("ln2",), (d,), 0))
    f = m["d_ff"]
    if m["mlp"] == "swiglu":
        out += [(("mlp", "w_gate"), (d, f), d), (("mlp", "w_up"), (d, f), d),
                (("mlp", "w_down"), (f, d), f)]
    else:
        out += [(("mlp", "w_up"), (d, f), d), (("mlp", "w_down"), (f, d), f)]
    return out


def _draw(key, shape, fan_in) -> jax.Array:
    z = jax.random.normal(key, shape, jnp.float32)
    if fan_in:
        return z * (1.0 / math.sqrt(fan_in))
    return 1.0 + GAIN_STD * z


def _set(tree: Dict, path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _layer_f32(m: Dict, root, layer) -> Dict:
    tree: Dict = {}
    for i, (path, shape, fan_in) in enumerate(layer_leaves(m)):
        key = jax.random.fold_in(jax.random.fold_in(root, 100 + i), layer)
        _set(tree, path, _draw(key, shape, fan_in))
    return tree


def _outer_f32(m: Dict, root, name: str) -> jax.Array:
    d, v = m["d_model"], m["vocab"]
    shape, fan_in, idx = {"embed": ((v, d), d, 0),
                          "final_norm": ((d,), 0, 1),
                          "unembed": ((d, v), d, 2)}[name]
    return _draw(jax.random.fold_in(root, idx), shape, fan_in)


def make_params(m: Dict, seed: int, dtype: str):
    """The whole served tree in ``dtype``, made on the device in one jit."""
    dt = jnp.dtype(dtype)

    def build(root):
        layers = jax.vmap(lambda i: _layer_f32(m, root, i))(
            jnp.arange(m["n_layers"]))
        tree = {"embed": _outer_f32(m, root, "embed"),
                "final_norm": _outer_f32(m, root, "final_norm"),
                "segments": [layers],
                "unembed": _outer_f32(m, root, "unembed")}
        return jax.tree.map(lambda a: a.astype(dt), tree)

    return jax.jit(build)(root_key(seed))


def _rounded(a: jax.Array, dtype) -> jax.Array:
    return a.astype(dtype).astype(jnp.float32)


class Frozen(dict):
    """A configuration's ``model`` block, hashable so jit can key on it."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _layer_rounded(root, layer, *, m, dtype):
    return jax.tree.map(lambda a: _rounded(a, jnp.dtype(dtype)),
                        _layer_f32(m, root, layer))


@functools.partial(jax.jit, static_argnames=("m", "name", "dtype"))
def _outer_rounded(root, *, m, name, dtype):
    return _rounded(_outer_f32(m, root, name), jnp.dtype(dtype))


def reference_layer(m: Dict, seed: int, layer: int, dtype: str) -> Dict:
    """Layer ``layer``'s served weights, upcast to float32."""
    return _layer_rounded(root_key(seed), jnp.int32(layer), m=Frozen(m),
                          dtype=dtype)


def reference_outer(m: Dict, seed: int, name: str, dtype: str) -> jax.Array:
    """``embed``, ``final_norm`` or ``unembed`` as served, in float32."""
    return _outer_rounded(root_key(seed), m=Frozen(m), name=name,
                          dtype=dtype)


def check_layout(params, program_shapes) -> None:
    """Raise unless ``params`` has the program's tree, shapes and dtypes."""
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), program_shapes)
    if jax.tree.structure(got) != jax.tree.structure(want) or got != want:
        raise SystemExit(f"weights layout differs from the program's: "
                         f"benchmark {got} vs program {want}")
