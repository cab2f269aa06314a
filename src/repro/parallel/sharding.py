"""Sharding rules: param-path patterns -> PartitionSpecs.

Axes (launch/mesh.py):
  * ``pod``   — data parallel across pods (multi-pod mesh only)
  * ``data``  — data parallel + FSDP (params' non-model dim)
  * ``model`` — tensor parallel (heads / ffn / vocab / experts)

Rules are *hints*: the steps run under jit with sharding propagation, so
any rule is correct; these pick the communication pattern the roofline
sees.  Name conventions come from the layer params:

  column-parallel (output dim on model): wq wk wv w_gate w_up w_uq w_uk
      w_uv wkq... ; row-parallel (input dim on model): wo w_down
  experts (E, D, F): E on model (expert parallelism)
  embed (V, D): vocab on model; unembed (D, V): vocab on model
  everything 1-D / small: replicated
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    fsdp: bool = True            # shard params' other big dim over `data`
    seq_shard_cache: bool = True  # shard decode KV caches over `data` (SP)

    def dp_axes(self, mesh: Mesh):
        axes = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
        return axes if len(axes) > 1 else (axes[0] if axes else None)


# param names that are column-parallel (model on last/output dim)
_COL = ("wq", "wk", "wv", "w_gate", "w_up", "w_uq", "w_uk", "w_uv",
        "wr", "wg", "w_in", "w_dt", "w_lora_b", "w_bcdt_T")
# row-parallel (model on first/input dim)
_ROW = ("wo", "w_down", "w_out", "wv_chan")
# per-output-dim 1-D params
_COL_BIAS = ("bq", "bk", "bv", "conv_b", "dt_bias", "d_skip")


def _divisible(n: int, mesh: Mesh, axis: str) -> bool:
    return axis in mesh.axis_names and n % mesh.shape[axis] == 0


def param_pspec(path: Tuple[str, ...], shape: Tuple[int, ...], mesh: Mesh,
                rules: ShardingRules) -> P:
    name = path[-1] if path else ""
    stacked = 0
    # stacked-segment params have a leading layer dim; detect via rule kinds
    # by matching expected ndim below and prepending None as needed.

    def spec(*dims):
        dims = list(dims)
        # pad to shape rank with leading None (layer-stack dims)
        while len(dims) < len(shape):
            dims.insert(0, None)
        # drop shardings that do not divide
        out = []
        for size, d in zip(shape[-len(dims):] if len(dims) == len(shape)
                           else shape, dims):
            if d is None:
                out.append(None)
            elif isinstance(d, str):
                out.append(d if _divisible(size, mesh, d) else None)
            else:
                sub = tuple(a for a in d if a in mesh.axis_names)
                tot = 1
                for a in sub:
                    tot *= mesh.shape[a]
                out.append(d if (sub == d and size % tot == 0) else None)
        return P(*out)

    fs = "data" if rules.fsdp else None

    if name == "embed":
        return spec("model", fs)
    if name == "unembed":
        return spec(fs, "model")
    if name == "router":
        return spec(None, None)
    is_expert = ("moe" in path and "shared" not in path
                 and name in ("w_gate", "w_up", "w_down"))
    if is_expert:
        # expert tensors (E, D, F): expert parallelism
        return spec("model", fs, None)
    if name in _COL:
        return spec(fs, "model")
    if name in _ROW:
        return spec("model", fs)
    if name in _COL_BIAS:
        return spec("model")
    if name == "conv_w":
        return spec(None, "model")
    if name == "a_log":
        return spec("model", None)
    if name == "u_bonus":
        return spec("model", None)
    # norms, mixes, small latent projections: replicated
    return P(*([None] * len(shape)))


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for e in path:
        if hasattr(e, "key"):
            names.append(str(e.key))
        elif hasattr(e, "idx"):
            names.append(str(e.idx))
        else:
            names.append(str(e))
    return tuple(names)


def param_shardings(params_shape: Any, mesh: Mesh,
                    rules: Optional[ShardingRules] = None) -> Any:
    """Map a params pytree (of ShapeDtypeStructs or arrays) to
    NamedShardings."""
    rules = rules or ShardingRules()

    def f(path, leaf):
        ps = param_pspec(_path_names(path), leaf.shape, mesh, rules)
        return NamedSharding(mesh, ps)

    return jax.tree_util.tree_map_with_path(f, params_shape)


def batch_sharding(mesh: Mesh, ndim: int, rules: Optional[ShardingRules] = None
                   ) -> NamedSharding:
    """Shard the leading (batch) dim over pod x data."""
    rules = rules or ShardingRules()
    dp = rules.dp_axes(mesh)
    return NamedSharding(mesh, P(dp, *([None] * (ndim - 1))))


# paged KV pool leaves: stacked (layer_count, n_pages, ...); dim 1 is
# the page-pool dim, the unit the paged serve loop allocates/migrates
_PAGED_POOL = ("kp", "vp", "latp")


def cache_shardings(cache_shape: Any, mesh: Mesh,
                    rules: Optional[ShardingRules] = None,
                    batch: int = 0) -> Any:
    """KV caches: batch over pod+data when divisible, else sequence over
    data (sequence parallelism for long-context decode).  Paged pool
    leaves shard their page dim over ``data`` (pages are
    batch-agnostic, so the batch rule never applies to them) and fall
    back to replication — never sequence sharding, which would split
    inside a page."""
    rules = rules or ShardingRules()
    dp = rules.dp_axes(mesh)
    dp_size = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp_size *= mesh.shape[a]

    def f(path, leaf):
        names = _path_names(path)
        shape = leaf.shape
        if names and names[-1] in _PAGED_POOL and len(shape) >= 3:
            if _divisible(shape[1], mesh, "data"):
                return NamedSharding(
                    mesh, P(None, "data", *([None] * (len(shape) - 2))))
            return NamedSharding(mesh, P(*([None] * len(shape))))
        # leading dims: (layers, batch, ...) after stacking
        if len(shape) >= 3:
            b = shape[1]
            if b % dp_size == 0 and b > 0:
                return NamedSharding(mesh, P(None, dp, *([None] * (len(shape) - 2))))
            # sequence-parallel fallback: shard the time axis over data
            if names and names[-1] in ("k", "v", "lat") and len(shape) == 5:
                s = shape[3]
                if rules.seq_shard_cache and _divisible(s, mesh, "data"):
                    return NamedSharding(mesh, P(None, None, None, "data", None))
        return NamedSharding(mesh, P(*([None] * len(shape))))

    return jax.tree_util.tree_map_with_path(f, cache_shape)


def page_table_sharding(mesh: Mesh, batch: int,
                        rules: Optional[ShardingRules] = None
                        ) -> NamedSharding:
    """Page tables (B, npb) int32: batch over pod+data when divisible,
    else replicated (tables are tiny; replication is never wrong)."""
    rules = rules or ShardingRules()
    dp = rules.dp_axes(mesh)
    dp_size = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp_size *= mesh.shape[a]
    if dp is not None and batch > 0 and batch % dp_size == 0:
        return NamedSharding(mesh, P(dp, None))
    return NamedSharding(mesh, P(None, None))
