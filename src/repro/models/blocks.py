"""Per-layer blocks for every architecture family, unified behind
``block_init(cfg, kind, key)`` / ``block_apply(cfg, kind, p, x, ...)`` so
stacks can lax.scan over homogeneous segments (common.LayerSpec)."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.common import ModelConfig, rmsnorm, rmsnorm_init
from repro.models.mlp import mlp_apply, mlp_init
from repro.models.moe import moe_apply, moe_apply_held, moe_init
from repro.models.rwkv import (rwkv_channel_apply, rwkv_channel_init,
                               rwkv_time_apply, rwkv_time_init)
from repro.models.ssm import ssm_apply, ssm_init, ssm_init_state


# where an expert layer's paged cache keeps the last step's counters
_COUNTERS = "stats"


def _attn_init(cfg: ModelConfig, key):
    if cfg.attn_kind == "mla":
        return attn.mla_init(cfg, key)
    return attn.gqa_init(cfg, key)


def _attn_apply(cfg, p, x, positions, *, causal=True, window=None, cache=None,
                valid=None, page_table=None):
    if cfg.attn_kind == "mla":
        return attn.mla_apply(cfg, p, x, positions, causal=causal, cache=cache,
                              valid=valid, page_table=page_table)
    return attn.gqa_apply(cfg, p, x, positions, causal=causal, window=window,
                          cache=cache, valid=valid, page_table=page_table)


def block_init(cfg: ModelConfig, kind: str, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    if kind == "attn":
        return {"ln1": rmsnorm_init(d, cfg.pdtype),
                "attn": _attn_init(cfg, ks[0]),
                "ln2": rmsnorm_init(d, cfg.pdtype),
                "mlp": mlp_init(cfg, ks[1])}
    if kind == "moe":
        return {"ln1": rmsnorm_init(d, cfg.pdtype),
                "attn": _attn_init(cfg, ks[0]),
                "ln2": rmsnorm_init(d, cfg.pdtype),
                "moe": moe_init(cfg, ks[1])}
    if kind in ("hymba", "hymba_global"):
        return {"ln1": rmsnorm_init(d, cfg.pdtype),
                "attn": _attn_init(cfg, ks[0]),
                "ssm": ssm_init(cfg, ks[1]),
                "ln2": rmsnorm_init(d, cfg.pdtype),
                "mlp": mlp_init(cfg, ks[2])}
    if kind == "rwkv":
        return {"ln1": rmsnorm_init(d, cfg.pdtype),
                "time": rwkv_time_init(cfg, ks[0]),
                "ln2": rmsnorm_init(d, cfg.pdtype),
                "chan": rwkv_channel_init(cfg, ks[1])}
    if kind == "xattn":  # enc-dec decoder block
        return {"ln1": rmsnorm_init(d, cfg.pdtype),
                "attn": _attn_init(cfg, ks[0]),
                "lnx": rmsnorm_init(d, cfg.pdtype),
                "xattn": attn.gqa_init(cfg, ks[1]),
                "ln2": rmsnorm_init(d, cfg.pdtype),
                "mlp": mlp_init(cfg, ks[2])}
    if kind == "enc":    # bidirectional encoder block
        return {"ln1": rmsnorm_init(d, cfg.pdtype),
                "attn": _attn_init(cfg, ks[0]),
                "ln2": rmsnorm_init(d, cfg.pdtype),
                "mlp": mlp_init(cfg, ks[1])}
    raise ValueError(f"unknown block kind {kind!r}")


def block_apply(cfg: ModelConfig, kind: str, p, x, positions, *,
                cache: Optional[Dict[str, Any]] = None,
                enc_kv=None,
                valid: Optional[jnp.ndarray] = None,
                page_table: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Optional[Dict[str, Any]]]:
    """``valid`` (B, S) marks which of the S tokens are real per batch
    row (chunked cache fill / masked decode); ``None`` means all are —
    the pre-existing train and single-token decode paths.  A paged
    attention cache (from :func:`block_cache_init_paged`) additionally
    needs the slot->page ``page_table`` (B, NPB)."""
    eps = cfg.norm_eps
    new_cache: Optional[Dict[str, Any]] = None

    if kind in ("attn", "moe", "enc"):
        causal = kind != "enc"
        window = cfg.window if kind != "enc" else None
        h, ac = _attn_apply(cfg, p["attn"], rmsnorm(x, p["ln1"], eps),
                            positions, causal=causal, window=window,
                            cache=None if cache is None else cache["attn"],
                            valid=valid, page_table=page_table)
        x = x + h
        stats = None
        if kind == "moe" and cache is not None:
            # serving: the held-expert layer, dropless (capacity drops
            # would make decode diverge from prefill)
            y, stats = moe_apply_held(cfg, p["moe"],
                                      rmsnorm(x, p["ln2"], eps), valid)
            x = x + y
        elif kind == "moe":
            x = x + moe_apply(cfg, p["moe"], rmsnorm(x, p["ln2"], eps))
        else:
            x = x + mlp_apply(cfg, p["mlp"], rmsnorm(x, p["ln2"], eps))
        if cache is not None:
            new_cache = {"attn": ac}
            if _COUNTERS in cache:
                new_cache[_COUNTERS] = stats

    elif kind in ("hymba", "hymba_global"):
        window = None if kind == "hymba_global" else cfg.window
        xin = rmsnorm(x, p["ln1"], eps)
        h_attn, ac = _attn_apply(cfg, p["attn"], xin, positions,
                                 causal=True, window=window,
                                 cache=None if cache is None else cache["attn"],
                                 valid=valid)
        h_ssm, sc = ssm_apply(cfg, p["ssm"], xin,
                              None if cache is None else cache["ssm"],
                              valid=valid)
        x = x + 0.5 * (h_attn + h_ssm)       # parallel heads, mean-combined
        x = x + mlp_apply(cfg, p["mlp"], rmsnorm(x, p["ln2"], eps))
        if cache is not None:
            new_cache = {"attn": ac, "ssm": sc}

    elif kind == "rwkv":
        st = None if cache is None else {"shift": cache["time_shift"],
                                         "wkv": cache["wkv"]}
        h, ts = rwkv_time_apply(cfg, p["time"], rmsnorm(x, p["ln1"], eps), st,
                                valid=valid)
        x = x + h
        cs = None if cache is None else cache["chan_shift"]
        h, ns = rwkv_channel_apply(cfg, p["chan"], rmsnorm(x, p["ln2"], eps), cs,
                                   valid=valid)
        x = x + h
        if cache is not None:
            new_cache = {"time_shift": ts["shift"], "wkv": ts["wkv"],
                         "chan_shift": ns}

    elif kind == "xattn":
        h, ac = _attn_apply(cfg, p["attn"], rmsnorm(x, p["ln1"], eps),
                            positions, causal=True,
                            cache=None if cache is None else cache["attn"],
                            valid=valid)
        x = x + h
        x = x + attn.cross_attn_apply(cfg, p["xattn"],
                                      rmsnorm(x, p["lnx"], eps), enc_kv,
                                      positions,
                                      per_query=valid is not None)
        x = x + mlp_apply(cfg, p["mlp"], rmsnorm(x, p["ln2"], eps))
        if cache is not None:
            new_cache = {"attn": ac}

    else:
        raise ValueError(f"unknown block kind {kind!r}")

    return x, new_cache


def block_cache_init(cfg: ModelConfig, kind: str, batch: int, s_max: int
                     ) -> Dict[str, Any]:
    """Decode-cache pytree for one layer of ``kind``."""
    hd, kvh = cfg.hd, cfg.n_kv_heads
    if kind in ("attn", "moe", "enc", "xattn", "hymba", "hymba_global"):
        if cfg.attn_kind == "mla":
            ac = {"lat": jnp.zeros((batch, 1, s_max,
                                    attn.mla_latent_lanes(cfg)), cfg.adtype),
                  "len": jnp.zeros((batch,), jnp.int32)}
        else:
            # NOTE: sliding-window layers could use a ring buffer of size
            # `window`; we allocate the full horizon for simplicity and
            # account for it in the roofline (perf TODO in EXPERIMENTS.md).
            ac = {"k": jnp.zeros((batch, kvh, s_max, hd), cfg.adtype),
                  "v": jnp.zeros((batch, kvh, s_max, hd), cfg.adtype),
                  "len": jnp.zeros((batch,), jnp.int32)}
        if kind in ("hymba", "hymba_global"):
            return {"attn": ac, "ssm": ssm_init_state(cfg, batch)}
        return {"attn": ac}
    if kind == "rwkv":
        from repro.models.rwkv import rwkv_state_init
        return rwkv_state_init(cfg, batch)
    raise ValueError(kind)


def block_cache_init_paged(cfg: ModelConfig, kind: str, batch: int,
                           n_pages: int, page: int) -> Dict[str, Any]:
    """Paged decode-cache pytree for one layer of ``kind``.

    KV lives in a shared physical pool of ``n_pages`` fixed-size pages;
    each slot addresses its logical sequence through a page table
    (passed separately at apply time).  Page 0 is reserved as the trash
    page — unmapped table entries point there and its contents are never
    attended to because ``len`` masks them.  Only pure-attention kinds
    page; recurrent state (ssm/rwkv/hymba) has no growing KV to page.

    Under sharded serving the pool leaves (``kp``/``vp``/``latp``)
    shard their page dim over the data axis — see the ``_PAGED_POOL``
    rule in ``parallel/sharding.py`` and the in-jit ``_pool_constraint``
    in ``attention.py``; ``len`` stays replicated (it is the scheduler's
    per-slot control state).  An expert layer's cache also carries the
    last step's counters (``moe_rows``, ``moe_blocks``: int32 scalars),
    which the serve loop reads through :func:`step_counters`.
    """
    hd, kvh = cfg.hd, cfg.n_kv_heads
    if kind not in ("attn", "moe"):
        raise ValueError(f"block kind {kind!r} has no paged cache")
    if cfg.attn_kind == "mla":
        ac = {"latp": jnp.zeros((n_pages, 1, page,
                                 attn.mla_latent_lanes(cfg)), cfg.adtype),
              "len": jnp.zeros((batch,), jnp.int32)}
    else:
        ac = {"kp": jnp.zeros((n_pages, kvh, page, hd), cfg.adtype),
              "vp": jnp.zeros((n_pages, kvh, page, hd), cfg.adtype),
              "len": jnp.zeros((batch,), jnp.int32)}
    if kind == "moe":
        zero = jnp.zeros((), jnp.int32)
        return {"attn": ac,
                _COUNTERS: {"moe_rows": zero, "moe_blocks": zero}}
    return {"attn": ac}


def step_counters(caches) -> Dict[str, List[Any]]:
    """The counters the last step left in a model's paged caches (one
    block cache per segment): each counter by name, one array per
    segment that keeps it.  Empty for a model with no expert layer."""
    out: Dict[str, List[Any]] = {}
    if isinstance(caches, list):
        for seg in caches:
            if isinstance(seg, dict):
                for key, val in seg.get(_COUNTERS, {}).items():
                    out.setdefault(key, []).append(val)
    return out
