"""Sharding-rule edge cases (parallel/sharding.py).

Uses AbstractMesh so an 8-way ``data`` axis can be described without
forcing host devices — the rules only read axis names/sizes, and
NamedSharding accepts an abstract mesh for spec inspection."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.parallel.sharding import (ShardingRules, _divisible,
                                     cache_shardings, page_table_sharding,
                                     param_shardings)

MESH8 = AbstractMesh((8,), ("data",))


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_divisible_requires_axis_and_divisibility():
    assert _divisible(16, MESH8, "data")
    assert not _divisible(12, MESH8, "data")    # 12 % 8 != 0
    assert not _divisible(16, MESH8, "model")   # axis absent


def test_cache_batch_sharded_when_divisible():
    tree = {"attn": {"k": _sds((2, 8, 4, 64, 16)),
                     "len": _sds((2, 8), jnp.int32)}}
    out = cache_shardings(tree, MESH8)
    assert out["attn"]["k"].spec == P(None, "data", None, None, None)
    # 2-D leaves (per-slot lengths) always replicate
    assert out["attn"]["len"].spec == P(None, None)


def test_cache_seq_shard_fallback_when_batch_does_not_divide():
    # batch 6 % 8 != 0 -> contiguous k/v fall back to sequence sharding
    tree = {"attn": {"k": _sds((2, 6, 4, 64, 16))}}
    out = cache_shardings(tree, MESH8)
    assert out["attn"]["k"].spec == P(None, None, None, "data", None)


def test_cache_full_replication_when_nothing_divides():
    # batch 6 and seq 60 both indivisible by 8 -> replicated
    tree = {"attn": {"k": _sds((2, 6, 4, 60, 16))}}
    out = cache_shardings(tree, MESH8)
    assert out["attn"]["k"].spec == P(None, None, None, None, None)


def test_seq_shard_respects_rules_flag():
    tree = {"attn": {"k": _sds((2, 6, 4, 64, 16))}}
    out = cache_shardings(tree, MESH8,
                          rules=ShardingRules(seq_shard_cache=False))
    assert out["attn"]["k"].spec == P(None, None, None, None, None)


def test_paged_pool_shards_page_dim():
    # pool leaves (count, n_pages, ...): page dim over data when divisible
    tree = [{"attn": {"kp": _sds((2, 64, 4, 8, 16)),
                      "vp": _sds((2, 64, 4, 8, 16)),
                      "len": _sds((2, 6), jnp.int32)}}]
    out = cache_shardings(tree, MESH8)
    assert out[0]["attn"]["kp"].spec == P(None, "data", None, None, None)
    assert out[0]["attn"]["vp"].spec == P(None, "data", None, None, None)
    assert out[0]["attn"]["len"].spec == P(None, None)


def test_paged_pool_replicates_never_seq_shards():
    # 33 pages % 8 != 0: replicate — sequence sharding would split
    # inside a page, and the batch rule must not fire on the page dim
    tree = [{"attn": {"kp": _sds((2, 33, 4, 8, 16)),
                      "ckvp": _sds((2, 33, 8, 32))}}]
    out = cache_shardings(tree, MESH8)
    assert out[0]["attn"]["kp"].spec == P(None, None, None, None, None)
    assert out[0]["attn"]["ckvp"].spec == P(None, None, None, None)


def test_paged_pool_mla_leaves_shard():
    tree = [{"attn": {"ckvp": _sds((2, 64, 8, 32)),
                      "krp": _sds((2, 64, 8, 16))}}]
    out = cache_shardings(tree, MESH8)
    assert out[0]["attn"]["ckvp"].spec == P(None, "data", None, None)
    assert out[0]["attn"]["krp"].spec == P(None, "data", None, None)


def test_page_table_sharding():
    assert page_table_sharding(MESH8, 16).spec == P("data", None)
    assert page_table_sharding(MESH8, 6).spec == P(None, None)   # 6 % 8
    assert page_table_sharding(MESH8, 0).spec == P(None, None)


def test_param_shardings_drop_indivisible_dims():
    # wq (D=96, H*hd=100): 100 % 8 != 0 on the model axis -> that dim
    # replicates; fsdp dim 96 % 8 == 0 -> data
    mesh = AbstractMesh((8, 8), ("data", "model"))
    params = {"layers": {"wq": _sds((96, 100))}}
    out = param_shardings(params, mesh)
    assert out["layers"]["wq"].spec == P("data", None)
