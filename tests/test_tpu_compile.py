"""Compile the main-path kernels and the qwen3-4b decode step for a
described TPU v5e, with interpret mode off, at published widths.

Nothing runs: the TPU compiler installed beside JAX compiles for a chip
that is described and not attached, and refuses what the chip would
refuse (tile-misaligned slices, too much VMEM, a program that does not
fit HBM).  The topology is described inside a module fixture, never at
import: only one process may load the TPU library at a time.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = int(15.75 * 2**30)     # what the v5e compiler lets a program use


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A program compiled for an absent chip can be written to the
    persistent cache but never read back; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def chip_dispatch(monkeypatch):
    """Model code asks ``resolve_interpret(None)``, which sees this
    host's CPU backend; make the dispatchers choose the chip path."""
    import repro.kernels.dae_gather.ops as gather_ops
    import repro.kernels.flash_attention.ops as flash_ops
    for mod in (gather_ops, flash_ops):
        monkeypatch.setattr(mod, "resolve_interpret",
                            lambda i: False if i is None else i)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("method", ["pipelined", "rif"])
def test_dae_gather_compiles_at_qwen3_vocab(one_chip, method):
    from repro.kernels.dae_gather import dae_gather
    table = _sds((151_936, 2_560), jnp.bfloat16, one_chip)
    idx = _sds((256,), jnp.int32, one_chip)
    fn = jax.jit(lambda t, i: dae_gather(t, i, method=method, block_d=512,
                                         chunk=64, rif=8, interpret=False))
    compiled = fn.lower(table, idx).compile()
    assert _kernels(compiled) == 1
    # the table is read in place: no relayout copy of its 778 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_flash_decode_paged_compiles_at_qwen3_widths(one_chip):
    from repro.kernels.flash_attention.ops import flash_decode_paged
    b, h, kvh, hd, page, npb = 8, 32, 8, 128, 16, 128
    n_pages = 1 + b * npb
    args = (_sds((b, h, hd), jnp.bfloat16, one_chip),
            _sds((n_pages, kvh, page, hd), jnp.bfloat16, one_chip),
            _sds((n_pages, kvh, page, hd), jnp.bfloat16, one_chip),
            _sds((b, npb), jnp.int32, one_chip),
            _sds((b,), jnp.int32, one_chip))
    fn = jax.jit(lambda *a: flash_decode_paged(*a, interpret=False))
    assert _kernels(fn.lower(*args).compile()) == 1


def test_grouped_matmul_compiles_at_granite_moe_widths(one_chip):
    from repro.kernels.grouped_matmul.ops import grouped_matmul
    # granite-moe-3b-a800m: d_model 1536, expert d_ff 512, 40 experts;
    # 512 routed tokens (64 tokens x top-8) in 128-token blocks
    t, d, f, e, bt = 512, 1_536, 512, 40, 128
    args = (_sds((t, d), jnp.bfloat16, one_chip),
            _sds((e, d, f), jnp.bfloat16, one_chip),
            _sds((t // bt,), jnp.int32, one_chip))
    fn = jax.jit(lambda x, w, be: grouped_matmul(x, w, be, bt=bt,
                                                 interpret=False))
    assert _kernels(fn.lower(*args).compile()) == 1


@pytest.mark.parametrize("chunk", [1, 32])
def test_qwen3_4b_serve_step_fits_one_chip(one_chip, chip_dispatch, chunk):
    """The serve loop's step at full width, bf16 weights, 8 slots, s_max
    2048, page 16 — the masked decode step (chunk 1) and a prefill
    chunk: fits HBM with the KV pool donated (held once) and runs the
    Pallas gather, and at chunk 1 the paged decode kernel."""
    from repro.configs import get_config
    from repro.models.registry import build_model

    cfg = get_config("qwen3-4b", param_dtype="bfloat16",
                     kernel_mode="pallas")
    bundle = build_model(cfg)
    b, page, npb = 8, 16, 128

    def on_chip(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        functools.partial(bundle.cache_init_paged, b, 1 + b * npb, page)))
    step = jax.jit(bundle.prefill_paged, donate_argnums=1)
    compiled = step.lower(
        params, cache, _sds((b, chunk), jnp.int32, one_chip),
        _sds((b,), jnp.int32, one_chip), _sds((b,), jnp.int32, one_chip),
        _sds((b, npb), jnp.int32, one_chip)).compile()
    ma = compiled.memory_analysis()
    pool = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert ma.alias_size_in_bytes >= pool     # device sizes include padding
    held = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert held < HBM_BYTES, held
    # dae_gather, plus flash_decode_paged when decoding
    assert _kernels(compiled) == (2 if chunk == 1 else 1)
