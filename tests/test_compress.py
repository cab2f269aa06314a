"""Int8 wire-format gradient all-reduce (parallel/compress.py):
quantize/dequantize round-trip bounds, error feedback, and the
shard_map use over the data axis (single-device inline; 8-device in a
subprocess, matching tests/test_distributed.py)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map as _shard_map

from repro.parallel.compress import (compressed_grad_mean, compressed_psum,
                                     dequantize, quantize)

ROOT = Path(__file__).resolve().parents[1]


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(513), jnp.float32)
    q, scale = quantize(g)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    # round-to-nearest against a max-abs/127 scale: error <= scale/2
    err = np.abs(np.asarray(dequantize(q, scale) - g))
    assert err.max() <= float(scale) / 2 + 1e-7
    # the max-magnitude element maps to exactly +-127
    assert int(np.abs(np.asarray(q)).max()) == 127


def test_quantize_handles_zeros():
    q, scale = quantize(jnp.zeros(7, jnp.float32))
    assert np.all(np.asarray(q) == 0) and float(scale) > 0.0


def _run_psum_1dev(g, residual):
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fn = _shard_map(lambda gg, rr: compressed_psum(gg, rr, "data"),
                    mesh=mesh, in_specs=(P("data"), P("data")),
                    out_specs=(P("data"), P("data")))
    return fn(g, residual)


def test_compressed_psum_single_shard_identity():
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal((1, 64)), jnp.float32)
    r = jnp.zeros_like(g)
    mean, new_r = _run_psum_1dev(g, r)
    # one participant: mean is dequantize(quantize(g)) and the residual
    # is exactly the quantization error (error feedback invariant)
    np.testing.assert_allclose(np.asarray(mean + new_r), np.asarray(g),
                               rtol=0, atol=1e-6)
    q, scale = quantize(g[0])
    np.testing.assert_allclose(np.asarray(mean[0]),
                               np.asarray(dequantize(q, scale)),
                               rtol=0, atol=1e-6)


def test_error_feedback_reduces_bias_over_steps():
    # feeding the residual forward, repeated reduction of a CONSTANT
    # gradient accumulates toward the true value (unbiasedness over time)
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.standard_normal((1, 32)) * 1e-3, jnp.float32)
    r = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    for _ in range(4):
        mean, r = _run_psum_1dev(g, r)
        total = total + mean
    np.testing.assert_allclose(np.asarray(total), np.asarray(4 * g),
                               rtol=0, atol=float(jnp.abs(g).max()) / 2)


def test_compressed_grad_mean_tree():
    rng = np.random.default_rng(3)
    grads = {"w": jnp.asarray(rng.standard_normal((1, 16)), jnp.float32),
             "b": jnp.asarray(rng.standard_normal((1, 4)), jnp.float32)}
    res = jax.tree.map(jnp.zeros_like, grads)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fn = _shard_map(lambda g, r: compressed_grad_mean(g, r, "data"),
                    mesh=mesh,
                    in_specs=(P("data"), P("data")),
                    out_specs=(P("data"), P("data")))
    mean, new_res = fn(grads, res)
    assert set(mean) == {"w", "b"} and set(new_res) == {"w", "b"}
    for k in grads:
        np.testing.assert_allclose(np.asarray(mean[k] + new_res[k]),
                                   np.asarray(grads[k]), rtol=0, atol=1e-6)


@pytest.mark.slow
def test_compressed_allreduce_8_devices():
    """Eight shards with different scales: the int32 payload sum against
    the shared max scale stays close to the exact f32 mean."""
    snippet = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map as _shard_map
        from repro.parallel.compress import compressed_psum

        assert jax.device_count() == 8
        mesh = Mesh(np.array(jax.devices()), ("data",))
        rng = np.random.default_rng(0)
        # per-shard gradients with very different magnitudes
        g = rng.standard_normal((8, 256)).astype(np.float32)
        g *= (10.0 ** rng.integers(-2, 3, size=(8, 1))).astype(np.float32)
        r = np.zeros_like(g)
        fn = _shard_map(lambda gg, rr: compressed_psum(gg, rr, "data"),
                        mesh=mesh, in_specs=(P("data"), P("data")),
                        out_specs=(P("data"), P("data")))
        mean, res = fn(jnp.asarray(g), jnp.asarray(r))
        mean = np.asarray(mean)
        exact = g.mean(0, keepdims=True)
        # every shard sees the same reduced mean
        assert np.allclose(mean, np.broadcast_to(mean[:1], mean.shape))
        # int8 wire format against the max scale: per-element error is
        # bounded by ~n_shards * scale_max / (2 * n)
        scale_max = np.abs(g).max() / 127.0
        assert np.abs(mean[0] - exact[0]).max() <= scale_max
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "OK" in out.stdout
