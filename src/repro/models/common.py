"""Shared model config, initializers and numeric primitives.

Pure-functional style: params are nested dicts of jnp arrays; every layer
is ``init(cfg, key) -> params`` + ``apply(cfg, params, ...) -> out``.
Param leaves are annotated for sharding by *path name convention*
(see repro.parallel.sharding): e.g. any leaf whose path ends in
``.../wq`` shards its output dim over the model axis.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """A run of ``count`` consecutive identical layers.

    kind: 'attn' (self-attn + mlp), 'moe' (self-attn + moe),
          'hymba' (parallel attn+ssm + mlp), 'hymba_global',
          'rwkv' (time-mix + channel-mix)
    """

    kind: str
    count: int


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch: str
    family: str                       # dense|moe|ssm|hybrid|encdec|vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                 # 0 -> d_model // n_heads

    # attention
    attn_kind: str = "gqa"            # gqa | mla
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    window: Optional[int] = None      # sliding-window size (local attn)
    # MLA (DeepSeek/MiniCPM3)
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_rope_dim: int = 64
    qk_nope_dim: int = 0              # 0 -> head_dim
    v_head_dim: int = 0               # 0 -> head_dim

    # mlp
    mlp_kind: str = "swiglu"          # swiglu | relu | gelu

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                 # per-expert hidden dim
    capacity_factor: float = 1.25
    first_dense_layers: int = 0       # leading dense-FFN layers (deepseek)
    pad_experts_to: int = 0           # pad expert dim for EP divisibility

    # SSM / hybrid
    ssm_state: int = 16
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_dt_rank: int = 0              # 0 -> d_model // 16
    global_attn_layers: Tuple[int, ...] = ()   # hymba full-attn layer ids

    # encoder-decoder
    n_enc_layers: int = 0
    enc_bidirectional: bool = True

    # rwkv
    rwkv_head_dim: int = 64

    # norms / embedding
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    logit_soft_cap: float = 0.0

    # numerics / kernels
    dtype: str = "bfloat16"           # activation/compute dtype
    param_dtype: str = "float32"
    kernel_mode: str = "ref"          # ref | pallas (interpret on CPU)
    attn_impl: str = "ref"            # ref (S^2) | chunked (online softmax)
    attn_chunk: int = 1024
    remat: bool = True
    remat_policy: str = "full"        # full | dots (save matmul outputs)
    scan_layers: bool = True          # False -> unrolled (cost-model probes)
    act_sp: bool = False              # sequence-parallel residual stream
    mesh_dp_axes: Tuple[str, ...] = ("data",)   # set by launch/steps.py
    mesh_tp_axis: str = "model"
    # sharded paged serving: constrain KV page pools' page dim to this
    # mesh axis inside jit (None = leave placement to propagation)
    mesh_pool_axis: Optional[str] = None

    @property
    def n_experts_padded(self) -> int:
        return max(self.n_experts, self.pad_experts_to)

    # ----- derived -----
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def adtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def qk_nope(self) -> int:
        return self.qk_nope_dim or self.hd

    @property
    def v_hd(self) -> int:
        return self.v_head_dim or self.hd

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or max(1, self.d_model // 16)

    def layer_specs(self) -> List[LayerSpec]:
        """Consecutive homogeneous segments for scan-over-layers."""
        if self.family == "ssm":
            return [LayerSpec("rwkv", self.n_layers)]
        if self.family == "hybrid":
            segs: List[LayerSpec] = []
            g = set(self.global_attn_layers)
            i = 0
            while i < self.n_layers:
                kind = "hymba_global" if i in g else "hymba"
                j = i
                while j < self.n_layers and (
                        ("hymba_global" if j in g else "hymba") == kind):
                    j += 1
                segs.append(LayerSpec(kind, j - i))
                i = j
            return segs
        if self.family == "moe":
            segs = []
            if self.first_dense_layers:
                segs.append(LayerSpec("attn", self.first_dense_layers))
            segs.append(LayerSpec("moe", self.n_layers - self.first_dense_layers))
            return segs
        return [LayerSpec("attn", self.n_layers)]


def on_each_device(cfg: ModelConfig, kernel: Callable, *args):
    """Call a Pallas ``kernel`` where the model may run on a mesh.

    XLA cannot partition a Mosaic call.  When the model runs under a
    serving mesh (``cfg.mesh_pool_axis`` is set by the sharded serve
    loop, which enters the mesh with ``jax.set_mesh``), the call goes
    through ``shard_map`` with every operand replicated: each device runs
    the kernel whole and the result comes out replicated.  Off a mesh
    the kernel is called as is."""
    if cfg.mesh_pool_axis is None:
        return kernel(*args)
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(kernel, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


# ---------------------------------------------------------------------------
# Initializers / numeric primitives
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def rmsnorm_init(d: int, dtype) -> jnp.ndarray:
    return jnp.ones((d,), dtype)


def rmsnorm(x: jnp.ndarray, g: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * g.astype(jnp.float32)).astype(x.dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x (..., S, D_even); positions (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs        # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def activation(kind: str, x: jnp.ndarray) -> jnp.ndarray:
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind in ("silu", "swiglu"):
        return jax.nn.silu(x)
    raise ValueError(kind)


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       ignore_id: int = -1) -> jnp.ndarray:
    """logits (..., V) f32; labels int; mean over non-ignored.

    The gold logit is extracted with an iota-mask reduction rather than
    take_along_axis: a gather along the vocab axis would force the SPMD
    partitioner to all-gather the (tokens, vocab) logits when vocab is
    model-sharded (~TB/step of ICI traffic at 4k x 256; see
    EXPERIMENTS.md §Perf iteration 1), while elementwise-mask + reduce
    keeps everything vocab-sharded and only psums scalars.
    """
    logits = logits.astype(jnp.float32)
    mask = labels != ignore_id
    safe = jnp.where(mask, labels, 0)
    m = jax.lax.stop_gradient(logits.max(axis=-1))
    logz = jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)) + m
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == safe[..., None], logits, 0.0),
                   axis=-1)
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)
