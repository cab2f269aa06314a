"""Plain float32 reference of the MLA + MoE decoder (DeepSeek-V2), and
its lower-precision control.

The equations are written out here from the published DeepSeek-V2 model
(arXiv:2405.04434 and its ``modeling_deepseek.py``), not imported from
the program: token embedding, then per layer

    h = x + attn(rms(x)),   x = h + ffn(rms(h))

Attention (MLA, no query compression): ``q = rms(x) Wq`` split per head
into ``q_nope`` (``qk_nope_dim``) and ``q_rope`` (``qk_rope_dim``); the
latent ``c = rms(rms(x) Wdkv) * g`` (``kv_lora_rank``); one key
``k_rope = rms(x) Wkr`` shared by the heads; per head ``k = [c Wuk |
rope(k_rope)]``, ``v = c Wuv``; ``q = [q_nope | rope(q_rope)]``; causal
softmax scaled by ``(qk_nope_dim + qk_rope_dim)^-0.5 *
yarn_get_mscale(factor, mscale_all_dim)^2``; the heads' outputs through
``Wo``.  The rotary embedding is YaRN as ``DeepseekV2YarnRotaryEmbedding``
computes it: inverse frequencies ``base^(-2i/d)``, those past
``yarn_find_correction_range(beta_fast, beta_slow)`` divided by
``factor`` and blended by ``yarn_linear_ramp_mask`` in between, cos and
sin times ``yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor,
mscale_all_dim)``, applied to the two halves of the rope dims (the
published code first de-interleaves them, a fixed permutation of the
rope columns of ``Wq`` and ``Wkr``: random weights drawn in the
permuted order are the same model).

Feed-forward: a SwiGLU ``silu(h Wg) * (h Wu) Wd`` in the first
``first_dense_layers`` layers; after them the router's softmax over all
``n_experts``, its greedy top-``top_k`` probabilities as gates
(renormalised to sum 1 only where ``norm_topk_prob`` is set; DeepSeek-
V2-Lite does not, and its ``routed_scaling_factor`` is 1),
the sum over the held experts ``0 .. n_experts_held - 1`` only of gate
times that expert's SwiGLU of ``moe_d_ff`` (one chip's share of a layer
divided by expert parallelism; the other experts' part lies on other
chips), plus the shared experts (one SwiGLU of ``n_shared_experts *
moe_d_ff``, counted once).  A final RMSNorm, then the untied head.

Every matrix product runs in float32 at ``precision="highest"`` through
:func:`reference.mm`; ``control=True`` rounds both operands of every
product to float8 e4m3 there (the step below the configuration's
bfloat16).  The reference runs over whole sequences, one layer at a time
with that layer's weights made from the seed (:mod:`weights`), the
queries in blocks of ``qblock`` and the held experts one at a time, so
that it fits one chip once the program's state is freed.

Departures from the published model: the weights are random; the
expert layer is one chip's share of it (above); the key/value
projection ``kv_a_proj_with_mqa`` is held as ``w_dkv`` and ``w_kr``,
``kv_b_proj`` as ``w_uk`` and ``w_uv``; the rope dims are rotated as two
halves (above).  Attention is not absorbed here: the program's
absorbed latent attention is what the comparison tests.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W
from reference import mm, rms


class Frozen(dict):
    """A configuration's ``model`` block (nested groups too), hashable so
    jit can key on it."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


def yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, base: float, ys: Dict) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies, transcribed."""
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = freq_extra / ys["factor"]

    def corr_dim(rot):
        return (dim * math.log(ys["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(ys["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (freq_inter * (1 - mask) + freq_extra * mask).astype(np.float32)


def softmax_scale(m: Dict) -> float:
    ys = m["rope_scaling"]
    scale = (m["qk_nope_dim"] + m["qk_rope_dim"]) ** -0.5
    if ys.get("mscale_all_dim"):
        scale *= yarn_get_mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    return scale


def rotary(x, pos, m):
    """x (..., S, dr); pos (S,): YaRN rotary over the two halves."""
    ys = m["rope_scaling"]
    dr = x.shape[-1]
    inv = jnp.asarray(yarn_inv_freq(dr, m["rope_theta"], ys))
    ms = (yarn_get_mscale(ys["factor"], ys["mscale"])
          / yarn_get_mscale(ys["factor"], ys["mscale_all_dim"]))
    ang = pos[:, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang) * ms, jnp.sin(ang) * ms
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def swiglu(x, w_gate, w_up, w_down, control):
    return mm(jax.nn.silu(mm(x, w_gate, control)) * mm(x, w_up, control),
              w_down, control)


@functools.partial(jax.jit, static_argnames=("m", "moe", "control",
                                              "qblock"))
def _layer(p, x, *, m, moe, control, qblock):
    """x (R, S, D) -> (R, S, D): one layer over R right-padded sequences
    (causal masking keeps padding out of real positions)."""
    r_, s, d = x.shape
    h, eps = m["n_heads"], m["eps"]
    dn, dr, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    a = p["attn"]
    xn = rms(x, p["ln1"], eps)
    q = mm(xn, a["wq"], control).reshape(r_, s, h, dn + dr)
    q = q.transpose(0, 2, 1, 3)                                 # (R,H,S,.)
    c = rms(mm(xn, a["w_dkv"], control), a["kv_norm"], eps)     # (R,S,r)
    pos = jnp.arange(s)
    k_rope = rotary(mm(xn, a["w_kr"], control), pos, m)         # (R,S,dr)
    k_nope = mm(c, a["w_uk"], control).reshape(r_, s, h, dn)
    v = mm(c, a["w_uv"], control).reshape(r_, s, h, dv).transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [k_nope.transpose(0, 2, 1, 3),
         jnp.broadcast_to(k_rope[:, None], (r_, h, s, dr))], -1)
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], pos, m)], -1)
    q = q * softmax_scale(m)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * qblock, qblock, axis=2)
        sc = jnp.einsum("rhqd,rhpd->rhqp", qb, k,
                        precision=jax.lax.Precision.HIGHEST)
        rows = i * qblock + jnp.arange(qblock)[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= rows, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("rhqp,rhpd->rhqd", pr, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(block, jnp.arange(s // qblock))          # (nb,R,H,qb,dv)
    o = jnp.moveaxis(o, 0, 2).reshape(r_, h, s, dv)
    o = o.transpose(0, 2, 1, 3).reshape(r_, s, h * dv)
    x = x + mm(o, a["wo"], control)
    xn = rms(x, p["ln2"], eps)
    if not moe:
        f = p["mlp"]
        return x + swiglu(xn, f["w_gate"], f["w_up"], f["w_down"], control)
    f = p["moe"]
    probs = jax.nn.softmax(mm(xn, f["router"], control), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m["top_k"])              # (R,S,K)
    if m["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    y = swiglu(xn, f["shared"]["w_gate"], f["shared"]["w_up"],
               f["shared"]["w_down"], control)
    for e in range(m["n_experts_held"]):
        gate = jnp.sum(jnp.where(top_i == e, top_p, 0.0), -1)[..., None]
        y = y + gate * swiglu(xn, f["w_gate"][e], f["w_up"][e],
                              f["w_down"][e], control)
    return x + y


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _head(xr, g, unembed, *, m, control):
    return mm(rms(xr, g, m["eps"]), unembed, control)


def logits_at(model: Dict, dtype: str, seed: int,
              seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
              control: bool = False, bucket: int = 512,
              qblock: int = 256) -> List[np.ndarray]:
    """Reference logits of each sequence ``seqs[i]`` at positions
    ``rows[i]`` (the positions whose next token was served), float32
    (len(rows[i]), vocab) on the host."""
    m = Frozen(model)
    n = max(len(s) for s in seqs)
    s_pad = -(-n // bucket) * bucket
    tok = np.zeros((len(seqs), s_pad), np.int32)
    for i, s in enumerate(seqs):
        tok[i, :len(s)] = s
    embed = W.reference_outer(model, seed, "embed", dtype)
    x = jnp.take(embed, jnp.asarray(tok), axis=0)
    del embed
    for layer in range(m["n_layers"]):
        p = W.reference_layer(model, seed, layer, dtype)
        x = _layer(p, x, m=m, moe=layer >= m["first_dense_layers"],
                   control=control, qblock=min(qblock, s_pad))
    sel = np.concatenate([np.stack([np.full(len(r), i), r], 1)
                          for i, r in enumerate(rows)])
    xr = x[sel[:, 0], sel[:, 1]]
    del x
    g = W.reference_outer(model, seed, "final_norm", dtype)
    unembed = W.reference_outer(model, seed, "unembed", dtype)
    out = np.asarray(_head(xr, g, unembed, m=m, control=control))
    splits = np.cumsum([len(r) for r in rows])[:-1]
    return np.split(out, splits)
