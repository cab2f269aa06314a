"""Plain float32 reference of a dense decoder, and its lower-precision control.

The equations are written out here from the configuration's description,
not imported: token embedding, then per layer

    h = x + Wo . attn(rope(qknorm(rms(x) Wq)), rope(qknorm(rms(x) Wk)), rms(x) Wv)
    x = h + mlp(rms(h))

with RMSNorm ``x / sqrt(mean(x^2) + eps) * g``, rotary embedding over
the two halves of each head (``[x1 cos - x2 sin, x1 sin + x2 cos]``,
frequencies ``theta^(-i / (hd/2))``), causal softmax attention scaled by
``hd^-0.5`` where query head ``j`` reads KV head ``j // (H / KVH)``, an
MLP that is SwiGLU (``silu(h Wg) * (h Wu) Wd``) or GELU with the tanh
approximation (``gelu(h Wu) Wd``), and a final RMSNorm before the
unembedding, which is the embedding's transpose where the configuration
ties the two (``tie_embeddings``).  Every matrix product runs in float32 at
``precision="highest"``.

The reference runs over whole sequences (prompt followed by the served
tokens), one layer at a time with that layer's weights made from the
seed (:mod:`weights`), so only one layer and the activations are on the
device at once.  ``control=True`` computes every matrix product with
both operands rounded to float8 (e4m3, scaled per tensor row/column to
its largest magnitude): the step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


class Frozen(dict):
    """A configuration's ``model`` block, hashable so jit can key on it."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _q8(a: jax.Array, axis: int) -> jax.Array:
    """Round ``a`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (a / scale).astype(F8).astype(jnp.float32) * scale


def mm(a: jax.Array, w: jax.Array, control: bool) -> jax.Array:
    """a (..., K) @ w (K, N) in float32; the control rounds both operands
    to float8 (per row of ``a``, per column of ``w``) first."""
    if control:
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rotary(x, pos, theta):
    """x (..., S, hd); pos (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("m", "control", "qblock"))
def _layer(p, x, *, m, control, qblock):
    """x (R, S, D) -> (R, S, D): one decoder layer over R right-padded
    sequences (causal masking keeps padding out of real positions)."""
    r, s, d = x.shape
    h, kvh, hd, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["eps"]
    a = p["attn"]
    xn = rms(x, p["ln1"], eps)
    q = mm(xn, a["wq"], control).reshape(r, s, h, hd)
    k = mm(xn, a["wk"], control).reshape(r, s, kvh, hd)
    v = mm(xn, a["wv"], control).reshape(r, s, kvh, hd)
    if m["qk_norm"]:
        q = rms(q, a["q_norm"], eps)
        k = rms(k, a["k_norm"], eps)
    pos = jnp.arange(s)
    q = rotary(q.transpose(0, 2, 1, 3), pos, m["rope_theta"])   # (R,H,S,hd)
    k = rotary(k.transpose(0, 2, 1, 3), pos, m["rope_theta"])   # (R,KVH,S,hd)
    v = v.transpose(0, 2, 1, 3)
    g = h // kvh
    q = q.reshape(r, kvh, g, s, hd) * hd ** -0.5

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * qblock, qblock, axis=3)
        sc = jnp.einsum("rkgqd,rkpd->rkgqp", qb, k,
                        precision=jax.lax.Precision.HIGHEST)
        rows = i * qblock + jnp.arange(qblock)[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= rows, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("rkgqp,rkpd->rkgqd", pr, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(block, jnp.arange(s // qblock))        # (nb,R,KVH,G,qb,hd)
    o = jnp.moveaxis(o, 0, 3).reshape(r, h, s, hd)
    o = o.transpose(0, 2, 1, 3).reshape(r, s, h * hd)
    x = x + mm(o, a["wo"], control)
    xn = rms(x, p["ln2"], eps)
    f = p["mlp"]
    if m["mlp"] == "swiglu":
        y = jax.nn.silu(mm(xn, f["w_gate"], control)) * mm(xn, f["w_up"],
                                                           control)
    else:
        y = gelu_tanh(mm(xn, f["w_up"], control))
    return x + mm(y, f["w_down"], control)


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
        x = _layer(p, x, m=m, control=control, qblock=min(qblock, s_pad))
    sel = np.concatenate([np.stack([np.full(len(r), i), r], 1)
                          for i, r in enumerate(rows)])
    xr = x[sel[:, 0], sel[:, 1]]
    del x
    g = W.reference_outer(model, seed, "final_norm", dtype)
    if m["tie_embeddings"]:
        unembed = W.reference_outer(model, seed, "embed", dtype).T
    else:
        unembed = W.reference_outer(model, seed, "unembed", dtype)
    out = np.asarray(_head(xr, g, unembed, m=m, control=control))
    splits = np.cumsum([len(r) for r in rows])[:-1]
    return np.split(out, splits)
