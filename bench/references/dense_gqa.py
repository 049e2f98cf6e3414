"""Plain reference of a dense decoder with grouped-query attention
(Qwen2/Qwen3 style): pre-RMSNorm, RoPE (rotate-half), causal softmax
attention, SwiGLU MLP, final RMSNorm, head (tied to the embedding or
not).  Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``, one
sequence at a time and one layer at a time, so that it fits beside the
weights at the cells' sizes.  It imports nothing of the program.

Departures from the published architectures, as the configuration files
state them: no q/k RMSNorm (Qwen3 has it; the engine does not).

``mode="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3 (weights per output column, activations per row,
each scaled to the format's range) and accumulates in float32; the rest
is as in ``"f32"``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PAD = 512          # sequences are padded to a multiple of this (causal)
Q_CHUNK = 512      # query rows per block of the attention scores


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    fmax = float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / fmax
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode):
    """(T, din) @ (din, dout) in float32, or with float8 operands."""
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (T, H, hd); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _layer(x, blocks, i, cfg, mode):
    d, hq, hkv, hd, ff, eps, theta = cfg
    p = jax.tree.map(lambda a: a[i], blocks["sub0"])
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, p["ln"]["scale"], eps)

    def proj(name, heads):
        y = _mm(h, p["attn"][name]["w"], mode)
        if "b" in p["attn"][name]:
            y = y + p["attn"][name]["b"].astype(jnp.float32)
        return y.reshape(T, heads, hd)

    q = _rope(proj("wq", hq), pos, theta)
    k = _rope(proj("wk", hkv), pos, theta)
    v = proj("wv", hkv)
    g = hq // hkv
    k = jnp.repeat(k, g, axis=1)                 # (T, hq, hd)
    v = jnp.repeat(v, g, axis=1)

    def block(qs):                               # qs: query row start
        qb = jax.lax.dynamic_slice_in_dim(q, qs, Q_CHUNK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(hd)
        causal = (qs + jnp.arange(Q_CHUNK))[:, None] >= pos[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    o = jax.lax.map(block, jnp.arange(0, T, Q_CHUNK))
    o = o.reshape(T, hq * hd)
    x = x + _mm(o, p["attn"]["wo"]["w"], mode)
    h = _rms(x, p["ln2"]["scale"], eps)
    u = _mm(h, p["ffn"]["wi"]["w"], mode)
    u = jax.nn.silu(u[:, :ff]) * u[:, ff:]
    return x + _mm(u, p["ffn"]["wo"]["w"], mode)


@partial(jax.jit, static_argnames=("eps", "tied", "mode"))
def _head(x, params, rows, eps, tied, mode):
    h = _rms(x[rows], params["final_norm"]["scale"], eps)
    w = params["embed"].T if tied else params["lm_head"]
    return _mm(h, w, mode)


def logits(params, model: Dict[str, Any], tokens: List[int],
           rows: List[int], mode: str = "f32") -> np.ndarray:
    """Logits (len(rows), vocab) at positions ``rows`` of ``tokens``."""
    cfg = (model["hidden_size"], model["num_attention_heads"],
           model["num_key_value_heads"], model["head_dim"],
           model["intermediate_size"], float(model["rms_norm_eps"]),
           float(model["rope_theta"]))
    T = len(tokens)
    Tp = -(-T // PAD) * PAD
    ids = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, Tp - T)))
    x = params["embed"][ids].astype(jnp.float32)
    for i in range(model["num_hidden_layers"]):
        x = _layer(x, params["blocks"], i, cfg, mode)
    out = _head(x, params, jnp.asarray(np.asarray(rows, np.int32)),
                float(model["rms_norm_eps"]),
                bool(model["tie_word_embeddings"]), mode)
    return np.asarray(out)
