"""Random weights from the seed, made on the device in one jitted call,
in bfloat16 and in the engine's parameter layout.

The benchmark makes the weights, so the plain reference may use them:
nothing the program computed goes into the comparison.  The layout is
checked against the program's own ``init_params`` shapes (``check``)."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def dims(model: Dict[str, Any]) -> Dict[str, int]:
    return {"layers": model["num_hidden_layers"],
            "d": model["hidden_size"],
            "hq": model["num_attention_heads"],
            "hkv": model["num_key_value_heads"],
            "hd": model["head_dim"],
            "ff": model["intermediate_size"],
            "vocab": model["vocab_size"]}


def jax_key(seed: int, stream: int = 0):
    """A JAX key from any non-negative seed (wider than 32 bits too)."""
    word = np.random.SeedSequence([int(seed), 1000 + stream]).generate_state(1)
    return jax.random.PRNGKey(int(word[0] & 0x7FFFFFFF))


def _build(model: Dict[str, Any], key):
    n = dims(model)
    w = model["weights"]
    dt = jnp.dtype(w["dtype"])
    bias = bool(model["attention_bias"])

    def dense(k, din, dout, gain=1.0):
        x = jax.random.normal(k, (din, dout), jnp.float32)
        return (x * (gain / np.sqrt(din))).astype(dt)

    def scale(k, d):
        u = jax.random.uniform(k, (d,), jnp.float32, -1.0, 1.0)
        return (1.0 + w["norm_scale_jitter"] * u).astype(dt)

    def proj(k, din, dout, gain=1.0):
        kw, kb = jax.random.split(k)
        p = {"w": dense(kw, din, dout, gain)}
        if bias:
            p["b"] = (w["bias_std"] * jax.random.normal(
                kb, (dout,), jnp.float32)).astype(dt)
        return p

    def layer(k):
        ks = jax.random.split(k, 8)
        d, hq, hkv, hd, ff = n["d"], n["hq"], n["hkv"], n["hd"], n["ff"]
        return {
            "ln": {"scale": scale(ks[0], d)},
            "attn": {"wq": proj(ks[1], d, hq * hd, w["q_gain"]),
                     "wk": proj(ks[2], d, hkv * hd),
                     "wv": proj(ks[3], d, hkv * hd),
                     "wo": {"w": dense(ks[4], hq * hd, d)}},
            "ln2": {"scale": scale(ks[5], d)},
            "ffn": {"wi": {"w": dense(ks[6], d, 2 * ff)},
                    "wo": {"w": dense(ks[7], ff, d)}},
        }

    k_emb, k_norm, k_layers, k_head = jax.random.split(key, 4)
    params = {
        "embed": (jax.random.normal(k_emb, (n["vocab"], n["d"]), jnp.float32)
                  * (1.0 / np.sqrt(n["d"]))).astype(dt),
        "final_norm": {"scale": scale(k_norm, n["d"])},
        # one layer at a time, written into the stack, so the f32 draws
        # of a single layer are the largest temporary
        "blocks": {"sub0": jax.lax.map(
            layer, jax.random.split(k_layers, n["layers"]))},
    }
    if not model["tie_word_embeddings"]:
        params["lm_head"] = dense(k_head, n["d"], n["vocab"])
    return params


def make(model: Dict[str, Any], seed: int):
    """The weights for ``seed``; the same seed gives the same weights."""
    return jax.block_until_ready(
        jax.jit(lambda k: _build(model, k))(jax_key(seed)))


def nbytes(params) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(params))


def check(params, expected) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from the
    program's (``expected``: ``jax.eval_shape`` of its initialiser)."""
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), expected)
    if got != want:
        raise ValueError(f"benchmark weights do not match the engine's "
                         f"parameter layout:\n{got}\n!=\n{want}")
