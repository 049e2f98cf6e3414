"""Dense decoder (Qwen2 / Qwen3 style): every layer grouped-query
attention with RoPE, then a SwiGLU MLP; pre-RMSNorm; the head tied to
the embedding or not.  What the harness knows of its shape
(``bench.spec.architecture`` lists what a module gives)."""

from __future__ import annotations

import copy
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights


def model_config(model: Dict[str, Any]):
    """The engine's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import LayerKind, ModelConfig

    if model["hidden_act"] != "silu":
        raise ValueError("only SwiGLU MLPs are mapped")
    return ModelConfig(
        name=model["name"], family="dense",
        num_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], d_ff=model["intermediate_size"],
        vocab_size=model["vocab_size"],
        qkv_bias=bool(model["attention_bias"]),
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        layer_pattern=(LayerKind("attn", "mlp"),),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=model["weights"]["dtype"])


def dims(model: Dict[str, Any]) -> Dict[str, int]:
    return {"layers": model["num_hidden_layers"],
            "d": model["hidden_size"],
            "hq": model["num_attention_heads"],
            "hkv": model["num_key_value_heads"],
            "hd": model["head_dim"],
            "ff": model["intermediate_size"],
            "vocab": model["vocab_size"]}


def attention_layers(model: Dict[str, Any]) -> int:
    return model["num_hidden_layers"]


def attention_heads(model: Dict[str, Any]) -> Dict[str, int]:
    n = dims(model)
    return {"hq": n["hq"], "hkv": n["hkv"], "hd": n["hd"]}


def state_bytes(model: Dict[str, Any], rows: int) -> int:
    """No recurrent state: the KV pool holds all a row carries."""
    return 0


def build(model: Dict[str, Any], key):
    n = dims(model)
    w = model["weights"]
    dt = jnp.dtype(w["dtype"])
    bias = bool(model["attention_bias"])

    def scale(k, d):
        return weights.scale(k, d, w["norm_scale_jitter"], dt)

    def proj(k, din, dout, gain=1.0):
        kw, kb = jax.random.split(k)
        p = {"w": weights.dense(kw, din, dout, dt, gain)}
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
                     "wo": {"w": weights.dense(ks[4], hq * hd, d, dt)}},
            "ln2": {"scale": scale(ks[5], d)},
            "ffn": {"wi": {"w": weights.dense(ks[6], d, 2 * ff, dt)},
                    "wo": {"w": weights.dense(ks[7], ff, d, dt)}},
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
        params["lm_head"] = weights.dense(k_head, n["d"], n["vocab"], dt)
    return params


def matmul_params_per_token(model: Dict[str, Any]) -> int:
    """Weights one decoded token multiplies by: every layer's
    projections and MLP, and the head (the embedding row is a gather,
    not a product, and a tied head still multiplies)."""
    n = dims(model)
    d, hq, hkv, hd, ff = n["d"], n["hq"], n["hkv"], n["hd"], n["ff"]
    attn = d * (hq + 2 * hkv) * hd + hq * hd * d
    mlp = 3 * d * ff
    return n["layers"] * (attn + mlp) + d * n["vocab"]


def step_flops(model: Dict[str, Any], contexts: Sequence[int]) -> int:
    """Model FLOPs of one decode step over live rows with these context
    lengths (tokens attended, the new one included): 2 per weight per
    row, plus 4 * heads * head_dim per attended token per attention
    layer (scores and the weighted sum of values)."""
    n = dims(model)
    dense = 2 * matmul_params_per_token(model) * len(contexts)
    attn = 4 * n["hq"] * n["hd"] * sum(contexts) * n["layers"]
    return dense + attn


def smoke(model: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at smoke widths: the same engine settings,
    weights recipe and reference."""
    m = _resized(model, hidden_size=64, intermediate_size=96,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, vocab_size=257)
    m["engine"]["headroom_bytes"] = 0
    return m


def control_smoke(model: Dict[str, Any]) -> Dict[str, Any]:
    """Small enough for the CPU, wide enough that float8 operands move
    the logits: vocabulary 32768, width 512, 4 layers."""
    return _resized(smoke(model), vocab_size=32768, hidden_size=512,
                    intermediate_size=1024, num_hidden_layers=4,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=64)


def _resized(model: Dict[str, Any], **sizes) -> Dict[str, Any]:
    m = copy.deepcopy(model)
    m.update(sizes)
    return m
