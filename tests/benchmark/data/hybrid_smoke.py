"""A hybrid decoder at smoke widths, for the tests only: Mamba-2 and
grouped-query attention mixers in the order ``layer_types`` gives
(periodic), each followed by a top-k mixture of experts that drops no
token.  It shows that an architecture module can carry such a model
through the harness (weights, pool sizing, engine, step work) with no
edit to ``bench/``.  It claims no correctness: there is no plain
reference of this architecture, so ``control_smoke`` refuses, and no
benchmark cell uses it.

Configuration keys follow Hugging Face's hybrid Mamba/MoE configs
(``layer_types``, ``mamba_*``, ``num_local_experts``); the expert width
is ``intermediate_size``."""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

_MIXER = {"mamba": "mamba", "attention": "attn"}


def _period(types: List[str]) -> int:
    """The shortest period of the layer order; the depth holds whole
    periods."""
    p = next(p for p in range(1, len(types) + 1)
             if all(t == types[i % p] for i, t in enumerate(types)))
    if len(types) % p:
        raise ValueError(f"{len(types)} layers do not hold whole periods "
                         f"of {p}")
    return p


def dims(model: Dict[str, Any]) -> Dict[str, int]:
    types = model["layer_types"]
    if len(types) != model["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    d_in = model["mamba_expand"] * model["hidden_size"]
    if model["mamba_n_heads"] * model["mamba_d_head"] != d_in:
        raise ValueError("mamba_n_heads * mamba_d_head must be "
                         "mamba_expand * hidden_size")
    if model["mamba_n_groups"] != 1:
        raise ValueError("the engine's Mamba-2 mixer has one group")
    return {"layers": len(types), "period": _period(types),
            "attn_layers": types.count("attention"),
            "mamba_layers": types.count("mamba"),
            "d": model["hidden_size"],
            "hq": model["num_attention_heads"],
            "hkv": model["num_key_value_heads"], "hd": model["head_dim"],
            "d_in": d_in, "ssm_heads": model["mamba_n_heads"],
            "ssm_hd": model["mamba_d_head"], "S": model["mamba_d_state"],
            "K": model["mamba_d_conv"],
            "E": model["num_local_experts"],
            "topk": model["num_experts_per_tok"],
            "ff": model["intermediate_size"],
            "vocab": model["vocab_size"]}


def model_config(model: Dict[str, Any]):
    from repro.configs.base import LayerKind, ModelConfig

    if model["hidden_act"] != "silu":
        raise ValueError("only SwiGLU experts are mapped")
    n = dims(model)
    pattern = tuple(LayerKind(_MIXER[t], "moe")
                    for t in model["layer_types"][:n["period"]])
    return ModelConfig(
        name=model["name"], family="hybrid", num_layers=n["layers"],
        d_model=n["d"], num_heads=n["hq"], num_kv_heads=n["hkv"],
        head_dim=n["hd"], d_ff=n["ff"], vocab_size=n["vocab"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        num_experts=n["E"], num_experts_per_tok=n["topk"],
        capacity_factor=0.0,
        ssm_state=n["S"], ssm_head_dim=n["ssm_hd"],
        ssm_expand=model["mamba_expand"],
        ssm_chunk=model["mamba_chunk_size"], ssm_conv=n["K"],
        layer_pattern=pattern,
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=model["weights"]["dtype"])


def attention_layers(model: Dict[str, Any]) -> int:
    return dims(model)["attn_layers"]


def attention_heads(model: Dict[str, Any]) -> Dict[str, int]:
    n = dims(model)
    return {"hq": n["hq"], "hkv": n["hkv"], "hd": n["hd"]}


def _state_per_row(n: Dict[str, int]) -> int:
    """float32 conv tail and SSM state of one row in one Mamba layer."""
    conv = (n["K"] - 1) * (n["d_in"] + 2 * n["S"])
    return 4 * (conv + n["ssm_heads"] * n["ssm_hd"] * n["S"])


def state_bytes(model: Dict[str, Any], rows: int) -> int:
    """The decode step's batched carry of every Mamba layer's state,
    and each request's own copy of it, for ``rows`` rows."""
    n = dims(model)
    return 2 * rows * n["mamba_layers"] * _state_per_row(n)


def build(model: Dict[str, Any], key):
    n = dims(model)
    w = model["weights"]
    dt = jnp.dtype(w["dtype"])
    d = n["d"]

    def scale(k, width):
        return weights.scale(k, width, w["norm_scale_jitter"], dt)

    def attn(k):
        ks = jax.random.split(k, 4)
        hq, hkv, hd = n["hq"], n["hkv"], n["hd"]
        return {"wq": {"w": weights.dense(ks[0], d, hq * hd, dt,
                                          w["q_gain"])},
                "wk": {"w": weights.dense(ks[1], d, hkv * hd, dt)},
                "wv": {"w": weights.dense(ks[2], d, hkv * hd, dt)},
                "wo": {"w": weights.dense(ks[3], hq * hd, d, dt)}}

    def mamba(k):
        ks = jax.random.split(k, 7)
        H, d_in, conv_dim = n["ssm_heads"], n["d_in"], n["d_in"] + 2 * n["S"]
        # decay rates and steps as Mamba-2 draws them: A in [1, 16],
        # softplus(dt_bias) log-uniform in [1e-3, 0.1]
        a = jax.random.uniform(ks[3], (H,), jnp.float32, 1.0, 16.0)
        step = jnp.exp(jax.random.uniform(ks[4], (H,), jnp.float32,
                                          np.log(1e-3), np.log(0.1)))
        return {
            "in_proj": {"w": weights.dense(ks[0], d, 2 * d_in + 2 * n["S"]
                                           + H, dt)},
            "conv_w": weights.dense(ks[1], n["K"], conv_dim, dt),
            "conv_b": (w["bias_std"] * jax.random.normal(
                ks[2], (conv_dim,), jnp.float32)).astype(dt),
            "A_log": jnp.log(a),
            "D": jnp.ones((H,), jnp.float32),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "norm": scale(ks[5], d_in),
            "out_proj": {"w": weights.dense(ks[6], d_in, d, dt)},
        }

    def moe(k):
        ks = jax.random.split(k, 3)
        E, ff = n["E"], n["ff"]
        return {
            "router": weights.dense(ks[0], d, E, jnp.float32),
            "wi": jax.vmap(lambda kk: weights.dense(kk, d, 2 * ff, dt))(
                jax.random.split(ks[1], E)),
            "wo": jax.vmap(lambda kk: weights.dense(kk, ff, d, dt))(
                jax.random.split(ks[2], E)),
        }

    def period(k):
        ks = jax.random.split(k, n["period"])
        out = {}
        for i, t in enumerate(model["layer_types"][:n["period"]]):
            kk = jax.random.split(ks[i], 4)
            mixer = {"mamba": mamba, "attn": attn}[_MIXER[t]]
            out[f"sub{i}"] = {"ln": {"scale": scale(kk[0], d)},
                              _MIXER[t]: mixer(kk[1]),
                              "ln2": {"scale": scale(kk[2], d)},
                              "ffn": moe(kk[3])}
        return out

    k_emb, k_norm, k_layers, k_head = jax.random.split(key, 4)
    params = {
        "embed": (jax.random.normal(k_emb, (n["vocab"], d), jnp.float32)
                  * (1.0 / np.sqrt(d))).astype(dt),
        "final_norm": {"scale": scale(k_norm, d)},
        "blocks": jax.lax.map(period, jax.random.split(
            k_layers, n["layers"] // n["period"])),
    }
    if not model["tie_word_embeddings"]:
        params["lm_head"] = weights.dense(k_head, d, n["vocab"], dt)
    return params


def matmul_params_per_token(model: Dict[str, Any]) -> int:
    """Weights one decoded token multiplies by: the mixers'
    projections (and the Mamba layers' depthwise convolution), the
    router and the ``topk`` experts the token is routed to, and the
    head."""
    n = dims(model)
    d, d_in, S, H = n["d"], n["d_in"], n["S"], n["ssm_heads"]
    attn = d * (n["hq"] + 2 * n["hkv"]) * n["hd"] + n["hq"] * n["hd"] * d
    mamba = (d * (2 * d_in + 2 * S + H) + d_in * d
             + n["K"] * (d_in + 2 * S))
    moe = d * n["E"] + n["topk"] * 3 * d * n["ff"]
    return (n["attn_layers"] * attn + n["mamba_layers"] * mamba
            + n["layers"] * moe + d * n["vocab"])


def step_flops(model: Dict[str, Any], contexts: Sequence[int]) -> int:
    """2 per weight per row; 4 * heads * head_dim per attended token per
    attention layer; per row per Mamba layer, 5 per SSM state element
    (decay, input outer product and add; the read-out's multiply-add)."""
    n = dims(model)
    rows = len(contexts)
    dense = 2 * matmul_params_per_token(model) * rows
    attn = 4 * n["hq"] * n["hd"] * sum(contexts) * n["attn_layers"]
    ssm = 5 * n["ssm_heads"] * n["ssm_hd"] * n["S"] * rows * n["mamba_layers"]
    return dense + attn + ssm


def smoke(model: Dict[str, Any]) -> Dict[str, Any]:
    """Already at smoke widths."""
    m = copy.deepcopy(model)
    m["engine"]["headroom_bytes"] = 0
    return m


def control_smoke(model: Dict[str, Any]) -> Dict[str, Any]:
    raise NotImplementedError("no plain reference of this test-only "
                              "architecture, so no control")
