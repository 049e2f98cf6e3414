"""Composable decoder-LM stack covering all assigned architectures.

The layer stack is expressed as a repeating *period* of heterogeneous
sub-layers (``cfg.layer_pattern``); the forward pass `lax.scan`s over
periods with stacked parameters, keeping the lowered HLO O(period) —
essential when compiling 48-64 layer models for 512 devices.  Remainder
layers (num_layers % period) are unrolled.

Supports: dense/GQA/MQA attention (+QKV bias, sliding window, softcap),
SwiGLU/GeGLU/GELU FFN, top-k MoE, Mamba-2 mixers, hybrid patterns,
encoder-decoder with cross attention (audio frontend stub), and VLM
prefix embeddings (vision frontend stub).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import LayerKind, ModelConfig
from . import layers as L
from . import mamba as M

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig, override=None):
    return override or jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #
def _sublayer_init(key, cfg: ModelConfig, kind: LayerKind, dtype) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"ln": L.norm_init(cfg.d_model, cfg, dtype)}
    if kind.mixer in ("attn", "attn_local"):
        p["attn"] = L.attn_init(ks[0], cfg, dtype)
        if cfg.cross_attention:
            p["ln_x"] = L.norm_init(cfg.d_model, cfg, dtype)
            p["xattn"] = L.attn_init(ks[3], cfg, dtype, cross=True)
    elif kind.mixer == "mamba":
        p["mamba"] = M.mamba_init(ks[0], cfg, dtype)
    if kind.ffn != "none":
        p["ln2"] = L.norm_init(cfg.d_model, cfg, dtype)
        p["ffn"] = (L.moe_init(ks[1], cfg, dtype) if kind.ffn == "moe"
                    else L.mlp_init(ks[1], cfg, dtype))
    return p


def _period_init(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, cfg.period)
    return {f"sub{i}": _sublayer_init(ks[i], cfg, cfg.layer_pattern[i], dtype)
            for i in range(cfg.period)}


def init_params(cfg: ModelConfig, key, dtype=None) -> Params:
    dtype = _dtype(cfg, dtype)
    k_embed, k_blocks, k_rem, k_head, k_enc = jax.random.split(key, 5)
    params: Params = {
        # 1/sqrt(d) embedding init keeps tied-head logits ~unit-scale at
        # init (with embed_scale the input embeddings are still ~N(0,1))
        "embed": L._init(k_embed, (cfg.vocab_size, cfg.d_model), dtype,
                         scale=cfg.d_model ** -0.5),
        "final_norm": L.norm_init(cfg.d_model, cfg, dtype),
    }
    if cfg.num_periods > 0:
        # one period at a time, written straight into the stack: building
        # per-period trees and stacking them would hold the layer
        # weights twice
        pk = jax.random.split(k_blocks, cfg.num_periods)
        params["blocks"] = jax.lax.map(
            lambda k: _period_init(k, cfg, dtype), pk)
    if cfg.remainder_layers:
        rk = jax.random.split(k_rem, cfg.remainder_layers)
        params["rem"] = [
            _sublayer_init(rk[i], cfg, cfg.layer_pattern[i], dtype)
            for i in range(cfg.remainder_layers)]
    if not cfg.tie_embeddings:
        params["lm_head"] = L._init(k_head, (cfg.d_model, cfg.vocab_size),
                                    dtype)
    if cfg.encoder_layers:
        ek = jax.random.split(k_enc, cfg.encoder_layers + 1)
        params["encoder"] = {
            "layers": [_sublayer_init(ek[i], cfg, LayerKind("attn", "mlp"),
                                      dtype)
                       for i in range(cfg.encoder_layers)],
            "final_norm": L.norm_init(cfg.d_model, cfg, dtype),
        }
    return params


# --------------------------------------------------------------------- #
# sub-layer forward (full sequence)
# --------------------------------------------------------------------- #
def _sub_forward(p: Params, cfg: ModelConfig, kind: LayerKind,
                 x: jnp.ndarray, positions: jnp.ndarray,
                 enc_out: Optional[jnp.ndarray], aux: jnp.ndarray,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, Params]:
    """Returns (x, aux, cache_entries) for one sub-layer over a full seq."""
    cache: Params = {}
    h = L.apply_norm(p["ln"], x, cfg)
    if kind.mixer in ("attn", "attn_local"):
        window = cfg.sliding_window if kind.mixer == "attn_local" else 0
        y, k, v = L.attn_full(p["attn"], cfg, h, positions, causal=True,
                              window=window)
        cache["k"], cache["v"] = k, v
        x = x + y
        if cfg.cross_attention and enc_out is not None:
            hx = L.apply_norm(p["ln_x"], x, cfg)
            ek, ev = L.cross_kv(p["xattn"], cfg, enc_out)
            x = x + L.cross_attn_full(p["xattn"], cfg, hx, ek, ev)
            cache["xk"], cache["xv"] = ek, ev
    elif kind.mixer == "mamba":
        y, (conv_s, ssm_s) = M.mamba_forward(p["mamba"], cfg, h)
        cache["conv"], cache["ssm"] = conv_s, ssm_s
        x = x + y
    x, a = L.apply_ffn_block(p, cfg, kind.ffn, x)
    return x, aux + a, cache


def _encode(params: Params, cfg: ModelConfig,
            encoder_embeds: jnp.ndarray) -> jnp.ndarray:
    """Bidirectional encoder over stubbed frontend embeddings."""
    x = encoder_embeds.astype(_dtype(cfg))
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    for lp in params["encoder"]["layers"]:
        h = L.apply_norm(lp["ln"], x, cfg)
        y, _, _ = L.attn_full(lp["attn"], cfg, h, positions, causal=False)
        x = x + y
        h2 = L.apply_norm(lp["ln2"], x, cfg)
        x = x + L.apply_mlp(lp["ffn"], cfg, h2)
    return L.apply_norm(params["encoder"]["final_norm"], x, cfg)


def _embed(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
           positions: jnp.ndarray) -> jnp.ndarray:
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    if cfg.pos_embedding == "absolute":
        d = cfg.d_model
        half = d // 2
        freq = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = positions[..., None].astype(jnp.float32) * freq
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
        x = x + pe.astype(x.dtype)
    return x


def _unembed(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = L.apply_norm(params["final_norm"], x, cfg)
    w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return (x @ w).astype(jnp.float32)


# --------------------------------------------------------------------- #
# stacked-apply entry point (fused paged decode)
# --------------------------------------------------------------------- #
def mixer_offsets(cfg: ModelConfig) -> Tuple[List[int], List[int], int, int]:
    """Per-pattern-position attention/mamba ordinals within one period.

    Returns ``(attn_off, mamba_off, attn_per_period, mamba_per_period)``;
    the dense ordinal of pattern position ``i`` in period ``pi`` is
    ``pi * attn_per_period + attn_off[i]`` — the same period-major order
    ``serving.engine.layer_kinds`` lists, i.e. the layer axis of
    the paged KV pool and of batched SSM state stacks.
    """
    attn_off, mamba_off = [], []
    na = nm = 0
    for kind in cfg.layer_pattern:
        attn_off.append(na)
        mamba_off.append(nm)
        if kind.mixer in ("attn", "attn_local"):
            na += 1
        elif kind.mixer == "mamba":
            nm += 1
    return attn_off, mamba_off, na, nm


def scan_layer_stack(cfg: ModelConfig, params: Params, body, carry):
    """Apply ``body`` to every sub-layer, scanning the period-stacked
    parameter pytree (``params["blocks"]``) and unrolling the remainder.

    ``body(carry, kind, p, attn_idx, mamba_idx) -> carry`` receives the
    sub-layer's parameters and its dense attention / mamba ordinals
    (traced scalars inside the scan, python ints for remainder layers) —
    what paged KV pools and batched recurrent-state stacks are indexed
    by.  Keeping the lowered HLO O(period) is what makes the fused
    decode step compile fast for deep models; ordering matches
    ``serving.engine.layer_kinds`` exactly.
    """
    attn_off, mamba_off, A, M = mixer_offsets(cfg)
    pat = cfg.layer_pattern

    def period_body(c, xs):
        pp, pi = xs
        for i, kind in enumerate(pat):
            c = body(c, kind, pp[f"sub{i}"],
                     pi * A + attn_off[i], pi * M + mamba_off[i])
        return c, None

    if cfg.num_periods > 0:
        carry, _ = jax.lax.scan(
            period_body, carry,
            (params["blocks"], jnp.arange(cfg.num_periods)))
    for i in range(cfg.remainder_layers):
        carry = body(carry, pat[i], params["rem"][i],
                     cfg.num_periods * A + attn_off[i],
                     cfg.num_periods * M + mamba_off[i])
    return carry


# --------------------------------------------------------------------- #
# full-sequence forward (train / prefill)
# --------------------------------------------------------------------- #
def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            prefix_embeds: Optional[jnp.ndarray] = None,
            encoder_embeds: Optional[jnp.ndarray] = None,
            collect_cache: bool = False,
            remat: bool = False,
            last_only: bool = False,
            unroll: bool = False,
            ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Params]]:
    """Returns (logits, moe_aux, cache|None).

    ``prefix_embeds`` (VLM stub) are prepended; logits cover only the
    token positions.  ``encoder_embeds`` (audio stub) feed the encoder for
    cross attention.  ``remat`` checkpoints each period (activation
    rematerialisation for the training path); ``last_only`` unembeds only
    the final position (prefill: avoids the (B, T, V) logits tensor).
    """
    B, T = tokens.shape
    n_prefix = 0 if prefix_embeds is None else prefix_embeds.shape[1]
    total = T + n_prefix
    positions = jnp.broadcast_to(jnp.arange(total)[None], (B, total))
    x = _embed(params, cfg, tokens, positions[:, n_prefix:])
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    enc_out = (_encode(params, cfg, encoder_embeds)
               if cfg.encoder_layers and encoder_embeds is not None else None)

    aux0 = jnp.zeros((), jnp.float32)

    def period_body(carry, period_params):
        x, aux = carry
        caches = {}
        for i, kind in enumerate(cfg.layer_pattern):
            x, aux, c = _sub_forward(period_params[f"sub{i}"], cfg, kind, x,
                                     positions, enc_out, aux)
            caches[f"sub{i}"] = c
        return (x, aux), (caches if collect_cache else 0)

    body = jax.checkpoint(period_body) if remat else period_body
    if cfg.num_periods > 0:
        if unroll:
            # python loop over periods: exact HLO cost accounting (XLA's
            # cost analysis counts while-loop bodies once; the dry-run
            # unrolls small-k models and extrapolates).
            carry, per_caches = (x, aux0), []
            for pi in range(cfg.num_periods):
                pp = jax.tree.map(lambda a: a[pi], params["blocks"])
                carry, c = body(carry, pp)
                per_caches.append(c)
            (x, aux) = carry
        else:
            (x, aux), per_caches = jax.lax.scan(body, (x, aux0),
                                                params["blocks"])
    else:
        aux, per_caches = aux0, None
    rem_caches = []
    for i in range(cfg.remainder_layers):
        kind = cfg.layer_pattern[i]
        x, aux, c = _sub_forward(params["rem"][i], cfg, kind, x, positions,
                                 enc_out, aux)
        rem_caches.append(c)

    x_out = x[:, -1:] if last_only else x[:, n_prefix:]
    logits = _unembed(params, cfg, x_out)
    cache = None
    if collect_cache:
        cache = {"blocks": per_caches, "rem": rem_caches}
    return logits, aux, cache


# --------------------------------------------------------------------- #
# dense decode cache
# --------------------------------------------------------------------- #
def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
               enc_len: int = 0) -> Params:
    """Allocate a dense decode cache pytree (period-stacked)."""
    dtype = _dtype(cfg, dtype)
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    K = cfg.ssm_conv
    conv_dim = cfg.d_inner + 2 * cfg.ssm_state

    def sub_cache(kind: LayerKind, lead=()):
        c: Params = {}
        if kind.mixer in ("attn", "attn_local"):
            win = cfg.sliding_window if kind.mixer == "attn_local" else 0
            ln = min(max_len, win + 1) if win else max_len
            # sliding-window layers only need a window-sized ring buffer,
            # but a dense cache keeps full length for simplicity of
            # position math; ring-buffering is the paged engine's job.
            ln = max_len
            # head-major (B, H, L, D): contraction-ready for the decode
            # QK^T/PV dots — avoids a cache-sized transpose every layer
            c["k"] = jnp.zeros(lead + (batch, hkv, ln, hd), dtype)
            c["v"] = jnp.zeros(lead + (batch, hkv, ln, hd), dtype)
            if cfg.cross_attention:
                c["xk"] = jnp.zeros(lead + (batch, hkv, enc_len, hd), dtype)
                c["xv"] = jnp.zeros(lead + (batch, hkv, enc_len, hd), dtype)
        elif kind.mixer == "mamba":
            c["conv"] = jnp.zeros(lead + (batch, K - 1, conv_dim), jnp.float32)
            c["ssm"] = jnp.zeros(
                lead + (batch, cfg.ssm_heads, cfg.ssm_head_dim,
                        cfg.ssm_state), jnp.float32)
        return c

    cache: Params = {}
    if cfg.num_periods > 0:
        cache["blocks"] = {
            f"sub{i}": sub_cache(cfg.layer_pattern[i], (cfg.num_periods,))
            for i in range(cfg.period)}
    cache["rem"] = [sub_cache(cfg.layer_pattern[i])
                    for i in range(cfg.remainder_layers)]
    return cache


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            max_len: int, dtype=None,
            prefix_embeds=None, encoder_embeds=None,
            ) -> Tuple[jnp.ndarray, Params, jnp.ndarray]:
    """Process the full prompt; returns (last_logits, cache, cache_len)."""
    B, T = tokens.shape
    logits, _, col = forward(params, cfg, tokens, prefix_embeds,
                             encoder_embeds, collect_cache=True)
    cache = init_cache(cfg, B, max_len, dtype,
                       enc_len=(encoder_embeds.shape[1]
                                if encoder_embeds is not None else 0))

    n_prefix = 0 if prefix_embeds is None else prefix_embeds.shape[1]
    total = T + n_prefix

    def fill(dst, src):
        # dst (..., B, hkv, max_len, hd); src (..., B, total, hkv, hd)
        src = jnp.swapaxes(src, -3, -2)        # -> (..., B, hkv, total, hd)
        return jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), 0, axis=dst.ndim - 2)

    def place(dst_sub, src_sub):
        out = dict(dst_sub)
        for key in dst_sub:
            if key in ("k", "v"):
                out[key] = fill(dst_sub[key], src_sub[key])
            elif key in ("xk", "xv"):
                out[key] = jnp.swapaxes(src_sub[key], -3, -2).astype(
                    dst_sub[key].dtype)
            elif key in ("conv", "ssm"):
                out[key] = src_sub[key].astype(dst_sub[key].dtype)
        return out

    new_cache: Params = {"rem": []}
    if cfg.num_periods > 0:
        new_cache["blocks"] = {
            k: place(cache["blocks"][k], col["blocks"][k])
            for k in cache["blocks"]}
    for i in range(cfg.remainder_layers):
        new_cache["rem"].append(place(cache["rem"][i], col["rem"][i]))
    cache_len = jnp.full((B,), total, jnp.int32)
    return logits[:, -1], new_cache, cache_len


# --------------------------------------------------------------------- #
# decode step (dense cache)
# --------------------------------------------------------------------- #
def _sub_decode(p: Params, cfg: ModelConfig, kind: LayerKind,
                x: jnp.ndarray, cache: Params, cache_len: jnp.ndarray,
                ) -> Tuple[jnp.ndarray, Params]:
    B = x.shape[0]
    new_cache = dict(cache)
    h = L.apply_norm(p["ln"], x, cfg)
    if kind.mixer in ("attn", "attn_local"):
        window = cfg.sliding_window if kind.mixer == "attn_local" else 0
        # project first so we can append KV before attending
        q, k_new, v_new = L.attn_project(p["attn"], cfg, h,
                                         cache_len[:, None])
        # cache layout (B, Hkv, L, hd): scatter the new token at
        # [b, h, cache_len[b]].  All-adjacent broadcast advanced indices
        # keep scatter dims in operand order — XLA emits an in-place
        # scatter instead of permuting the whole cache around it.
        H = cache["k"].shape[1]
        bidx = jnp.arange(B)[:, None]                  # (B, 1)
        hidx = jnp.arange(H)[None, :]                  # (1, H)
        pidx = cache_len[:, None]                      # (B, 1)
        k_cache = cache["k"].at[bidx, hidx, pidx].set(
            k_new[:, 0].astype(cache["k"].dtype))
        v_cache = cache["v"].at[bidx, hidx, pidx].set(
            v_new[:, 0].astype(cache["v"].dtype))
        Lc = k_cache.shape[2]
        kv_pos = jnp.broadcast_to(jnp.arange(Lc)[None], (B, Lc))
        kv_valid = kv_pos <= cache_len[:, None]
        o = L.mha(q, k_cache, v_cache, causal=True, window=window,
                  softcap=cfg.attn_logit_softcap,
                  q_positions=cache_len[:, None], kv_positions=kv_pos,
                  kv_valid=kv_valid, q_chunk=1, kv_layout="bhld")
        y = L.dense(p["attn"]["wo"],
                    o.reshape(B, 1, cfg.num_heads * cfg.head_dim))
        new_cache["k"], new_cache["v"] = k_cache, v_cache
        x = x + y
        if cfg.cross_attention and "xk" in cache:
            hx = L.apply_norm(p["ln_x"], x, cfg)
            x = x + L.cross_attn_full(p["xattn"], cfg, hx,
                                      cache["xk"], cache["xv"],
                                      kv_layout="bhld")
    elif kind.mixer == "mamba":
        y, (conv_s, ssm_s) = M.mamba_decode(p["mamba"], cfg, h,
                                            cache["conv"], cache["ssm"])
        new_cache["conv"], new_cache["ssm"] = conv_s, ssm_s
        x = x + y
    x, _ = L.apply_ffn_block(p, cfg, kind.ffn, x)
    return x, new_cache


def decode_step(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: Params, cache_len: jnp.ndarray,
                unroll: bool = False,
                ) -> Tuple[jnp.ndarray, Params]:
    """One decode step. tokens: (B, 1); cache_len: (B,) tokens already
    cached (new token KV is written at index cache_len).
    Returns (logits (B, V), new_cache)."""
    B = tokens.shape[0]
    x = _embed(params, cfg, tokens, cache_len[:, None])

    def period_body(carry, inputs):
        x = carry
        period_params, period_cache = inputs
        new_caches = {}
        for i, kind in enumerate(cfg.layer_pattern):
            x, nc = _sub_decode(period_params[f"sub{i}"], cfg, kind, x,
                                period_cache[f"sub{i}"], cache_len)
            new_caches[f"sub{i}"] = nc
        return x, new_caches

    new_cache: Params = {"rem": []}
    if cfg.num_periods > 0:
        if unroll:
            outs = []
            for pi in range(cfg.num_periods):
                inp = jax.tree.map(lambda a: a[pi],
                                   (params["blocks"], cache["blocks"]))
                x, nc = period_body(x, inp)
                outs.append(nc)
            nb = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        else:
            x, nb = jax.lax.scan(period_body, x,
                                 (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = nb
    for i in range(cfg.remainder_layers):
        kind = cfg.layer_pattern[i]
        x, nc = _sub_decode(params["rem"][i], cfg, kind, x,
                            cache["rem"][i], cache_len)
        new_cache["rem"].append(nc)
    logits = _unembed(params, cfg, x)[:, 0]
    return logits, new_cache
