"""Fused device-side decode step: one jitted dispatch per token.

The eager engine loop (``DecodeEngine._decode_phase_eager``) drives each
decode token through dozens of small jitted ops — per-layer host→device
conversions, per-layer KV-pool scatters, a python loop over layers —
so wall-clock TPOT is dominated by dispatch overhead and the PAC
kernel's memory-access savings never reach end-to-end numbers.  This
module collapses the whole step into **one** jitted, donated,
shape-bucketed device function:

* the layer stack is applied through ``transformer.scan_layer_stack``
  (``lax.scan`` over the period-stacked parameter pytree, remainder
  unrolled) so the lowered HLO stays O(period);
* tail-page metadata is pre-batched into :class:`StepBase` device
  arrays once per **plan epoch** (the interval between plan rebuilds);
  within an epoch the only per-step inputs are the previous step's
  token array, the PRNG key, and the epoch-relative step counter
  ``delta`` (query positions and tail slots advance as
  ``base + delta`` on device);
* KV tail writes, the backend's frozen-plan ``partials``
  (``AttentionBackend.partials_arrays_fn`` — the jit-safe contract),
  the tail-page attention, the POR merge, FFN/MoE/Mamba mixing,
  unembedding, and sampling all trace into the same program;
* the KV pool and batched Mamba state are **donated**
  (:class:`StepState`), so XLA updates them in place;
* every shape is bucketed (batch rows and plan arrays to powers of two
  — ``core.plan.bucket_plan``) so arrivals/completions/evictions reuse
  the compiled program; padded rows carry ``q_pos = -1`` and write
  their tail KV to the pool's trash page.

The engine dispatches step *t+1* while the host still holds step *t*'s
token array as an opaque future — host⇄device syncs happen only at
plan-rebuild and admission boundaries (see ``DecodeEngine.flush_tokens``).

``distributed/step_fn.py`` is the SPMD sibling: the same program
traced under ``shard_map`` over a ``(data, model)`` mesh, with
per-shard plans and a cross-device POR merge (DESIGN.md §9); it reuses
:class:`StepState` and the donation-warning shim from here.

:func:`make_prefill_fn` builds prefill's counterpart: one prompt chunk
as one jitted, donated program per power-of-two shape bucket.
"""

from __future__ import annotations

import warnings
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..kernels import ops, ref as ref_mod
from ..models import layers as L
from ..models import mamba as M
from ..models import transformer as T
from . import sampler

_DONATION_WARNING_SILENCED = False


def _silence_donation_warning() -> None:
    """CPU XLA often cannot honour buffer donation; the fallback copy is
    correct, just slower — don't warn about it on every fused dispatch.
    Installed once, and only when a fused step is actually built, so
    processes that never use the fused path keep the warning."""
    global _DONATION_WARNING_SILENCED
    if not _DONATION_WARNING_SILENCED:
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        _DONATION_WARNING_SILENCED = True


class StepBase(NamedTuple):
    """Per-epoch device inputs: constant between plan rebuilds."""

    row_valid: jnp.ndarray   # (B,) bool — padded bucket rows are False
    q_pos0: jnp.ndarray      # (B,) int32 query position at delta=0 (-1 pads)
    tail_page: jnp.ndarray   # (B,) int32 tail KV page (pads → pool trash)
    tail_base: jnp.ndarray   # (B,) int32 abs position of the page's slot 0
    tail_off0: jnp.ndarray   # (B,) int32 in-page slot written at delta=0


class StepState(NamedTuple):
    """Donated device state threaded through consecutive fused steps."""

    pool_k: jnp.ndarray      # (n_attn, P+1, page, n_kv, hd) paged KV pool
    pool_v: jnp.ndarray
    conv: jnp.ndarray        # (n_mamba, B, K-1, conv_dim) f32 SSM conv state
    ssm: jnp.ndarray         # (n_mamba, B, H, P_h, S) f32 SSM recurrent state


class SpecState(NamedTuple):
    """Donated pool state threaded through fused verification dispatches
    (speculative mode is attention-only, so no SSM state rides along)."""

    pool_k: jnp.ndarray      # (n_attn, P+1, page, n_kv, hd)
    pool_v: jnp.ndarray


def make_spec_step_fn(cfg: ModelConfig, backend, windows: Tuple[int, ...]):
    """Build the fused speculative *verification* dispatch (DESIGN §10).

    Returns a jitted callable

        ``fn(params, state, tokens, q_pos, write_page, write_off,
        prepared) -> (greedy_tokens, row_ok, state')``

    scoring a whole batch of verification queries — every request's
    committed-tail base query plus one query per draft-tree node — in
    ONE device dispatch.  Per attention layer it projects q/k/v for all
    rows, scatters the new K/V into each row's ``(write_page,
    write_off)`` slot (draft nodes own their page's slot 0; padded
    bucket rows hit the pool's trash page), then runs the backend's
    ``partials_arrays_fn`` over the *verify plan* — which covers the
    entire forest including partial tail pages and draft nodes, so no
    tail/POR split is needed and the partials' ``o`` is already the
    full softmax output.  Greedy argmax replaces sampling (speculative
    mode is greedy-only; acceptance happens on the host).

    Shapes bucket exactly like the regular fused step: the row axis to
    ``bucket_pow2`` and the plan through ``core.plan.bucket_plan``, so
    draft trees of varying shape reuse the compiled program.
    """
    _silence_donation_warning()
    win_slot = {w: i for i, w in enumerate(windows)}

    def step(params, state: SpecState, tokens: jnp.ndarray,
             q_pos: jnp.ndarray, write_page: jnp.ndarray,
             write_off: jnp.ndarray, prepared: Tuple[Any, ...]):
        B = tokens.shape[0]
        x = T._embed(params, cfg, tokens[:, None], q_pos[:, None])

        def body(c, kind, p, la, lm):
            x, pool_k, pool_v = c
            h = L.apply_norm(p["ln"], x, cfg)
            if kind.mixer in ("attn", "attn_local"):
                w = cfg.sliding_window if kind.mixer == "attn_local" else 0
                q, k_new, v_new = L.attn_project(p["attn"], cfg, h,
                                                 q_pos[:, None])
                pool_k = pool_k.at[la, write_page, write_off].set(
                    k_new[:, 0].astype(pool_k.dtype))
                pool_v = pool_v.at[la, write_page, write_off].set(
                    v_new[:, 0].astype(pool_v.dtype))
                o, _, _ = backend.partials_arrays_fn(
                    q[:, 0], pool_k[la], pool_v[la],
                    prepared[win_slot[w]], num_queries=B, window=w)
                y = L.dense(p["attn"]["wo"],
                            o.astype(q.dtype).reshape(
                                B, 1, cfg.num_heads * cfg.head_dim))
                x = x + y
            x, _ = L.apply_ffn_block(p, cfg, kind.ffn, x)
            return (x, pool_k, pool_v)

        x, pool_k, pool_v = T.scan_layer_stack(
            cfg, params, body, (x, state.pool_k, state.pool_v))
        with jax.named_scope("codec.spec_verify"):
            logits = T._unembed(params, cfg, x)[:, 0]       # (B, V)
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
            ok = jnp.isfinite(logits).all(-1)               # (B,) NaN guard
        return toks, ok, SpecState(pool_k, pool_v)

    return jax.jit(step, donate_argnums=(1,))


# KV position of a slot the causal mask must drop: a page slot past its
# node's cached length, a padded prefix page, a padded chunk row
NO_POS = int(np.iinfo(np.int32).max)


def make_prefill_fn(cfg: ModelConfig, temperature: float):
    """Build the prefill program for one engine: one chunk of one
    request's prompt in one jitted, donated dispatch.

    Returns a jitted callable

        ``fn(params, pool_k, pool_v, tokens, positions, prefix_pages,
        prefix_pos, write_page, write_off, last, key)
        -> (token, pool_k', pool_v')``

    ``tokens``/``positions`` are the chunk's ``(T,)`` rows, padded rows
    at position -1; ``prefix_pages`` the ``(P,)`` pool pages holding the
    cached prefix and ``prefix_pos`` the ``(P * page,)`` KV position of
    each of their slots (``NO_POS`` where the slot holds no prefix
    token); ``write_page``/``write_off`` the pool slot each row's new
    K/V lands in (the pool's trash page for rows whose KV is not
    written); ``last`` the chunk's last real row, whose logits are
    sampled with ``key``.

    Per attention layer, inside ``transformer.scan_layer_stack``, the
    body writes the rows' new K/V into the donated pool, gathers the
    prefix pages back out of it (the written slots are never prefix
    slots), and runs ``layers.mha`` over prefix + chunk exactly as the
    eager chunk does: causal, the layer's window and softcap, the
    prefix cast to the new K/V's dtype.  Every shape is a power-of-two
    bucket chosen by the caller (``core.plan.bucket_pow2``), so the
    programs number one per ``(T, P)`` bucket.  Attention stacks only:
    recurrent mixers carry state the scan does not thread.
    """
    _silence_donation_warning()

    def prefill(params, pool_k, pool_v, tokens, positions, prefix_pages,
                prefix_pos, write_page, write_off, last, key):
        T_pad = tokens.shape[0]
        q_pos = positions[None]                             # (1, T)
        kv_pos = jnp.concatenate(
            [prefix_pos, jnp.where(positions >= 0, positions, NO_POS)])[None]
        x = T._embed(params, cfg, tokens[None], q_pos)      # (1, T, d)

        def body(c, kind, p, la, lm):
            x, pool_k, pool_v = c
            h = L.apply_norm(p["ln"], x, cfg)
            w = cfg.sliding_window if kind.mixer == "attn_local" else 0
            q, k_new, v_new = L.attn_project(p["attn"], cfg, h, q_pos)
            with jax.named_scope("codec.prefill.kv_write"):
                pool_k = pool_k.at[la, write_page, write_off].set(
                    k_new[0].astype(pool_k.dtype))
                pool_v = pool_v.at[la, write_page, write_off].set(
                    v_new[0].astype(pool_v.dtype))
            with jax.named_scope("codec.prefill.gather"):
                shape = (1, -1) + k_new.shape[2:]
                pk = pool_k[la, prefix_pages].reshape(shape)
                pv = pool_v[la, prefix_pages].reshape(shape)
                k_all = jnp.concatenate([pk.astype(k_new.dtype), k_new], 1)
                v_all = jnp.concatenate([pv.astype(v_new.dtype), v_new], 1)
            with jax.named_scope("codec.prefill.attn"):
                o = L.mha(q, k_all, v_all, causal=True, window=w,
                          softcap=cfg.attn_logit_softcap,
                          q_positions=q_pos, kv_positions=kv_pos)
            y = L.dense(p["attn"]["wo"],
                        o.reshape(1, T_pad, cfg.num_heads * cfg.head_dim))
            x, _ = L.apply_ffn_block(p, cfg, kind.ffn, x + y)
            return (x, pool_k, pool_v)

        x, pool_k, pool_v = T.scan_layer_stack(cfg, params, body,
                                               (x, pool_k, pool_v))
        with jax.named_scope("codec.prefill.sample"):
            x_last = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
            logits = T._unembed(params, cfg, x_last)[:, 0]  # (1, V)
            tok = sampler.sample(logits, key, temperature)[0]
        return tok, pool_k, pool_v

    return jax.jit(prefill, donate_argnums=(1, 2))


def make_step_fn(cfg: ModelConfig, backend, windows: Tuple[int, ...],
                 temperature: float):
    """Build the fused decode step for one engine configuration.

    Returns a jitted callable

        ``fn(params, state, tokens, key, base, delta, prepared)
        -> (tokens', row_ok, key', state')``

    ``row_ok`` is a per-row finite-logits flag — essentially free to
    compute (one reduction over an array already resident for sampling)
    and carried with the deferred token array so the engine's optional
    NaN guard can quarantine a poisoned row at the next flush without
    adding a sync point.

    where ``state`` (:class:`StepState`) is donated, ``tokens`` is the
    (bucketed) batch of tokens appended this step, ``delta`` the
    epoch-relative step counter (traced — no recompile per step), and
    ``prepared`` a tuple of the backend's prepared plan arrays, one per
    attention window in ``windows``.  ``backend`` must satisfy the
    registry's jit-safe contract (``partials_arrays_fn``/``advance_fn``).
    """
    _silence_donation_warning()
    win_slot = {w: i for i, w in enumerate(windows)}

    def step(params, state: StepState, tokens: jnp.ndarray, key,
             base: StepBase, delta, prepared: Tuple[Any, ...]):
        B = tokens.shape[0]
        dlt = jnp.asarray(delta, jnp.int32) * base.row_valid.astype(jnp.int32)
        q_pos = base.q_pos0 + dlt
        tail_off = base.tail_off0 + dlt
        with jax.named_scope("codec.plan_advance"):
            advanced = tuple(backend.advance_fn(p, delta) for p in prepared)
        with jax.named_scope("codec.embed"):
            x = T._embed(params, cfg, tokens[:, None],
                         q_pos[:, None])                    # (B,1,d)

        def body(c, kind, p, la, lm):
            x, pool_k, pool_v, conv_all, ssm_all = c
            h = L.apply_norm(p["ln"], x, cfg)
            if kind.mixer in ("attn", "attn_local"):
                w = cfg.sliding_window if kind.mixer == "attn_local" else 0
                q, k_new, v_new = L.attn_project(p["attn"], cfg, h,
                                                 q_pos[:, None])
                with jax.named_scope("codec.kv_write"):
                    pool_k = pool_k.at[la, base.tail_page, tail_off].set(
                        k_new[:, 0].astype(pool_k.dtype))
                    pool_v = pool_v.at[la, base.tail_page, tail_off].set(
                        v_new[:, 0].astype(pool_v.dtype))
                with jax.named_scope("codec.pool_slice"):
                    k_pool, v_pool = pool_k[la], pool_v[la]
                qb = q[:, 0]                                # (B, h, hd)
                with jax.named_scope("codec.attn"):
                    o_f, m_f, l_f = backend.partials_arrays_fn(
                        qb, k_pool, v_pool, advanced[win_slot[w]],
                        num_queries=B, window=w)
                with jax.named_scope("codec.tail_page"):
                    kt = k_pool[base.tail_page]
                    vt = v_pool[base.tail_page]
                    o_t, m_t, l_t = ops.single_page_attention(
                        qb, kt, vt, base.tail_base, q_pos, window=w)
                with jax.named_scope("codec.por_merge"):
                    o, _, _ = ref_mod.por_ref(o_f, m_f, l_f, o_t, m_t, l_t)
                y = L.dense(p["attn"]["wo"],
                            o.astype(qb.dtype).reshape(
                                B, 1, cfg.num_heads * cfg.head_dim))
                x = x + y
            elif kind.mixer == "mamba":
                y, (conv_n, ssm_n) = M.mamba_decode(
                    p["mamba"], cfg, h, conv_all[lm], ssm_all[lm])
                conv_all = conv_all.at[lm].set(conv_n)
                ssm_all = ssm_all.at[lm].set(ssm_n)
                x = x + y
            with jax.named_scope("codec.ffn"):
                x, _ = L.apply_ffn_block(p, cfg, kind.ffn, x)
            return (x, pool_k, pool_v, conv_all, ssm_all)

        x, pool_k, pool_v, conv_all, ssm_all = T.scan_layer_stack(
            cfg, params, body,
            (x, state.pool_k, state.pool_v, state.conv, state.ssm))
        with jax.named_scope("codec.sample"):
            logits = T._unembed(params, cfg, x)[:, 0]       # (B, V)
            key, sk = jax.random.split(key)
            toks = sampler.sample(logits, sk, temperature)
            ok = jnp.isfinite(logits).all(-1)               # (B,) NaN guard
        return toks, ok, key, StepState(pool_k, pool_v, conv_all, ssm_all)

    return jax.jit(step, donate_argnums=(1,))
