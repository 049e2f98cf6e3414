"""SPMD fused decode step: one jitted dispatch per token, per mesh.

The sharded sibling of ``serving/step_fn.py``: the whole decode step —
layer scan, KV tail writes, per-shard backend partials, the
cross-device POR merge, head-TP output projection, FFN/Mamba, unembed,
sampling — traces as ONE donated program under ``shard_map`` over a
``(data, model)`` mesh:

* **data axis** — KV pages (and so plan subtasks) are sharded; every
  device runs its own shard's plan over its local pool block and the
  per-query partials of rows whose KV spans shards are packed and
  merged with the psum/all_gather-free sparse POR butterfly
  (``kernels.por.por_subgroup_merge`` — one packed ppermute per round
  over the minimal contributing subgroup).  Rows served entirely by
  replicated nodes are computed bitwise identically on every shard and
  never cross the wire; when no row needs merging the collective is
  absent from the compiled program.  A node sequence-split across data
  shards is merged by exactly the same reduction.
* **model axis** — KV heads are sharded (TP-aligned): each device
  slices its head block out of the (replicated-weight) q/k/v
  projections, attends with its local heads, and the output
  projection is a partial matmul ``psum``-reduced over ``model`` —
  the standard TP epilogue.
* everything head/page-free (embedding, FFN/MoE, Mamba state, norm,
  unembed, sampling) is computed replicated on every device, so the
  sampled tokens are bitwise identical mesh-wide and the ``P()``
  output spec is honest.  Sampling is safe to replicate because the
  sampler derives per-row keys with ``fold_in`` — draws are
  independent of mesh shape and bucket padding alike.

Tail pages: each batch row's growing page lives on exactly one data
shard; non-owner shards scatter the row's new KV into their local
**trash page** and contribute the POR identity ``(o=0, m=-inf, l=0)``
to the tail merge, so the butterfly stays shape-uniform.

Per-epoch inputs mirror the single-device ``StepBase`` but carry the
per-shard tail layout stacked on a leading ``data``-sharded axis
(:class:`ShardedStepBase`); per-shard prepared plan arrays are stacked
the same way by the engine (``core.plan.build_sharded_plan`` buckets
all shards to common shapes precisely so this stacking is
rectangular).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..kernels import ops, por as por_mod, ref as ref_mod
from ..launch.sharding import paged_pool_spec
from ..models import layers as L
from ..models import mamba as M
from ..models import transformer as T
from ..serving import sampler
from ..serving.step_fn import StepState, _silence_donation_warning

MASK_VALUE = ref_mod.MASK_VALUE


class ShardedStepBase(NamedTuple):
    """Per-epoch device inputs for the SPMD step.

    Replicated fields are ``(B,)``; tail-layout fields are stacked
    ``(D, B)`` and sharded over ``data`` (each shard reads its row).
    """

    row_valid: jnp.ndarray   # (B,) bool — padded bucket rows are False
    q_pos0: jnp.ndarray      # (B,) int32 query position at delta=0 (-1 pads)
    tail_page: jnp.ndarray   # (D, B) int32 LOCAL tail page row (else trash)
    tail_base: jnp.ndarray   # (B,) int32 abs position of the page's slot 0
    tail_off0: jnp.ndarray   # (B,) int32 in-page slot written at delta=0
    tail_owner: jnp.ndarray  # (D, B) bool — shard holds the row's tail
    #                          (one-hot per row; ALL shards for a
    #                          replicated leaf, whose tail page is that
    #                          shard's local replica)
    # sparse cross-shard merge (Bm is in the jit signature; Bm=0 skips
    # the collective entirely — fully-replicated epochs pay no wire):
    merge_gather: jnp.ndarray   # (Bm,) int32 rows to pack (pad 0)
    merge_scatter: jnp.ndarray  # (Bm,) int32 scatter target (pad B -> drop)
    contrib: jnp.ndarray        # (D,) bool — shards with local partials


def make_sharded_step_fn(cfg: ModelConfig, backend,
                         windows: Tuple[int, ...], temperature: float,
                         mesh):
    """Build the SPMD fused decode step for one engine configuration.

    Same signature as the single-device step —

        ``fn(params, state, tokens, key, base, delta, prepared)
        -> (tokens', key', state')``

    — but ``state.pool_k/v`` are mesh-sharded (pages -> ``data``, heads
    -> ``model``), ``base`` is a :class:`ShardedStepBase`, and each
    element of ``prepared`` is the backend's prepared plan arrays
    stacked ``(D, ...)`` over data shards.  ``backend`` must be
    ``shardable`` (registry flag).
    """
    _silence_donation_warning()
    D = mesh.shape["data"]
    Mx = mesh.shape["model"]
    win_slot = {w: i for i, w in enumerate(windows)}
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    heads_sharded = Mx > 1
    if heads_sharded and (hq % Mx or hkv % Mx):
        raise ValueError(
            f"model axis {Mx} must divide heads ({hq} q / {hkv} kv)")
    hq_loc = hq // Mx if heads_sharded else hq
    hkv_loc = hkv // Mx if heads_sharded else hkv

    def local_step(params, state: StepState, tokens: jnp.ndarray, key,
                   base: ShardedStepBase, delta, prepared: Tuple[Any, ...]):
        B = tokens.shape[0]
        m_idx = jax.lax.axis_index("model")
        # squeeze this shard's row off the data-stacked fields
        tail_page = base.tail_page[0]
        tail_owner = base.tail_owner[0]
        prepared = jax.tree.map(lambda a: a[0], prepared)

        dlt = jnp.asarray(delta, jnp.int32) * base.row_valid.astype(jnp.int32)
        q_pos = base.q_pos0 + dlt
        tail_off = base.tail_off0 + dlt
        advanced = tuple(backend.advance_fn(p, delta) for p in prepared)
        x = T._embed(params, cfg, tokens[:, None], q_pos[:, None])  # (B,1,d)

        def head_slice(a, blk, axis):
            if not heads_sharded:
                return a
            return jax.lax.dynamic_slice_in_dim(a, m_idx * blk, blk, axis)

        def body(c, kind, p, la, lm):
            x, pool_k, pool_v, conv_all, ssm_all = c
            h = L.apply_norm(p["ln"], x, cfg)
            if kind.mixer in ("attn", "attn_local"):
                w = cfg.sliding_window if kind.mixer == "attn_local" else 0
                q, k_new, v_new = L.attn_project(p["attn"], cfg, h,
                                                 q_pos[:, None])
                # this device's head block of the (replicated) projection
                k_loc = head_slice(k_new[:, 0], hkv_loc, 1)
                v_loc = head_slice(v_new[:, 0], hkv_loc, 1)
                q_loc = head_slice(q[:, 0], hq_loc, 1)     # (B, h_loc, hd)
                # tail write: owners hit the row's tail slot, everyone
                # else this shard's trash page
                pool_k = pool_k.at[la, tail_page, tail_off].set(
                    k_loc.astype(pool_k.dtype))
                pool_v = pool_v.at[la, tail_page, tail_off].set(
                    v_loc.astype(pool_v.dtype))
                k_pool, v_pool = pool_k[la], pool_v[la]
                # frozen-plan partials over this shard's pages + heads
                o_f, m_f, l_f = backend.partials_arrays_fn(
                    q_loc, k_pool, v_pool, advanced[win_slot[w]],
                    num_queries=B, window=w)
                # tail partials; non-owners contribute the POR identity
                kt = k_pool[tail_page]
                vt = v_pool[tail_page]
                o_t, m_t, l_t = ops.single_page_attention(
                    q_loc, kt, vt, base.tail_base, q_pos, window=w)
                own = tail_owner
                m_t = jnp.where(own[:, None], m_t, MASK_VALUE)
                l_t = jnp.where(own[:, None], l_t, 0.0)
                o_t = jnp.where(own[:, None, None], o_t, 0.0)
                o, m, l = ref_mod.por_ref(o_f, m_f, l_f, o_t, m_t, l_t)
                # sparse cross-device sequence merge: only rows whose KV
                # actually spans shards are packed and sent through the
                # subgroup butterfly; fully-replicated / single-shard
                # rows were computed bitwise identically everywhere and
                # skip the wire.  Bm == 0 drops the collective from the
                # program altogether.
                Bm = base.merge_gather.shape[0]
                if D > 1 and Bm > 0:
                    with jax.named_scope("codec.por_merge"):
                        gi = base.merge_gather
                        og, mg, lg = por_mod.por_subgroup_merge(
                            o[gi], m[gi], l[gi], "data", D, base.contrib)
                        si = base.merge_scatter
                        o = o.at[si].set(og, mode="drop")
                        m = m.at[si].set(mg, mode="drop")
                        l = l.at[si].set(lg, mode="drop")
                o_flat = o.astype(q_loc.dtype).reshape(B, 1, hq_loc * hd)
                if heads_sharded:
                    # TP epilogue: partial output projection, psum(model).
                    # Partials are summed in f32 and rounded once, as the
                    # single-device projection rounds its f32 accumulator
                    # once, so token streams match at any mesh shape.
                    w_rows = jax.lax.dynamic_slice_in_dim(
                        p["attn"]["wo"]["w"], m_idx * hq_loc * hd,
                        hq_loc * hd, 0)
                    y = jax.lax.psum(
                        jnp.matmul(o_flat, w_rows,
                                   preferred_element_type=jnp.float32),
                        "model").astype(o_flat.dtype)
                else:
                    y = L.dense(p["attn"]["wo"], o_flat)
                x = x + y
            elif kind.mixer == "mamba":
                y, (conv_n, ssm_n) = M.mamba_decode(
                    p["mamba"], cfg, h, conv_all[lm], ssm_all[lm])
                conv_all = conv_all.at[lm].set(conv_n)
                ssm_all = ssm_all.at[lm].set(ssm_n)
                x = x + y
            x, _ = L.apply_ffn_block(p, cfg, kind.ffn, x)
            return (x, pool_k, pool_v, conv_all, ssm_all)

        x, pool_k, pool_v, conv_all, ssm_all = T.scan_layer_stack(
            cfg, params, body,
            (x, state.pool_k, state.pool_v, state.conv, state.ssm))
        with jax.named_scope("codec.sample"):
            logits = T._unembed(params, cfg, x)[:, 0]       # (B, V)
            key, sk = jax.random.split(key)
            toks = sampler.sample(logits, sk, temperature)
        return toks, key, StepState(pool_k, pool_v, conv_all, ssm_all)

    pool_spec = paged_pool_spec(mesh, hkv)
    state_spec = StepState(pool_spec, pool_spec, P(), P())
    base_spec = ShardedStepBase(P(), P(), P("data"), P(), P(), P("data"),
                                P(), P(), P())
    fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), state_spec, P(), P(), base_spec, P(), P("data")),
        out_specs=(P(), P(), state_spec),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(1,))
