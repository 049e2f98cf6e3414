"""Pure-jnp oracles for the CoDec kernels.

Everything here is deliberately simple and materialises full score
matrices; used only as the ground truth for kernel tests and the `ref`
attention impl.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MASK_VALUE = -1e30
# Attention math is float32 and every implementation keeps it so: on a
# TPU, XLA's default precision would round f32 matmul operands to bf16,
# and then backends that agree on the CPU would disagree on the chip.
PRECISION = jax.lax.Precision.HIGHEST


def _fold_gqa(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(nq, h_q, d) -> (n_kv, nq*group, d); head h belongs to kv h//group."""
    nq, h_q, d = q.shape
    group = h_q // n_kv
    return (q.reshape(nq, n_kv, group, d)
             .transpose(1, 0, 2, 3)
             .reshape(n_kv, nq * group, d))


def _unfold_gqa(x: jnp.ndarray, nq: int) -> jnp.ndarray:
    """(n_kv, nq*group, ...) -> (nq, h_q, ...)."""
    n_kv, rows = x.shape[:2]
    group = rows // nq
    tail = x.shape[2:]
    return (x.reshape(n_kv, nq, group, *tail)
             .transpose(1, 0, 2, *(3 + i for i in range(len(tail))))
             .reshape(nq, n_kv * group, *tail))


def pac_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
            kv_len: Optional[int] = None,
            pos_base: int = 0,
            q_pos: Optional[jnp.ndarray] = None,
            window: int = 0,
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Partial attention computation (paper Alg. 2) + flash statistics.

    q: (nq, h_q, d); k, v: (n, n_kv, d).  Returns (o, m, l) with
    o: (nq, h_q, d) normalised *within this node*, m: (nq, h_q) running
    max (log-space frame), l: (nq, h_q) softmax denominator at frame m.
    ``kv_len`` masks padding rows of k/v; ``pos_base``/``q_pos``/``window``
    implement the visibility mask of §4.1.
    """
    nq, h_q, d = q.shape
    n, n_kv, _ = k.shape
    scale = 1.0 / np.sqrt(d)
    qf = _fold_gqa(q.astype(jnp.float32), n_kv)              # (n_kv, R, d)
    kf = k.astype(jnp.float32).transpose(1, 0, 2)            # (n_kv, n, d)
    vf = v.astype(jnp.float32).transpose(1, 0, 2)
    s = jnp.einsum("hrd,hnd->hrn", qf, kf,
                   precision=PRECISION) * scale              # (n_kv, R, n)

    pos = pos_base + jnp.arange(n)
    valid = jnp.ones(n, bool) if kv_len is None else pos < pos_base + kv_len
    mask = jnp.broadcast_to(valid[None, :], (nq, n))
    if q_pos is not None:
        qp = q_pos.astype(jnp.int32)[:, None]
        mask = mask & (pos[None, :] <= qp)                   # causality
        if window and window > 0:
            mask = mask & (pos[None, :] > qp - window)
    group = h_q // n_kv
    mask_r = jnp.repeat(mask, group, axis=0).reshape(nq, group, n)
    mask_r = jnp.broadcast_to(mask_r[None], (n_kv, nq, group, n))
    mask_r = mask_r.reshape(n_kv, nq * group, n)

    s = jnp.where(mask_r, s, MASK_VALUE)
    m = jnp.max(s, axis=-1)                                  # (n_kv, R)
    p = jnp.exp(s - m[..., None]) * mask_r
    l = jnp.sum(p, axis=-1)
    u = jnp.einsum("hrn,hnd->hrd", p, vf, precision=PRECISION)
    o = u / jnp.maximum(l, 1e-30)[..., None]
    return (_unfold_gqa(o, nq), _unfold_gqa(m, nq), _unfold_gqa(l, nq))


def por_ref(o1, m1, l1, o2, m2, l2):
    """Partial output reduction (paper Alg. 3): LSE merge of two partials."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m) * l1
    a2 = jnp.exp(m2 - m) * l2
    l = a1 + a2
    o = (o1 * a1[..., None] + o2 * a2[..., None]) / jnp.maximum(l, 1e-30)[..., None]
    return o, m, l


def combine_partials_stats_ref(o_parts, m_parts, l_parts, seg_ids,
                               num_queries):
    """Segment-LSE reduction returning per-query (o, m, l) partials.

    o_parts: (P, h, d); m/l: (P, h); seg_ids: (P,) in [0, num_queries]
    (num_queries = trash).  Returns ((B,h,d), (B,h), (B,h)) — itself a
    valid partial, so the result can be POR-merged with further partials
    (e.g. the engine's per-step tail page, or a cross-device shard).
    """
    num_seg = num_queries + 1
    m_max = jax.ops.segment_max(m_parts, seg_ids, num_segments=num_seg)
    m_max = jnp.maximum(m_max, MASK_VALUE)  # empty segments -> -inf guard
    alpha = jnp.exp(m_parts - m_max[seg_ids]) * l_parts
    denom = jax.ops.segment_sum(alpha, seg_ids, num_segments=num_seg)
    numer = jax.ops.segment_sum(o_parts * alpha[..., None], seg_ids,
                                num_segments=num_seg)
    out = numer / jnp.maximum(denom, 1e-30)[..., None]
    return (out[:num_queries], m_max[:num_queries], denom[:num_queries])


def combine_partials_ref(o_parts, m_parts, l_parts, seg_ids, num_queries):
    """Flattened segment-LSE reduction (our TPU-native tree reduction)."""
    o, _, _ = combine_partials_stats_ref(o_parts, m_parts, l_parts, seg_ids,
                                         num_queries)
    return o


def decode_attention_ref(q, k, v, kv_lens, window: int = 0):
    """Dense-batch decode attention oracle (the FlashDecoding semantics).

    q: (B, h_q, d); k, v: (B, L, n_kv, d); kv_lens: (B,).
    Query position of request b is kv_lens[b] - 1... the query attends to
    all cached positions [0, kv_lens[b]) (its own KV is already appended).
    """
    B, h_q, d = q.shape

    def one(qb, kb, vb, ln):
        o, _, _ = pac_ref(qb[None].reshape(1, h_q, d) if qb.ndim == 2 else qb,
                          kb, vb, kv_len=ln,
                          q_pos=jnp.full((1,), ln - 1, jnp.int32),
                          window=window)
        return o[0]

    return jax.vmap(lambda qb, kb, vb, ln: one(qb[None], kb, vb, ln))(
        q, k, v, kv_lens.astype(jnp.int32))


def codec_ref_stats(q, k_pool, v_pool, plan, window: int = 0):
    """Shared-prefix decode attention oracle driven by a DecodePlan.

    q: (B, h_q, d); pools: (P, page, n_kv, d).  Loops tasks in Python —
    slow, exact.  Returns per-query mergeable (o, m, l).
    """
    ps = plan.page_size
    parts_o, parts_m, parts_l, segs = [], [], [], []
    for t in range(plan.num_tasks):
        npages = int(plan.task_npages[t])
        kvlen = int(plan.task_kvlen[t])
        nq = int(plan.task_qnum[t])
        if nq == 0 or kvlen == 0:
            continue
        pages = np.asarray(plan.task_pages[t, :npages])
        k = k_pool[pages].reshape(npages * ps, *k_pool.shape[2:])
        v = v_pool[pages].reshape(npages * ps, *v_pool.shape[2:])
        rows = np.asarray(plan.q_gather[t, :nq])
        qt = q[rows]
        qp = jnp.asarray(plan.q_pos[t, :nq])
        o, m, l = pac_ref(qt, k, v, kv_len=kvlen,
                          pos_base=int(plan.task_pos[t]), q_pos=qp,
                          window=window)
        parts_o.append(o); parts_m.append(m); parts_l.append(l)
        segs.append(rows)
    o_parts = jnp.concatenate(parts_o, 0)
    m_parts = jnp.concatenate(parts_m, 0)
    l_parts = jnp.concatenate(parts_l, 0)
    seg_ids = jnp.concatenate([jnp.asarray(s) for s in segs], 0)
    return combine_partials_stats_ref(o_parts, m_parts, l_parts, seg_ids,
                                      plan.num_queries)


def codec_ref(q, k_pool, v_pool, plan) -> jnp.ndarray:
    """Full-output convenience wrapper around ``codec_ref_stats``."""
    o, _, _ = codec_ref_stats(q, k_pool, v_pool, plan)
    return o
