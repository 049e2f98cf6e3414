"""Jit'd wrappers around the CoDec kernels + the XLA fallback impl.

``codec_attention`` is the public op: stacked decode queries + paged KV
pool + a compiled ``DecodePlan`` -> attention outputs, with three
interchangeable implementations:

* ``pallas``  — the PAC kernel (interpreted on the CPU backend, compiled
                on a TPU: ``kernels.pallas_interpret`` decides);
* ``xla``     — the same task/plan semantics expressed as dense jnp ops
                (vectorised over tasks); this is what the distributed
                serve_step lowers, so the multi-pod dry-run exercises the
                paper's plan structure without Pallas;
* ``ref``     — the python-loop oracle from ``ref.py``.

All implementations share the flattened segment-LSE reduction
(``combine_partials``) — the TPU-native tree reduction.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import pac as pac_mod
from . import pallas_interpret
from . import ref as ref_mod

MASK_VALUE = ref_mod.MASK_VALUE


class PlanArrays(NamedTuple):
    """Device-ready DecodePlan arrays (all jnp, static shapes)."""
    step_task: jnp.ndarray
    step_page: jnp.ndarray
    step_valid: jnp.ndarray
    step_first: jnp.ndarray
    step_last: jnp.ndarray
    step_pos: jnp.ndarray
    step_kvlen: jnp.ndarray
    task_qnum: jnp.ndarray
    task_npages: jnp.ndarray
    task_kvlen: jnp.ndarray
    task_pos: jnp.ndarray
    task_pages: jnp.ndarray
    q_gather: jnp.ndarray
    q_pos: jnp.ndarray
    seg_ids: jnp.ndarray


def plan_arrays(plan) -> PlanArrays:
    return PlanArrays(*(jnp.asarray(getattr(plan, f)) for f in PlanArrays._fields))


def advance_plan_arrays(pa: PlanArrays, delta) -> PlanArrays:
    """Advance all query positions by ``delta`` steps, device-side.

    Between plan rebuilds every live query moves one position per decode
    step; the fused step passes the epoch-relative step counter instead
    of re-uploading plan arrays.  Dead q-slots advance too — harmless,
    they are masked out by ``task_qnum`` in every implementation.
    """
    return pa._replace(q_pos=pa.q_pos + jnp.asarray(delta, jnp.int32))


@functools.partial(jax.jit, static_argnames=("num_queries",))
def combine_partials(o_parts: jnp.ndarray, m_parts: jnp.ndarray,
                     l_parts: jnp.ndarray, seg_ids: jnp.ndarray,
                     num_queries: int) -> jnp.ndarray:
    """Flattened parallel tree reduction (POR collapsed to segment LSE)."""
    P = o_parts.shape[0] * o_parts.shape[1]
    h, d = o_parts.shape[2], o_parts.shape[3]
    return ref_mod.combine_partials_ref(
        o_parts.reshape(P, h, d), m_parts.reshape(P, h),
        l_parts.reshape(P, h), seg_ids, num_queries)


@functools.partial(jax.jit, static_argnames=("num_queries",))
def combine_partials_stats(o_parts, m_parts, l_parts, seg_ids,
                           num_queries: int):
    """Like combine_partials but returns mergeable per-query (o, m, l)."""
    P = o_parts.shape[0] * o_parts.shape[1]
    h, d = o_parts.shape[2], o_parts.shape[3]
    return ref_mod.combine_partials_stats_ref(
        o_parts.reshape(P, h, d), m_parts.reshape(P, h),
        l_parts.reshape(P, h), seg_ids, num_queries)


@functools.partial(jax.jit, static_argnames=("window",))
def single_page_attention(q: jnp.ndarray,        # (B, h_q, d)
                          k_pages: jnp.ndarray,  # (B, page, n_kv, d)
                          v_pages: jnp.ndarray,
                          pos_base: jnp.ndarray,  # (B,) abs pos of page[0]
                          q_pos: jnp.ndarray,     # (B,)
                          window: int = 0):
    """Per-request attention over one (tail) page -> partial (o, m, l).

    The engine's growing-tail fast path: the frozen CoDec plan covers all
    full pages; this covers each request's last partial page and the
    result is POR-merged with the frozen partials.
    """
    def one(qb, kb, vb, pb, qp):
        return ref_mod.pac_ref(qb[None], kb, vb,
                               kv_len=None, pos_base=pb,
                               q_pos=qp[None], window=window)

    o, m, l = jax.vmap(one)(q, k_pages, v_pages,
                            pos_base.astype(jnp.int32),
                            q_pos.astype(jnp.int32))
    return o[:, 0], m[:, 0], l[:, 0]


def gather_queries(q: jnp.ndarray, q_gather: jnp.ndarray) -> jnp.ndarray:
    """(B, h, d) -> task-major (T+1, max_q, h, d)."""
    return q[q_gather]


# --------------------------------------------------------------------- #
# GQA fold: (T+1, max_q, h_q, ...) <-> (T+1, n_kv * max_q * group, ...)
# --------------------------------------------------------------------- #
def fold_gqa(x: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """Task-major ``(T+1, max_q, h_q, *tail)`` -> ``(T+1, rows, *tail)``.

    Head ``h = kv * group + g`` of query slot ``qi`` lands on row
    ``(kv * max_q + qi) * group + g``: each KV head's query rows are
    contiguous, so one load of its K/V serves all of them.
    """
    Tp1, max_q, h_q = x.shape[:3]
    tail = x.shape[3:]
    group = h_q // n_kv
    perm = (0, 2, 1, 3) + tuple(4 + i for i in range(len(tail)))
    return (x.reshape(Tp1, max_q, n_kv, group, *tail).transpose(perm)
             .reshape(Tp1, n_kv * max_q * group, *tail))


def unfold_gqa(x: jnp.ndarray, max_q: int, n_kv: int) -> jnp.ndarray:
    """Inverse of :func:`fold_gqa`."""
    Tp1, rows = x.shape[:2]
    tail = x.shape[2:]
    group = rows // (n_kv * max_q)
    perm = (0, 2, 1, 3) + tuple(4 + i for i in range(len(tail)))
    return (x.reshape(Tp1, n_kv, max_q, group, *tail).transpose(perm)
             .reshape(Tp1, max_q, n_kv * group, *tail))


# --------------------------------------------------------------------- #
# XLA implementation of PAC over the task-major plan arrays
# --------------------------------------------------------------------- #
def pac_xla(q_tasks: jnp.ndarray,     # (T+1, max_q, h_q, d)
            qpos_tasks: jnp.ndarray,  # (T+1, max_q)
            k_pool: jnp.ndarray,      # (P, page, n_kv, d)
            v_pool: jnp.ndarray,
            task_pages: jnp.ndarray,  # (T+1, max_pages)
            task_kvlen: jnp.ndarray,  # (T+1,)
            task_pos: jnp.ndarray,    # (T+1,)
            window: int = 0,
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    Tp1, max_q, h_q, d = q_tasks.shape
    _, page, n_kv, _ = k_pool.shape
    max_pages = task_pages.shape[1]
    n = max_pages * page
    group = h_q // n_kv
    scale = 1.0 / np.sqrt(d)

    k_t = k_pool[task_pages].reshape(Tp1, n, n_kv, d)
    v_t = v_pool[task_pages].reshape(Tp1, n, n_kv, d)

    qf = fold_gqa(q_tasks.astype(jnp.float32), n_kv).reshape(
        Tp1, n_kv, max_q * group, d)
    kf = k_t.astype(jnp.float32).transpose(0, 2, 1, 3)   # (T, n_kv, n, d)
    vf = v_t.astype(jnp.float32).transpose(0, 2, 1, 3)

    s = jnp.einsum("thrd,thnd->thrn", qf, kf,
                   precision=ref_mod.PRECISION) * scale

    off = jnp.arange(n, dtype=jnp.int32)
    pos = task_pos[:, None].astype(jnp.int32) + off[None, :]   # (T, n)
    valid = off[None, :] < task_kvlen[:, None]
    qp = qpos_tasks.astype(jnp.int32)                          # (T, max_q)
    mask = valid[:, None, :] & (pos[:, None, :] <= qp[:, :, None])
    if window > 0:
        mask = mask & (pos[:, None, :] > qp[:, :, None] - window)
    # (T, max_q, n) -> (T, n_kv, max_q*group, n)
    mask_r = jnp.broadcast_to(mask[:, :, None, :], (Tp1, max_q, group, n))
    mask_r = mask_r.reshape(Tp1, 1, max_q * group, n)
    mask_r = jnp.broadcast_to(mask_r, s.shape)

    s = jnp.where(mask_r, s, MASK_VALUE)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None]) * mask_r
    l = jnp.sum(p, axis=-1)
    u = jnp.einsum("thrn,thnd->thrd", p, vf,
                   precision=ref_mod.PRECISION)
    o = u / jnp.maximum(l, 1e-30)[..., None]

    def unfold(x):
        return unfold_gqa(x.reshape(Tp1, n_kv * max_q * group, *x.shape[3:]),
                          max_q, n_kv)

    return unfold(o), unfold(m), unfold(l)


# --------------------------------------------------------------------- #
# public op
# --------------------------------------------------------------------- #
@functools.partial(
    jax.jit,
    static_argnames=("num_queries", "window", "impl", "interpret"))
def codec_partials_arrays(q: jnp.ndarray, k_pool: jnp.ndarray,
                          v_pool: jnp.ndarray, pa: PlanArrays,
                          num_queries: int, *, window: int = 0,
                          impl: str = "pallas",
                          interpret: Optional[bool] = None):
    """Plan-covered attention -> per-query mergeable (o, m, l) stats.

    ``interpret=None`` lets :func:`kernels.pallas_interpret` decide from
    the backend; a compile for a described TPU passes ``False``.
    """
    q_tasks = gather_queries(q, pa.q_gather)
    if impl == "pallas":
        if interpret is None:
            interpret = pallas_interpret()
        max_q = q_tasks.shape[1]
        n_kv = k_pool.shape[2]
        qpos = jnp.broadcast_to(pa.q_pos[:, :, None, None],
                                pa.q_pos.shape + (q.shape[1], 1))
        o, m, l = pac_mod.pac(
            fold_gqa(q_tasks, n_kv), fold_gqa(qpos, n_kv), k_pool, v_pool,
            pa.step_task, pa.step_page, pa.step_valid, pa.step_first,
            pa.step_last, pa.step_pos, pa.step_kvlen,
            window=window, interpret=interpret,
            num_lanes=pa.step_task.shape[0],
            max_steps=pa.step_task.shape[1])
        o, m, l = (unfold_gqa(x, max_q, n_kv) for x in (o, m, l))
    elif impl == "xla":
        o, m, l = pac_xla(q_tasks, pa.q_pos, k_pool, v_pool,
                          pa.task_pages, pa.task_kvlen, pa.task_pos,
                          window=window)
    else:
        raise ValueError(impl)
    # zero-out padding slots so stale/trash flushes can't reach a segment
    slot = jnp.arange(pa.q_gather.shape[1], dtype=jnp.int32)
    live = slot[None, :] < pa.task_qnum[:, None]              # (T+1, max_q)
    m = jnp.where(live[..., None], m, MASK_VALUE)
    l = jnp.where(live[..., None], l, 0.0)
    o = jnp.where(live[..., None, None], o, 0.0)  # trash may hold NaNs
    return combine_partials_stats(o, m, l, pa.seg_ids, num_queries)


def codec_attention_arrays(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, pa: PlanArrays,
                           num_queries: int, *, window: int = 0,
                           impl: str = "pallas",
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    out, _, _ = codec_partials_arrays(q, k_pool, v_pool, pa, num_queries,
                                      window=window, impl=impl,
                                      interpret=interpret)
    return out.astype(q.dtype)


def codec_attention(q, k_pool, v_pool, plan, *, impl: str = "pallas",
                    window: int = 0,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Convenience entry taking a host DecodePlan object."""
    if impl == "ref":
        return ref_mod.codec_ref(q, k_pool, v_pool, plan).astype(q.dtype)
    return codec_attention_arrays(q, k_pool, v_pool, plan_arrays(plan),
                                  plan.num_queries, window=window,
                                  impl=impl, interpret=interpret)
