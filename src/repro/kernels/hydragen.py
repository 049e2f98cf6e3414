"""Hydragen-style batched shared-prefix decode attention.

A distinct point in the shared-prefix design space (Juravsky et al.,
"Hydragen"; Ye et al., "ChunkAttention"): instead of CoDec's page-level
task scheduling, decompose decode attention into

1. **prefix phase** — for every *shared* forest node, attention of all
   sharing queries against the node's KV as ONE batched dense matmul.
   Because every prefix token precedes every live query position, no
   causal comparison is needed inside the matmul (only page-remainder
   validity, plus the sliding-window bound when ``window > 0``) — the
   score computation is a pure GEMM, which is the source of Hydragen's
   throughput on matmul-heavy accelerators.
2. **suffix phase** — per-request attention over each request's private
   (single-query) KV slices, batched across requests.
3. **merge** — both phases emit flash partials ``(o, m, l)`` that the
   standard segment log-sum-exp reduction (``ref.combine_partials``)
   folds into exact full-softmax outputs.

No new planner is needed: ``prepare`` consumes the existing
``DecodePlan`` task-major arrays (``q_gather`` / ``task_pages`` /
``q_pos``) and splits tasks by sharing degree on the host — shared
tasks (``task_qnum > 1``) form the prefix batch, single-query tasks the
suffix batch.  Window pruning done by the planner therefore carries
over unchanged.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as ref_mod

MASK_VALUE = ref_mod.MASK_VALUE


class HydragenArrays(NamedTuple):
    """Device arrays for the two phases (static shapes per plan)."""

    # shared-prefix groups: (S, ...) — tasks with > 1 sharing query
    px_pages: jnp.ndarray    # (S, max_pages) global page ids
    px_kvlen: jnp.ndarray    # (S,) valid tokens in the slice
    px_pos: jnp.ndarray      # (S,) absolute position of first token
    px_qnum: jnp.ndarray     # (S,) live queries of the group
    px_gather: jnp.ndarray   # (S, max_q) query rows (pad 0)
    px_qpos: jnp.ndarray     # (S, max_q) absolute query positions
    px_seg: jnp.ndarray      # (S * max_q,) segment ids (trash = B)

    # per-request suffixes: (U, ...) — single-query tasks
    sf_pages: jnp.ndarray    # (U, max_pages)
    sf_kvlen: jnp.ndarray    # (U,)
    sf_pos: jnp.ndarray      # (U,)
    sf_gather: jnp.ndarray   # (U,) the one query row
    sf_qpos: jnp.ndarray     # (U,)
    sf_seg: jnp.ndarray      # (U,)


def _bucket_rows(n: int) -> int:
    """Bucketed group count: smallest power of two >= n (0 stays 0).

    Both phase batches are padded to bucketed row counts so the jitted
    phases (and the fused decode step wrapping them) keep stable shapes
    across plan rebuilds; padded rows are dead (``qnum 0`` / ``kvlen 0``,
    segment = trash) and fully masked.  An empty batch stays empty —
    ``hydragen_partials_arrays`` skips the phase at trace time.
    """
    return 0 if n <= 0 else 1 << (n - 1).bit_length()


def prepare(plan) -> HydragenArrays:
    """Split a DecodePlan's tasks into prefix/suffix batches (host side)."""
    T = plan.num_tasks
    max_q = plan.max_q
    trash = plan.num_queries
    qnum = np.asarray(plan.task_qnum[:T])
    seg = np.asarray(plan.seg_ids[:(T + 1) * max_q]).reshape(-1, max_q)[:T]
    shared = np.nonzero(qnum > 1)[0]
    single = np.nonzero(qnum == 1)[0]
    S, U = _bucket_rows(len(shared)), _bucket_rows(len(single))

    def dev(a, rows, fill=0):
        a = np.ascontiguousarray(a)
        if a.shape[0] < rows:
            pad = np.full((rows - a.shape[0],) + a.shape[1:], fill, a.dtype)
            a = np.concatenate([a, pad], 0)
        return jnp.asarray(a)

    return HydragenArrays(
        px_pages=dev(plan.task_pages[shared], S),
        px_kvlen=dev(plan.task_kvlen[shared], S),
        px_pos=dev(plan.task_pos[shared], S),
        px_qnum=dev(qnum[shared], S),
        px_gather=dev(plan.q_gather[shared], S),
        px_qpos=dev(plan.q_pos[shared], S),
        px_seg=dev(seg[shared].reshape(-1), S * max_q, fill=trash),
        sf_pages=dev(plan.task_pages[single], U),
        sf_kvlen=dev(plan.task_kvlen[single], U),
        sf_pos=dev(plan.task_pos[single], U),
        sf_gather=dev(plan.q_gather[single, 0], U),
        sf_qpos=dev(plan.q_pos[single, 0], U),
        sf_seg=dev(seg[single, 0], U, fill=trash),
    )


def advance(ha: HydragenArrays, delta) -> HydragenArrays:
    """Advance all query positions by ``delta`` decode steps, device-side
    (dead slots advance too — they are masked by ``px_qnum`` / ``kvlen``)."""
    d = jnp.asarray(delta, jnp.int32)
    return ha._replace(px_qpos=ha.px_qpos + d, sf_qpos=ha.sf_qpos + d)


def _gather_kv(pool: jnp.ndarray, pages: jnp.ndarray) -> jnp.ndarray:
    """(P, page, n_kv, d)[(G, max_pages)] -> (G, n, n_kv, d)."""
    G, max_pages = pages.shape
    page = pool.shape[1]
    return pool[pages].reshape(G, max_pages * page, *pool.shape[2:])


def _prefix_phase(q, k_pool, v_pool, ha: HydragenArrays, window: int):
    """Batched dense matmul per shared node — no causal comparison.

    Returns flattened partials: o (S*max_q, h, d), m/l (S*max_q, h).
    """
    S, max_q = ha.px_gather.shape
    _, _, n_kv, d = k_pool.shape
    h_q = q.shape[1]
    group = h_q // n_kv
    scale = 1.0 / np.sqrt(d)

    k_t = _gather_kv(k_pool, ha.px_pages)                 # (S, n, kv, d)
    v_t = _gather_kv(v_pool, ha.px_pages)
    n = k_t.shape[1]
    qg = q[ha.px_gather].astype(jnp.float32)              # (S, max_q, h, d)
    qf = (qg.reshape(S, max_q, n_kv, group, d)
          .transpose(0, 2, 1, 3, 4)
          .reshape(S, n_kv, max_q * group, d))
    kf = k_t.astype(jnp.float32).transpose(0, 2, 1, 3)    # (S, kv, n, d)
    vf = v_t.astype(jnp.float32).transpose(0, 2, 1, 3)

    # the Hydragen GEMM: every sharing query vs the whole node KV
    s = jnp.einsum("shrd,shnd->shrn", qf, kf,
                   precision=ref_mod.PRECISION) * scale

    off = jnp.arange(n, dtype=jnp.int32)
    valid = off[None, :] < ha.px_kvlen[:, None]           # (S, n) padding
    mask = jnp.broadcast_to(valid[:, None, :], (S, max_q, n))
    if window > 0:
        pos = ha.px_pos[:, None].astype(jnp.int32) + off[None, :]
        qp = ha.px_qpos.astype(jnp.int32)                 # (S, max_q)
        mask = mask & (pos[:, None, :] > qp[:, :, None] - window)
    mask_r = (jnp.broadcast_to(mask[:, :, None, :], (S, max_q, group, n))
              .reshape(S, 1, max_q * group, n))
    mask_r = jnp.broadcast_to(mask_r, s.shape)

    s = jnp.where(mask_r, s, MASK_VALUE)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None]) * mask_r
    l = jnp.sum(p, axis=-1)
    u = jnp.einsum("shrn,shnd->shrd", p, vf,
                   precision=ref_mod.PRECISION)
    o = u / jnp.maximum(l, 1e-30)[..., None]

    def unfold(x):
        tail = x.shape[3:]
        return (x.reshape(S, n_kv, max_q, group, *tail)
                .transpose(0, 2, 1, 3, *(4 + i for i in range(len(tail))))
                .reshape(S * max_q, h_q, *tail))

    o, m, l = unfold(o), unfold(m), unfold(l)
    # dead query slots (slot >= qnum) must not pollute their gather row
    slot = jnp.arange(max_q, dtype=jnp.int32)
    live = (slot[None, :] < ha.px_qnum[:, None]).reshape(S * max_q)
    m = jnp.where(live[:, None], m, MASK_VALUE)
    l = jnp.where(live[:, None], l, 0.0)
    o = jnp.where(live[:, None, None], o, 0.0)
    return o, m, l


def _suffix_phase(q, k_pool, v_pool, ha: HydragenArrays, window: int):
    """Per-request attention over private KV slices, batched over tasks.

    Returns o (U, h, d), m/l (U, h).  The causal bound IS applied here:
    a suffix slice may contain the query's own newest token.
    """
    U = ha.sf_gather.shape[0]
    _, _, n_kv, d = k_pool.shape
    h_q = q.shape[1]
    group = h_q // n_kv
    scale = 1.0 / np.sqrt(d)

    k_t = _gather_kv(k_pool, ha.sf_pages)                 # (U, n, kv, d)
    v_t = _gather_kv(v_pool, ha.sf_pages)
    n = k_t.shape[1]
    qg = q[ha.sf_gather].astype(jnp.float32)              # (U, h, d)
    qf = qg.reshape(U, n_kv, group, d)    # head h = kv*group + g
    kf = k_t.astype(jnp.float32).transpose(0, 2, 1, 3)    # (U, kv, n, d)
    vf = v_t.astype(jnp.float32).transpose(0, 2, 1, 3)

    s = jnp.einsum("shgd,shnd->shgn", qf, kf,
                   precision=ref_mod.PRECISION) * scale     # (U, kv, g, n)

    off = jnp.arange(n, dtype=jnp.int32)
    pos = ha.sf_pos[:, None].astype(jnp.int32) + off[None, :]   # (U, n)
    qp = ha.sf_qpos.astype(jnp.int32)[:, None]
    mask = (off[None, :] < ha.sf_kvlen[:, None]) & (pos <= qp)
    if window > 0:
        mask = mask & (pos > qp - window)
    mask_r = jnp.broadcast_to(mask[:, None, None, :], s.shape)

    s = jnp.where(mask_r, s, MASK_VALUE)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None]) * mask_r
    l = jnp.sum(p, axis=-1)
    u = jnp.einsum("shgn,shnd->shgd", p, vf,
                   precision=ref_mod.PRECISION)
    o = u / jnp.maximum(l, 1e-30)[..., None]

    def unfold(x):
        tail = x.shape[3:]
        return x.reshape(U, n_kv * group, *tail)

    return unfold(o), unfold(m), unfold(l)


@functools.partial(jax.jit, static_argnames=("num_queries", "window"))
def hydragen_partials_arrays(q: jnp.ndarray, k_pool: jnp.ndarray,
                             v_pool: jnp.ndarray, ha: HydragenArrays,
                             num_queries: int, *, window: int = 0
                             ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                        jnp.ndarray]:
    """Both phases + segment-LSE merge -> per-query (o, m, l)."""
    parts_o, parts_m, parts_l, segs = [], [], [], []
    if ha.px_pages.shape[0] > 0:               # static shape: trace-time
        o, m, l = _prefix_phase(q, k_pool, v_pool, ha, window)
        parts_o.append(o); parts_m.append(m); parts_l.append(l)
        segs.append(ha.px_seg)
    if ha.sf_pages.shape[0] > 0:
        o, m, l = _suffix_phase(q, k_pool, v_pool, ha, window)
        parts_o.append(o); parts_m.append(m); parts_l.append(l)
        segs.append(ha.sf_seg)
    if not parts_o:                        # zero-task plan: all-trash
        h_q, d = q.shape[1], q.shape[2]
        parts_o = [jnp.zeros((1, h_q, d), jnp.float32)]
        parts_m = [jnp.full((1, h_q), MASK_VALUE, jnp.float32)]
        parts_l = [jnp.zeros((1, h_q), jnp.float32)]
        segs = [jnp.full((1,), num_queries, jnp.int32)]
    o_parts = jnp.concatenate(parts_o, 0)
    m_parts = jnp.concatenate(parts_m, 0)
    l_parts = jnp.concatenate(parts_l, 0)
    seg_ids = jnp.concatenate(segs, 0)
    return ref_mod.combine_partials_stats_ref(o_parts, m_parts, l_parts,
                                              seg_ids, num_queries)


def hydragen_partials(q, k_pool, v_pool, plan, prepared=None,
                      window: int = 0):
    """Registry entry point (plan + optional cached ``prepare`` output)."""
    if prepared is None:
        prepared = prepare(plan)
    return hydragen_partials_arrays(q, k_pool, v_pool, prepared,
                                    plan.num_queries, window=window)


def hydragen_attention(q, k_pool, v_pool, plan, *, window: int = 0,
                       prepared=None) -> jnp.ndarray:
    """Full decode attention through the Hydragen decomposition."""
    o, _, _ = hydragen_partials(q, k_pool, v_pool, plan, prepared, window)
    return o.astype(q.dtype)
