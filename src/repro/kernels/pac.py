"""CoDec PAC (partial attention computation) Pallas TPU kernel.

One ``pallas_call`` executes the *whole* inter-block schedule (paper §4.3):
the grid is ``(num_lanes, max_steps)`` where a step processes one KV page
of one subtask.  Lanes are the TPU's parallel slots (megacore halves /
sharded cores) — ``dimension_semantics=("parallel", "arbitrary")`` — and
the LPT scheduler balanced work across them; the step dimension executes
sequentially so flash accumulators persist in VMEM scratch across a
subtask's pages.

Memory hierarchy mapping (GPU shared memory -> TPU VMEM):

* K/V pages stream HBM->VMEM through BlockSpec index maps driven by a
  scalar-prefetched page table — the Pallas pipeline double-buffers them;
  *shared-prefix pages are fetched once per subtask regardless of how many
  queries share them* (the paper's central IO saving).
* The per-task query tile (pre-gathered, task-major) is fetched once per
  subtask: consecutive steps with an unchanged block index skip the DMA.
* GQA: Q arrives folded to ``(n_kv * n_q * group, d)`` rows (the fold is
  done in XLA by ``ops.fold_gqa``), and a page's K/V is viewed as
  ``(page * n_kv, d)``, so one MXU pass scores every head's rows against
  the page; a block-diagonal mask keeps each row to its own KV head.
  Each KV page is loaded once for all of its query groups — the paper's
  GQA-aware load combining — and the kernel needs no in-register
  transpose, which Mosaic does not lower for these shapes.

Outputs are *partial* results ``(o, m, l)`` per (task, query-slot); the
tree reduction (ops.combine_partials) merges them per query.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as ref_mod

MASK_VALUE = ref_mod.MASK_VALUE
# m/l statistics are kept replicated across one vreg's lanes: a (rows, 1)
# column is not a layout Mosaic stores efficiently (the idiom of the
# Pallas TPU flash-attention kernel)
STAT_LANES = 128


def _pac_kernel(
    # scalar-prefetch refs (num_lanes, max_steps)
    step_task, step_page, step_valid, step_first, step_last,
    step_pos, step_kvlen,
    # operand refs
    q_ref,      # (1, rows, d)            rows = n_kv * max_q * group
    qpos_ref,   # (1, rows, 1) int32      query position of each row
    k_ref,      # (1, page, n_kv, d)
    v_ref,      # (1, page, n_kv, d)
    # output refs
    o_ref,      # (1, rows, d) f32
    m_ref,      # (1, rows, STAT_LANES) f32
    l_ref,      # (1, rows, STAT_LANES) f32
    # scratch
    acc,        # (rows, d) f32
    m_s,        # (rows, STAT_LANES) f32
    l_s,        # (rows, STAT_LANES) f32
    *,
    n_kv: int,
    window: int,
):
    lane = pl.program_id(0)
    step = pl.program_id(1)
    valid = step_valid[lane, step] == 1
    first = (step_first[lane, step] == 1) & valid
    last = (step_last[lane, step] == 1) & valid

    @pl.when(first)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, MASK_VALUE)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(valid)
    def _step():
        rows, d = q_ref.shape[1], q_ref.shape[2]
        page = k_ref.shape[1]
        rows_per_head = rows // n_kv
        scale = 1.0 / np.sqrt(d)

        q = q_ref[0].astype(jnp.float32)                     # (rows, d)
        # the page's heads side by side: column c = slot * n_kv + head
        k = k_ref[0].astype(jnp.float32).reshape(page * n_kv, d)
        v = v_ref[0].astype(jnp.float32).reshape(page * n_kv, d)

        # one MXU pass for every head; a row only keeps the columns of
        # its own KV head (block-diagonal mask below)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=ref_mod.PRECISION,
            preferred_element_type=jnp.float32) * scale      # (rows, C)

        # visibility mask (§4.1): own head + page padding + causality +
        # sliding window
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        slot = col // n_kv
        pos = step_pos[lane, step] + slot
        qp = qpos_ref[0]                                     # (rows, 1)
        mask = ((row // rows_per_head == col % n_kv)
                & (slot < step_kvlen[lane, step]) & (pos <= qp))
        if window > 0:
            mask = mask & (pos > qp - window)

        s = jnp.where(mask, s, MASK_VALUE)
        m_prev = m_s[:, :1]                                  # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new) * mask                        # masked -> 0
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_s[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=ref_mod.PRECISION,
            preferred_element_type=jnp.float32)              # (rows, d)
        acc[...] = acc[...] * alpha + pv
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(last)
    def _finalize():
        o_ref[0] = acc[...] / jnp.maximum(l_s[:, :1], 1e-30)
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]


@functools.partial(
    jax.jit,
    static_argnames=("window", "interpret", "num_lanes", "max_steps"))
def pac(q_rows: jnp.ndarray,       # (T+1, rows, d)
        qpos_rows: jnp.ndarray,    # (T+1, rows, 1) int32
        k_pool: jnp.ndarray,       # (P, page, n_kv, d)
        v_pool: jnp.ndarray,
        step_task: jnp.ndarray,    # (num_lanes, max_steps) int32
        step_page: jnp.ndarray,
        step_valid: jnp.ndarray,
        step_first: jnp.ndarray,
        step_last: jnp.ndarray,
        step_pos: jnp.ndarray,
        step_kvlen: jnp.ndarray,
        *,
        window: int = 0,
        interpret: bool,
        num_lanes: int = 2,
        max_steps: int = 1,
        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run the PAC kernel over a compiled DecodePlan's step arrays.

    ``q_rows`` holds each task's queries GQA-folded (``ops.fold_gqa``):
    row ``(kv * max_q + qi) * group + g`` is query slot ``qi``'s head
    ``kv * group + g``, so every KV head's rows are contiguous.  Returns
    task-major partials ``(o, m, l)`` in the same row order, of shapes
    ``(T+1, rows, d)``, ``(T+1, rows)``, ``(T+1, rows)``.
    """
    Tp1, rows, d = q_rows.shape
    _, page, n_kv, _ = k_pool.shape
    assert rows % n_kv == 0, (rows, n_kv)

    def task_index(lane, step, st, *_):
        return (st[lane, step], 0, 0)

    def kv_index(lane, step, st, sp, *_):
        return (sp[lane, step], 0, 0, 0)

    kernel = functools.partial(_pac_kernel, n_kv=n_kv, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(num_lanes, max_steps),
        in_specs=[
            pl.BlockSpec((1, rows, d), task_index),
            pl.BlockSpec((1, rows, 1), task_index),
            pl.BlockSpec((1, page, n_kv, d), kv_index),
            pl.BlockSpec((1, page, n_kv, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, d), task_index),
            pl.BlockSpec((1, rows, STAT_LANES), task_index),
            pl.BlockSpec((1, rows, STAT_LANES), task_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, STAT_LANES), jnp.float32),
            pltpu.VMEM((rows, STAT_LANES), jnp.float32),
        ],
    )
    out_shapes = [
        jax.ShapeDtypeStruct((Tp1, rows, d), jnp.float32),
        jax.ShapeDtypeStruct((Tp1, rows, STAT_LANES), jnp.float32),
        jax.ShapeDtypeStruct((Tp1, rows, STAT_LANES), jnp.float32),
    ]
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(step_task, step_page, step_valid, step_first, step_last,
      step_pos, step_kvlen,
      q_rows, qpos_rows, k_pool, v_pool)
    return o, m[..., 0], l[..., 0]
