"""FlashDecoding baseline kernel (dense 4D batch KV layout).

The paper's baseline: decode attention over regular ``(B, L, n_kv, d)``
tensors — each request's KV is read independently, so a shared prefix is
fetched once *per request*.  Implemented as a Pallas TPU kernel with the
same flash accumulators as PAC so kernel-vs-kernel comparisons isolate the
prefix-sharing effect.  (FlashDecoding's split-KV trick exists to create
parallelism across SMs; on TPU the chunk dimension is the sequential grid
axis and batch×head supplies the parallelism, so the split is implicit.)

Note: CoDec with a ``flash_plan`` (every request its own task chain) is the
*plan-level* baseline over the paged pool; this kernel is the *layout-level*
baseline over dense tensors.  Both are exposed to the benchmarks.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_interpret

MASK_VALUE = -1e30


def _fd_kernel(kvlen_ref,            # scalar prefetch (B,)
               q_ref,                # (1, h_q, d)
               k_ref,                # (1, chunk, n_kv, d)
               v_ref,
               o_ref,                # (1, h_q, d)
               acc, m_s, l_s,        # scratch
               *, n_kv: int, group: int, chunk: int, window: int):
    b = pl.program_id(0)
    c = pl.program_id(1)
    num_chunks = pl.num_programs(1)
    kv_len = kvlen_ref[b]
    start = c * chunk

    @pl.when(c == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, MASK_VALUE)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(start < kv_len)
    def _step():
        h_q, d = q_ref.shape[1], q_ref.shape[2]
        scale = 1.0 / np.sqrt(d)
        q = q_ref[0].astype(jnp.float32)                     # (h_q, d)
        qf = q.reshape(n_kv, group, d)
        kf = k_ref[0].astype(jnp.float32).transpose(1, 0, 2)  # (n_kv, chunk, d)
        vf = v_ref[0].astype(jnp.float32).transpose(1, 0, 2)
        s = jax.lax.dot_general(
            qf, kf, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale       # (n_kv, g, chunk)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        mask = pos < kv_len
        if window > 0:
            mask = mask & (pos > kv_len - 1 - window)
        # rows beyond kv_len may be OOB block padding (NaN): zero V so the
        # (p==0) x NaN product can't poison the accumulator
        vf = jnp.where(mask.reshape(1, chunk, 1), vf, 0.0)
        mask = jnp.broadcast_to(mask[None], s.shape)
        s = jnp.where(mask, s, MASK_VALUE)
        m_new = jnp.maximum(m_s[...], jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None]) * mask
        alpha = jnp.exp(m_s[...] - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, vf, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc[...] = acc[...] * alpha[..., None] + pv
        m_s[...] = m_new

    @pl.when(c == num_chunks - 1)
    def _finalize():
        h_q, d = q_ref.shape[1], q_ref.shape[2]
        l_safe = jnp.maximum(l_s[...], 1e-30)
        o = acc[...] / l_safe[..., None]                      # (n_kv, g, d)
        o_ref[0] = o.reshape(h_q, d)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "window", "interpret"))
def flash_decode(q: jnp.ndarray,        # (B, h_q, d)
                 k: jnp.ndarray,        # (B, L, n_kv, d)
                 v: jnp.ndarray,
                 kv_lens: jnp.ndarray,  # (B,) int32
                 *, chunk: int = 256, window: int = 0,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    B, h_q, d = q.shape
    _, L, n_kv, _ = k.shape
    group = h_q // n_kv
    chunk = min(chunk, L)
    num_chunks = -(-L // chunk)

    kernel = functools.partial(_fd_kernel, n_kv=n_kv, group=group,
                               chunk=chunk, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, num_chunks),
        in_specs=[
            pl.BlockSpec((1, h_q, d), lambda b, c, *_: (b, 0, 0)),
            pl.BlockSpec((1, chunk, n_kv, d), lambda b, c, *_: (b, c, 0, 0)),
            pl.BlockSpec((1, chunk, n_kv, d), lambda b, c, *_: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h_q, d), lambda b, c, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_kv, group, d), jnp.float32),
            pltpu.VMEM((n_kv, group), jnp.float32),
            pltpu.VMEM((n_kv, group), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h_q, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=(pallas_interpret() if interpret is None
                   else interpret),
    )(kv_lens.astype(jnp.int32), q, k, v)
    return out.astype(q.dtype)
