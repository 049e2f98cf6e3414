"""CoDec POR (partial output reduction) Pallas kernel (paper Alg. 3).

Binary log-sum-exp merge of two partial-output sets belonging to the same
queries.  The serving path normally uses the flattened segment reduction in
``ops.combine_partials`` (one pass, maximal parallelism — our TPU-native
form of the paper's parallel tree reduction), but the pairwise kernel is
kept (a) as the literal paper primitive, property-tested for the
associativity/commutativity the tree reduction relies on, and (b) for the
cross-device sequence-parallel combine where exactly two partials meet.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_interpret
from . import ref as ref_mod


def _por_kernel(o1_ref, m1_ref, l1_ref, o2_ref, m2_ref, l2_ref,
                o_ref, m_ref, l_ref):
    m1 = m1_ref[...]
    m2 = m2_ref[...]
    l1 = l1_ref[...]
    l2 = l2_ref[...]
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m) * l1
    a2 = jnp.exp(m2 - m) * l2
    l = a1 + a2
    l_safe = jnp.maximum(l, 1e-30)
    o = (o1_ref[...] * a1[..., None] + o2_ref[...] * a2[..., None]) / l_safe[..., None]
    o_ref[...] = o
    m_ref[...] = m
    l_ref[...] = l


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def por(o1: jnp.ndarray, m1: jnp.ndarray, l1: jnp.ndarray,
        o2: jnp.ndarray, m2: jnp.ndarray, l2: jnp.ndarray,
        *, block_rows: int = 128, interpret: Optional[bool] = None,
        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Merge partials. o*: (N, h, d) f32; m*/l*: (N, h) f32."""
    n, h, d = o1.shape
    block_rows = min(block_rows, n)
    grid = (-(-n // block_rows),)

    o_spec = pl.BlockSpec((block_rows, h, d), lambda i: (i, 0, 0))
    ml_spec = pl.BlockSpec((block_rows, h), lambda i: (i, 0))

    return pl.pallas_call(
        _por_kernel,
        grid=grid,
        in_specs=[o_spec, ml_spec, ml_spec, o_spec, ml_spec, ml_spec],
        out_specs=[o_spec, ml_spec, ml_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, d), jnp.float32),
            jax.ShapeDtypeStruct((n, h), jnp.float32),
            jax.ShapeDtypeStruct((n, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=(pallas_interpret() if interpret is None
                   else interpret),
    )(o1, m1, l1, o2, m2, l2)


# --------------------------------------------------------------------- #
# cross-device sequence-parallel merge (SPMD decode, under shard_map)
# --------------------------------------------------------------------- #
def por_allmerge(o: jnp.ndarray, m: jnp.ndarray, l: jnp.ndarray,
                 axis_name: str, axis_size: int,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """All-reduce the per-query partials over a mesh axis using POR only.

    Recursive-doubling butterfly: ``log2(axis_size)`` ``ppermute``
    rounds, each followed by one pairwise POR merge — no ``psum`` and no
    ``all_gather`` (an LSE merge is not a sum, so ``psum`` cannot
    express it, and gathering all partials would move ``axis_size``
    copies instead of ``log2``).  After the last round every device
    holds the full merge identically in max space (``m`` — pure
    ``maximum`` commutes bitwise) and to one FMA slot asymmetry in
    ``o``/``l``: XLA fuses ``o1*a1 + o2*a2`` as
    ``fma(o_local, a_local, o_recv*a_recv)``, and the local/received
    operand roles swap between XOR partners, so the two sides round
    once differently (±1 ulp).  Sampling consumes device 0's logits
    (replicated out-spec), so token streams stay deterministic.

    Requires ``axis_size`` to be a power of two (mesh data axes are).
    Partials over disjoint KV slices are exactly what this merges — each
    data-shard's plan covers only the KV pages resident on that shard.
    """
    if axis_size <= 1:
        return o, m, l
    if axis_size & (axis_size - 1):
        raise ValueError(f"por_allmerge needs a power-of-two axis, "
                         f"got {axis_size}")
    shift = 1
    while shift < axis_size:
        perm = [(i, i ^ shift) for i in range(axis_size)]
        o2 = jax.lax.ppermute(o, axis_name, perm)
        m2 = jax.lax.ppermute(m, axis_name, perm)
        l2 = jax.lax.ppermute(l, axis_name, perm)
        o, m, l = ref_mod.por_ref(o, m, l, o2, m2, l2)
        shift *= 2
    return o, m, l


def _pack(o: jnp.ndarray, m: jnp.ndarray, l: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate([o, m[..., None], l[..., None]], axis=-1)


def _unpack(p: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    return p[..., :-2], p[..., -2], p[..., -1]


def por_subgroup_merge(o: jnp.ndarray, m: jnp.ndarray, l: jnp.ndarray,
                       axis_name: str, axis_size: int,
                       contrib: jnp.ndarray,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sparse POR merge over the minimal subgroup of contributing shards.

    Same result contract as :func:`por_allmerge` — after the call every
    device on the axis holds the merged partials (bitwise in max space,
    to FMA slot asymmetry in ``o``/``l``, and **bitwise verbatim** when
    a single shard contributes) — but with two cost improvements for
    the sparse sharded-decode case:

    * **one packed transfer per round**: ``(o, m, l)`` ride in a single
      ``(rows, h, d + 2)`` f32 buffer, so each butterfly round issues
      ONE ``ppermute`` (and pays one launch) instead of three;
    * **subgroup rounds**: ``contrib`` is a traced ``(axis_size,)`` bool
      vector marking the shards that hold non-identity partials for the
      packed rows (from the plan's ownership mask).  With contributors
      confined to an aligned block of ``2^k`` devices, only the first
      ``k`` rounds are *merge* rounds (ppermute + pairwise POR inside
      the block); the remaining ``log2(axis_size) - k`` rounds degrade
      to *copy* rounds — the block's finished result is forwarded
      verbatim (``where`` select, no float math), doubling the holder
      set each round until the axis is covered.  Copy rounds move the
      same bytes but skip the POR FLOPs and, crucially, are bitwise
      round-trips, so devices with no contribution introduce zero float
      perturbation.

    The round structure is selected with traced predicates (anchor =
    first contributor, ``xall`` = OR-fold of ``id XOR anchor`` over
    contributors; round ``s`` merges iff ``xall >= s``), so ONE compiled
    program serves every ownership pattern — the mask does not enter
    the jit signature.  Devices outside the contributor block feed
    identity partials (``m = MASK, l = 0``) into nothing: their rows
    are overwritten by the copy cascade.

    Requires ``axis_size`` to be a power of two (mesh data axes are).
    """
    if axis_size <= 1:
        return o, m, l
    if axis_size & (axis_size - 1):
        raise ValueError(f"por_subgroup_merge needs a power-of-two axis, "
                         f"got {axis_size}")
    c = contrib.astype(jnp.int32)
    ids = jnp.arange(axis_size, dtype=jnp.int32)
    anchor = jnp.argmax(c).astype(jnp.int32)   # first contributor (0 if none)
    xall = jnp.max(jnp.where(c > 0, ids ^ anchor, 0))
    me = jax.lax.axis_index(axis_name)
    packed = _pack(o, m, l)
    shift = 1
    while shift < axis_size:
        perm = [(i, i ^ shift) for i in range(axis_size)]
        recv = jax.lax.ppermute(packed, axis_name, perm)
        og, mg, lg = ref_mod.por_ref(*_unpack(packed), *_unpack(recv))
        merged = _pack(og, mg, lg)
        # copy round: anchor's aligned shift-block already holds the
        # finished merge; its XOR partners receive it verbatim
        have = (me // shift) == (anchor // shift)
        copied = jnp.where(have, packed, recv)
        packed = jnp.where(xall >= shift, merged, copied)
        shift *= 2
    return _unpack(packed)
