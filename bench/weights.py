"""Random weights from the seed, made on the device in one jitted call,
in bfloat16 and in the engine's parameter layout.  What to draw is the
architecture module's (``build``); the draws it shares are here.

The benchmark makes the weights, so the plain reference may use them:
nothing the program computed goes into the comparison.  The layout is
checked against the program's own ``init_params`` shapes (``check``)."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import spec


def jax_key(seed: int, stream: int = 0):
    """A JAX key from any non-negative seed (wider than 32 bits too)."""
    word = np.random.SeedSequence([int(seed), 1000 + stream]).generate_state(1)
    return jax.random.PRNGKey(int(word[0] & 0x7FFFFFFF))


def dense(key, din: int, dout: int, dtype, gain: float = 1.0):
    """A (din, dout) matrix, N(0, gain^2 / din)."""
    x = jax.random.normal(key, (din, dout), jnp.float32)
    return (x * (gain / np.sqrt(din))).astype(dtype)


def scale(key, d: int, jitter: float, dtype):
    """A norm scale, uniform in 1 +- jitter."""
    u = jax.random.uniform(key, (d,), jnp.float32, -1.0, 1.0)
    return (1.0 + jitter * u).astype(dtype)


def make(model: Dict[str, Any], seed: int):
    """The weights for ``seed``, drawn by the configuration's
    architecture module; the same seed gives the same weights."""
    arch = spec.architecture_of(model)
    return jax.block_until_ready(
        jax.jit(lambda k: arch.build(model, k))(jax_key(seed)))


def nbytes(params) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(params))


def check(params, expected) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from the
    program's (``expected``: ``jax.eval_shape`` of its initialiser)."""
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), expected)
    if got != want:
        raise ValueError(f"benchmark weights do not match the engine's "
                         f"parameter layout:\n{got}\n!=\n{want}")
