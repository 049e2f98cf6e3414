"""Compile the decode-attention kernels for a described TPU v5e.

The rest of the suite runs Pallas in interpret mode on the CPU, which
accepts layouts Mosaic refuses.  These tests compile the main path's
kernels with the TPU compiler for a chip that is described, not
attached, at qwen3-4b's widths (32 query heads, 8 KV heads, head_dim
128): the PAC kernel with float32 and bfloat16 pools at pages 16 and
64, once with all heads and once with the head block one device of a
model-2 mesh sees, plus the ``hydragen`` and ``codec-xla`` partials.
Nothing runs; a compile that passes says the chip's compiler takes the
program, not that its results are right.

The topology is described inside a fixture: only one process may load
the TPU library at a time, so nothing may touch it while modules are
imported.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import cost_model, plan as plan_mod, tree as tree_mod
from repro.kernels import hydragen, ops

CFG = get_config("qwen3-4b")
NUM_REQUESTS = 4
DOC_LEN, QUESTION_LEN = 1024, 32
MAX_Q = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-chip compile cannot be read back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _plan(page: int):
    forest = tree_mod.two_level(NUM_REQUESTS, DOC_LEN, QUESTION_LEN,
                                block_size=page)
    plan_mod.assign_dense_pages(forest)
    cm = cost_model.CostModel(CFG.num_heads, CFG.num_kv_heads,
                              CFG.head_dim, page_size=page)
    plan = plan_mod.build_plan(forest, cm, num_lanes=2, max_q=MAX_Q)
    return plan_mod.bucket_plan(plan, NUM_REQUESTS)


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _operands(one_chip, page, pool_dtype, h_q, n_kv):
    num_pages = (DOC_LEN + NUM_REQUESTS * QUESTION_LEN) // page + 8
    q = jax.ShapeDtypeStruct((NUM_REQUESTS, h_q, CFG.head_dim),
                             jnp.bfloat16, sharding=one_chip)
    pool = jax.ShapeDtypeStruct((num_pages, page, n_kv, CFG.head_dim),
                                pool_dtype, sharding=one_chip)
    return q, pool


@pytest.mark.parametrize("heads", ["all", "model-sharded"])
@pytest.mark.parametrize("page", [16, 64])
@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_pac_compiles_for_v5e(one_chip, no_compile_cache, heads, page,
                              pool_dtype):
    shards = 1 if heads == "all" else 2
    h_q, n_kv = CFG.num_heads // shards, CFG.num_kv_heads // shards
    plan = _plan(page)
    q, pool = _operands(one_chip, page, pool_dtype, h_q, n_kv)
    pa = _sds(ops.plan_arrays(plan), one_chip)
    lowered = ops.codec_partials_arrays.lower(
        q, pool, pool, pa, NUM_REQUESTS, impl="pallas", interpret=False)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    o = compiled.out_info[0]
    assert o.shape == (NUM_REQUESTS, h_q, CFG.head_dim)


@pytest.mark.parametrize("impl", ["hydragen", "codec-xla"])
def test_xla_partials_compile_for_v5e(one_chip, no_compile_cache, impl):
    page = 16
    plan = _plan(page)
    q, pool = _operands(one_chip, page, jnp.float32, CFG.num_heads,
                        CFG.num_kv_heads)
    if impl == "hydragen":
        prepared = _sds(hydragen.prepare(plan), one_chip)
        lowered = hydragen.hydragen_partials_arrays.lower(
            q, pool, pool, prepared, NUM_REQUESTS)
    else:
        prepared = _sds(ops.plan_arrays(plan), one_chip)
        lowered = ops.codec_partials_arrays.lower(
            q, pool, pool, prepared, NUM_REQUESTS, impl="xla")
    compiled = lowered.compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.out_info[0].shape == (NUM_REQUESTS, CFG.num_heads,
                                          CFG.head_dim)
