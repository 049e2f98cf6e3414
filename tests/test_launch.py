"""Launch layer: sharding rules, hlo parsing, cost model, mini dry-run."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, PAPER_ARCH, get_config, smoke_config
from repro.core.cost_model import CostModel, profile
from repro.launch import hlo_stats
from repro.launch import sharding as sh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# --------------------------------------------------------------------- #
# sharding rules
# --------------------------------------------------------------------- #
class _FakeMesh:
    def __init__(self, sizes):
        self.shape = sizes
        self.axis_names = tuple(sizes)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS + [PAPER_ARCH])
def test_param_specs_legal_for_all_archs(arch):
    """Every full-config param gets a spec whose axes divide its dims."""
    from repro.models import transformer as T
    cfg = get_config(arch)
    params_sds = jax.eval_shape(lambda k: T.init_params(cfg, k),
                                jax.random.PRNGKey(0))
    mesh = _FakeMesh({"data": 16, "model": 16})

    def check(path, leaf):
        ps = sh.param_pspec(
            "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path), len(leaf.shape), cfg)
        spec = sh.legalize(ps, leaf.shape, mesh)
        for i, entry in enumerate(spec):
            if entry is not None:
                assert leaf.shape[i] % sh._axis_size(mesh, entry) == 0
        return spec

    specs = jax.tree_util.tree_map_with_path(check, params_sds)
    # big weights must actually be sharded (not silently replicated)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    sds_flat = jax.tree_util.tree_flatten_with_path(params_sds)[0]
    for (path, spec), (_, leaf) in zip(flat, sds_flat):
        n = int(np.prod(leaf.shape))
        if n >= (1 << 22):  # >= 4M params
            assert any(e is not None for e in spec), (path, leaf.shape)


def test_embed_and_attn_specs():
    cfg = get_config("qwen3-4b")
    # vocab->data, d->model (§Perf: the transposed layout removed the
    # token-gather permute chain; see EXPERIMENTS.md)
    assert tuple(sh.param_pspec("embed", 2, cfg)) == ("data", "model")
    assert tuple(sh.param_pspec("blocks/sub0/attn/wq/w", 3, cfg)) \
        == (None, "data", "model")
    assert tuple(sh.param_pspec("blocks/sub0/attn/wo/w", 3, cfg)) \
        == (None, "model", "data")
    assert tuple(sh.param_pspec("blocks/sub0/ln/scale", 2, cfg)) \
        == ()


def test_moe_expert_parallel_spec():
    cfg = get_config("kimi-k2-1t-a32b")
    assert tuple(sh.param_pspec("blocks/sub0/ffn/wi", 4, cfg)) \
        == (None, "model", "data", None)
    assert tuple(sh.param_pspec("blocks/sub0/ffn/router", 3, cfg)) \
        == (None, "data", None)


# --------------------------------------------------------------------- #
# HLO collective parsing
# --------------------------------------------------------------------- #
HLO_SAMPLE = textwrap.dedent("""\
    ENTRY main {
      %p0 = f32[128,64]{1,0} parameter(0)
      %ar = f32[128,64]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
      %ag = bf16[256,64]{1,0} all-gather(%p0), replica_groups=[2,8]<=[16], dimensions={0}
      %rs = f32[16,64]{1,0} reduce-scatter(%p0), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
      %cp = f32[128,64]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
      %ars = (f32[32]{0}, f32[32]{0}) all-reduce-start(%p0), replica_groups={{0,1}}
      %ard = f32[32]{0} all-reduce-done(%ars)
      %dot = f32[128,128]{1,0} dot(%p0, %p0)
    }
""")


def test_collect_collectives_counts_and_bytes():
    st = hlo_stats.collect_collectives(HLO_SAMPLE, total_devices=16)
    assert st.count["all-reduce"] == 2      # plain + start (done excluded)
    assert st.count["all-gather"] == 1
    assert st.count["reduce-scatter"] == 1
    assert st.count["collective-permute"] == 1
    # all-reduce: 128*64*4 bytes, group 4 -> wire 2*(3/4)*32768
    ar_plain = 2 * 0.75 * 128 * 64 * 4
    ar_start = 2 * 0.5 * 32 * 4          # group 2, result half = f32[32]
    assert abs(st.link_bytes["all-reduce"] - (ar_plain + ar_start)) < 1
    # all-gather bf16[256,64] group 8 -> (7/8)*32768
    assert abs(st.link_bytes["all-gather"] - 0.875 * 256 * 64 * 2) < 1
    # permute: full size
    assert abs(st.link_bytes["collective-permute"] - 128 * 64 * 4) < 1


def test_group_size_parsing():
    assert hlo_stats._group_size("replica_groups={{0,1,2}}", 99) == 3
    assert hlo_stats._group_size("replica_groups=[4,64]<=[256]", 99) == 64
    assert hlo_stats._group_size("no groups here", 7) == 7


# --------------------------------------------------------------------- #
# cost model
# --------------------------------------------------------------------- #
def test_cost_model_monotone_and_bounds():
    cm = CostModel(32, 8, 128, page_size=64)
    assert cm(1, 1024) < cm(1, 8192) < cm(1, 65536)
    assert cm(1, 4096) <= cm(64, 4096)
    # long-thin decode task is memory bound; fat task compute bound
    assert cm.bound(1, 8192) == "memory"
    assert cm.bound(512, 8192) == "compute"


def test_cost_model_table_interpolation():
    cm0 = CostModel(8, 2, 64)
    table = {(1, 512): 1.0, (1, 2048): 3.0, (4, 512): 2.0, (4, 2048): 6.0}
    cm = CostModel(8, 2, 64, table=table)
    for k, v in table.items():
        assert abs(cm(*k) - v) < 1e-9      # exact at grid points
    mid = cm(2, 1024)                      # log-bilinear midpoint
    assert 1.0 < mid < 6.0


def test_profile_builds_usable_table():
    cm = CostModel(4, 2, 16)
    calls = []
    cm2 = profile(cm, lambda nq, n: calls.append((nq, n)),
                  n_qs=(1, 2), ns=(64, 128), repeats=1)
    assert cm2._grid is not None
    assert cm2(1, 64) >= 0


# --------------------------------------------------------------------- #
# mini dry-run in a subprocess (4 forced host devices)
# --------------------------------------------------------------------- #
MINI_DRYRUN = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.launch import sharding as sh
    from repro.training import trainer
    from repro.training.optimizer import cosine_schedule, make_optimizer

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = smoke_config("qwen2.5-14b")
    opt = make_optimizer("adamw", cosine_schedule(1e-3, 2, 10))
    step = trainer.make_train_step(cfg, opt, remat=False)
    state_sds = trainer.abstract_state(cfg, opt)
    psh = sh.params_shardings(state_sds.params, mesh, cfg)
    state = trainer.TrainState(
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sh.replicated(mesh)),
        sh.with_sharding(state_sds.params, psh),
        jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh.replicated(mesh)),
            state_sds.opt_state))
    bshd = sh.batch_sharding(mesh, 2, 4)
    tok = jax.ShapeDtypeStruct((4, 16), jnp.int32, sharding=bshd)
    with mesh:
        compiled = jax.jit(step).lower(state, (tok, tok)).compile()
    print("MEM", compiled.memory_analysis().temp_size_in_bytes)
    ca = compiled.cost_analysis()
    print("FLOPS", ca["flops"])
    print("DRYRUN_OK")
""")


@pytest.mark.slow
def test_mini_dryrun_subprocess(tmp_path):
    script = tmp_path / "mini.py"
    script.write_text(MINI_DRYRUN)
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "DRYRUN_OK" in r.stdout, r.stderr[-2000:]


# --------------------------------------------------------------------- #
# serving launcher exit status
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("max_steps,rc", [(0, 0), (2, 1)],
                         ids=["finished", "unfinished"])
def test_serve_exit_code_reports_unfinished(tmp_path, max_steps, rc):
    """A run that leaves requests unfinished must not exit 0."""
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "qwen3-4b",
         "--smoke", "--backend", "codec-xla", "--requests", "2",
         "--doc-len", "32", "--max-new", "4", "--no-telemetry",
         "--max-steps", str(max_steps)],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == rc, r.stdout[-2000:] + r.stderr[-2000:]
    assert ("unfinished" in r.stdout) == bool(rc)

