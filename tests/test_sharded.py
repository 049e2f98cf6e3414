"""Multi-device sharded decode serving (``src/repro/distributed/``).

In-process tests cover the host-side pieces on one device — the
sharded allocator's per-shard invariants and placement policy, the
mesh-aware plan partitioner, the ICI merge term, registry capability
flags, and the full SPMD engine on a ``1x1`` mesh (the whole
shard_map path minus collectives).

The acceptance sweep runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the pattern
``test_launch.py`` uses — conftest strips XLA_FLAGS from the main
process): the same seeded forest workload at ``1x1 / 2x1 / 1x2 / 2x2``
meshes must produce greedy AND temp>0 token streams identical to the
single-device eager reference, with a forced sequence split of the
long shared prefix, an eviction + chunked-prefill pressure run on the
``2x2`` mesh, zero leaked pages on every shard, and the fused compile
count bounded by bucket signatures across the resharding events.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.core import tree as tree_mod
from repro.core.cost_model import CostModel, HardwareSpec
from repro.distributed.kv_pool import ShardedPageAllocator
from repro.distributed.mesh import decode_mesh, parse_mesh
from repro.kernels import registry

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# --------------------------------------------------------------------- #
# ICI merge cost (HardwareSpec.ici_bw finally read)
# --------------------------------------------------------------------- #
def test_merge_cost_wired_to_ici_bw():
    cm = CostModel(8, 2, 64, page_size=8)
    assert cm.merge_cost(1, 16) == 0.0
    assert cm.merge_cost(4, 0) == 0.0
    # more splits -> more butterfly rounds -> more cost
    assert 0 < cm.merge_cost(2, 16) < cm.merge_cost(4, 16) \
        < cm.merge_cost(8, 16)
    # more queries -> more wire bytes
    assert cm.merge_cost(2, 16) < cm.merge_cost(2, 64)
    # a slower interconnect must cost more (ici_bw actually read)
    slow = CostModel(8, 2, 64, page_size=8,
                     hw=HardwareSpec(ici_bw=1e9))
    assert slow.merge_cost(2, 64) > cm.merge_cost(2, 64)


# --------------------------------------------------------------------- #
# sharded allocator: per-shard invariants + placement
# --------------------------------------------------------------------- #
def test_sharded_allocator_invariants():
    al = ShardedPageAllocator(2, 8)
    assert al.num_pages == 16 and al.num_free == 16
    rows = al.alloc(5, hint=7)
    assert len(rows) == 5 and al.num_free == 11
    # trash rows (local id == pages_per_shard) are never handed out
    assert all(al.local_of(g) < al.pages_per_shard for g in rows)
    al.retain(rows[:2])
    al.release(rows[:2])            # refcount 2 -> 1, still allocated
    assert al.num_used == 5
    al.release(rows)
    assert al.num_free == 16
    al.check()
    with pytest.raises(ValueError):
        al.release([rows[0]])       # double free
    with pytest.raises(ValueError):
        al.release([al.pages_per_shard])   # shard 0's trash row
    with pytest.raises(MemoryError):
        al.alloc(17)


def test_placement_sequence_splits_long_nodes():
    """A node's pages stay on one shard until the quota, then continue
    on the next shard — contiguous runs = the sequence split."""
    al = ShardedPageAllocator(2, 8, seq_split_pages=2)
    rows = al.alloc(6, hint=1)
    owners = [al.shard_of(g) for g in rows]
    # runs of exactly quota length, alternating shards
    assert owners == [owners[0], owners[0], 1 - owners[0], 1 - owners[0],
                      owners[0], owners[0]]
    # a second node starts on the freest shard but keeps its own runs
    rows2 = al.alloc(2, hint=2)
    assert len({al.shard_of(g) for g in rows2}) == 1
    al.check()


def test_placement_without_quota_spills_only_when_full():
    al = ShardedPageAllocator(2, 4)
    rows = al.alloc(6, hint=3)      # shard of 4 fills, then spills
    owners = [al.shard_of(g) for g in rows]
    assert owners[:4] == [owners[0]] * 4 and owners[4:] == [1 - owners[0]] * 2
    al.check()


# --------------------------------------------------------------------- #
# mesh-aware plan partitioner
# --------------------------------------------------------------------- #
def _forest_with_sharded_pages(num_shards=2, quota=2):
    ps = 8
    forest = tree_mod.PrefixForest(ps)
    doc = np.arange(100, 100 + 6 * ps, dtype=np.int32)   # 6-page shared node
    for r in range(3):
        forest.insert_tokens(r, np.concatenate(
            [doc, np.asarray([200 + r, 201 + r], np.int32)]))
    al = ShardedPageAllocator(num_shards, 32, seq_split_pages=quota)
    for node in forest.real_nodes():
        npages = -(-node.length // ps)
        node.page_ids = al.alloc(npages, hint=node.id)
    return forest, al


def test_build_sharded_plan_partitions_and_localizes():
    forest, al = _forest_with_sharded_pages()
    cm = CostModel(4, 2, 16, page_size=8)
    sp = plan_mod.build_sharded_plan(forest, cm, al.num_shards, al.stride,
                                     num_lanes=2, max_q=8)
    assert len(sp.shards) == 2
    assert sp.seq_splits >= 1                    # the 6-page doc node split
    assert sp.merge_cost > 0 and sp.makespan > max(
        p.makespan for p in sp.shards) - 1e-12
    # common bucketed shapes across shards (stackable)
    shapes = {(p.max_steps, p.task_qnum.shape[0], p.max_pages,
               p.num_queries) for p in sp.shards}
    assert len(shapes) == 1
    # every page id is shard-local (within the shard's block incl. trash)
    for p in sp.shards:
        assert p.step_page.max() < al.stride
        assert p.task_pages.max() < al.stride
    # coverage: per-shard valid KV tokens sum to the plan-covered total
    covered = sum(int(p.task_kvlen[t])
                  for p in sp.shards for t in range(p.num_tasks))
    total = sum(n.length for n in forest.real_nodes())
    assert covered == total
    st = sp.stats()
    assert st["num_shards"] == 2 and st["seq_splits"] == sp.seq_splits
    assert st["merge_cost"] > 0


def test_sharded_plan_single_shard_has_no_merge_term():
    forest, al = _forest_with_sharded_pages(num_shards=1, quota=0)
    cm = CostModel(4, 2, 16, page_size=8)
    sp = plan_mod.build_sharded_plan(forest, cm, 1, al.stride)
    assert sp.merge_cost == 0.0 and sp.seq_splits == 0
    assert sp.makespan == pytest.approx(sp.shards[0].makespan)


# --------------------------------------------------------------------- #
# registry capability flag + engine guards
# --------------------------------------------------------------------- #
def test_registry_shardable_flags():
    assert registry.get("codec-xla").shardable
    assert registry.get("codec-pallas").shardable
    assert not registry.get("ref").shardable
    assert not registry.get("hydragen").shardable
    assert set(registry.names(shardable=True)) == {"codec-pallas",
                                                   "codec-xla"}
    for n in registry.names(shardable=True):
        assert registry.get(n).jit_safe     # shardable implies jit-safe


def test_parse_mesh():
    assert parse_mesh("2x2") == (2, 2)
    assert parse_mesh("1X4") == (1, 4)
    with pytest.raises(ValueError):
        parse_mesh("2")
    with pytest.raises(ValueError):
        parse_mesh("0x2")


def test_decode_mesh_rejects_non_pow2_data():
    with pytest.raises(ValueError):
        decode_mesh(3, 1)


def test_mesh_engine_guards():
    import jax
    from repro.configs import smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import DecodeEngine
    cfg = smoke_config("qwen2.5-14b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    mesh = decode_mesh(1, 1)
    with pytest.raises(ValueError, match="fused"):
        DecodeEngine(cfg, params, mesh=mesh)
    with pytest.raises(ValueError, match="shardable"):
        DecodeEngine(cfg, params, mesh=mesh, fused=True, backend="hydragen")


# --------------------------------------------------------------------- #
# 1x1 mesh: the whole SPMD path on one device, byte-identical streams
# --------------------------------------------------------------------- #
def test_mesh_1x1_engine_matches_plain_engine():
    import jax
    from repro.configs import smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import DecodeEngine
    cfg = smoke_config("qwen2.5-14b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    doc = list(range(10, 42))
    prompts = [doc + [100 + i] for i in range(3)]

    def run(**kw):
        eng = DecodeEngine(cfg, params, page_size=8, num_pages=64,
                           backend="codec-xla", max_q=8, temperature=0.0,
                           **kw)
        rids = [eng.add_request(p, max_new=4) for p in prompts]
        eng.run(16)
        outs = {i: list(eng.requests[r].generated)
                for i, r in enumerate(rids)}
        return outs, eng

    ref, _ = run(fused=False)
    got, eng = run(fused=True, mesh=decode_mesh(1, 1))
    assert got == ref
    assert eng.stats["fused_calls"] == eng.stats["steps"]
    assert eng.fused_cache_size <= len(eng.bucket_signatures)
    # leak-free per shard after release
    for r in list(eng.requests):
        eng.release(r)
    for s in eng.pool.allocator.shards:
        assert s.num_free == s.num_pages
    eng.pool.allocator.check()


# --------------------------------------------------------------------- #
# acceptance sweep: 4 forced host devices, all mesh shapes, pressure
# --------------------------------------------------------------------- #
SHARDED_PARITY = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro.configs import smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import DecodeEngine
    from repro.distributed import decode_mesh

    cfg = smoke_config("qwen2.5-14b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    DOC = list(range(10, 58))                      # 6-page shared prefix
    PROMPTS = [DOC + [100 + 3 * i + j for j in range(3)] for i in range(4)]
    LATE = DOC + [250, 251]                        # arrives mid-decode

    def run(mesh=None, temperature=0.0, num_pages=256, prefill_chunk=None,
            fused=True, check_leaks=True, replicate=False):
        eng = DecodeEngine(cfg, params, page_size=8, num_pages=num_pages,
                           backend="codec-xla", max_q=8,
                           temperature=temperature, mesh=mesh, fused=fused,
                           seq_split_pages=2 if mesh is not None else 0,
                           prefill_chunk=prefill_chunk, replicate=replicate)
        rids = [eng.add_request(p, max_new=6) for p in PROMPTS]
        eng.step(); eng.step()
        rids.append(eng.add_request(LATE, max_new=4))
        eng.run(96)
        outs = {i: list(eng.requests[r].generated)
                for i, r in enumerate(rids)}
        assert all(len(outs[i]) == eng.requests[r].max_new
                   for i, r in enumerate(rids)), "unfinished requests"
        stats = dict(eng.stats)
        stats["seq_splits"] = sum(sp.seq_splits
                                  for sp in eng._sharded_plans.values())
        stats["compile_ok"] = (eng.fused_cache_size
                               <= len(eng.bucket_signatures)) if eng.fused \\
            else True
        for r in list(eng.requests):
            eng.release(r)
        if check_leaks and mesh is not None:
            for s in eng.pool.allocator.shards:
                assert s.num_free == s.num_pages, "leaked pages on a shard"
        eng.pool.allocator.check()
        return outs, stats

    ref, _ = run(mesh=None, fused=False)           # single-device eager
    reft, _ = run(mesh=None, fused=False, temperature=0.7)
    for d, m in ((1, 1), (2, 1), (1, 2), (2, 2)):
        mesh = decode_mesh(d, m)
        got, st = run(mesh=mesh)
        assert got == ref, f"greedy stream diverged on {d}x{m}"
        assert st["compile_ok"], f"compile count unbounded on {d}x{m}"
        if d > 1:
            assert st["seq_splits"] >= 1, f"no sequence split on {d}x{m}"
        gott, _ = run(mesh=mesh, temperature=0.7)
        assert gott == reft, f"temp>0 stream diverged on {d}x{m}"
        print(f"mesh {d}x{m}: parity OK")

    # forced replication: the hot shared prefix is promoted to replicas
    # on every data shard, streams stay byte-identical (replicated rows
    # are computed identically everywhere and skip the wire), and every
    # replica page is reclaimed on release
    for d, m in ((2, 1), (2, 2)):
        gotr, str_ = run(mesh=decode_mesh(d, m), replicate=True)
        assert gotr == ref, f"replicated stream diverged on {d}x{m}"
        assert str_["replica_promotions"] >= 1, str_
        assert str_["compile_ok"], str_
        gotrt, _ = run(mesh=decode_mesh(d, m), replicate=True,
                       temperature=0.7)
        assert gotrt == reft, f"replicated temp stream diverged on {d}x{m}"
        print(f"mesh {d}x{m}: replication OK")

    # 2x2 under memory pressure: eviction + chunked prefill, same stream
    gotp, stp = run(mesh=decode_mesh(2, 2), num_pages=10, prefill_chunk=8)
    assert gotp == ref, "pressured 2x2 stream diverged"
    assert stp["preempted"] >= 1, stp
    assert stp["prefill_chunks"] >= 1, stp
    assert stp["compile_ok"], stp

    # replication enabled under the same pressure: the free-page guard
    # and the demotion reclaim tier must keep the stream correct
    gotrp, strp = run(mesh=decode_mesh(2, 1), num_pages=12,
                      prefill_chunk=8, replicate=True)
    assert gotrp == ref, "pressured replicated stream diverged"
    assert strp["compile_ok"], strp
    print("SHARDED_PARITY_OK")
""")


def test_sharded_parity_subprocess(tmp_path):
    """Acceptance: mesh-shape invariance + pressure on 4 fake devices."""
    script = tmp_path / "sharded_parity.py"
    script.write_text(SHARDED_PARITY)
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=1200)
    assert "SHARDED_PARITY_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]


ARCH_SWEEP = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    from repro.configs import smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import DecodeEngine
    from repro.distributed import decode_mesh

    for arch, page in (("gemma3-1b", 16),        # sliding-window layers
                       ("jamba-v0.1-52b", 8),    # hybrid attn + mamba
                       ("mamba2-2.7b", 8)):      # attention-free
        cfg = smoke_config(arch)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        doc = list(range(10, 10 + 64))
        prompts = [doc + [100 + i, 101 + i] for i in range(2)]
        outs = {}
        for mode in ("eager", "mesh"):
            kw = (dict(fused=True, mesh=decode_mesh(2, 1),
                       seq_split_pages=2) if mode == "mesh"
                  else dict(fused=False))
            eng = DecodeEngine(cfg, params, page_size=page, num_pages=64,
                               backend="codec-xla", max_q=8,
                               temperature=0.0, **kw)
            for p in prompts:
                eng.add_request(p, max_new=4)
            outs[mode] = eng.run(12)
            eng.pool.allocator.check()
        assert outs["eager"] == outs["mesh"], (arch, outs)
        print(arch, "OK")
    print("ARCH_SWEEP_OK")
""")


def test_sharded_arch_sweep_subprocess(tmp_path):
    """Sliding-window, hybrid-SSM, and attention-free archs through the
    2-device sharded step (per-window plans, replicated Mamba state)."""
    script = tmp_path / "sharded_archs.py"
    script.write_text(ARCH_SWEEP)
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=1200)
    assert "ARCH_SWEEP_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]


POR_PROPERTY = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import itertools
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.kernels import por
    from repro.kernels.ref import MASK_VALUE

    rng = np.random.default_rng(0)

    def partials(D, rows, h, d, contrib):
        # per-shard stacked partials; non-contributing shards hold the
        # POR identity (o=0, m=MASK_VALUE, l=0) exactly as the sharded
        # step's tail/plan paths produce it
        o = rng.standard_normal((D, rows, h, d)).astype(np.float32)
        m = (3.0 * rng.standard_normal((D, rows, h))).astype(np.float32)
        l = rng.uniform(0.5, 4.0, (D, rows, h)).astype(np.float32)
        for s in range(D):
            if not contrib[s]:
                o[s], m[s], l[s] = 0.0, MASK_VALUE, 0.0
        return (jnp.asarray(o), jnp.asarray(m), jnp.asarray(l))

    def build(D):
        devs = np.asarray(jax.devices()[:D]).reshape(D)
        mesh = Mesh(devs, ("data",))
        spec = (P("data"),) * 3
        def sub(o, m, l, c):
            ro, rm, rl = por.por_subgroup_merge(o[0], m[0], l[0],
                                                "data", D, c)
            return ro[None], rm[None], rl[None]
        def full(o, m, l):
            ro, rm, rl = por.por_allmerge(o[0], m[0], l[0], "data", D)
            return ro[None], rm[None], rl[None]
        # contrib is a TRACED argument, exactly as the engine passes it:
        # ONE compiled program serves every ownership mask
        f_sub = jax.jit(jax.shard_map(sub, mesh=mesh,
                                      in_specs=spec + (P(),),
                                      out_specs=spec, check_vma=False))
        f_full = jax.jit(jax.shard_map(full, mesh=mesh, in_specs=spec,
                                       out_specs=spec, check_vma=False))
        return f_sub, f_full

    for D in (1, 2, 4):
        masks = [m for m in itertools.product([False, True], repeat=D)
                 if any(m)]
        f_sub, f_full = build(D)
        for trial in range(3):
            for mask in masks:
                args = partials(D, rows=5, h=2, d=16, contrib=mask)
                c = jnp.asarray(np.asarray(mask))
                got = [np.asarray(a) for a in f_sub(*args, c)]
                want = [np.asarray(a) for a in f_full(*args)]
                # the max-space statistic matches the full butterfly
                # BITWISE for every mask (identity merges are exact and
                # max admits no fused-multiply reassociation)...
                np.testing.assert_array_equal(got[1], want[1])
                # ...o and l match within FMA slot asymmetry + the one
                # (o*l)/l rounding only the butterfly's identity merges
                # pay; likewise across devices
                for g, w in zip(got, want):
                    np.testing.assert_allclose(g, w, rtol=2e-6, atol=2e-6)
                    for s in range(1, D):
                        np.testing.assert_allclose(g[s], g[0], rtol=2e-6,
                                                   atol=2e-6)
                ids = [i for i, f in enumerate(mask) if f]
                if len(ids) == 1:
                    # single contributor: pure copy cascade — the
                    # owner's partials reach every shard UNPERTURBED,
                    # bitwise (the wire-skip float-hygiene guarantee;
                    # the full butterfly would perturb o)
                    src = [np.asarray(a)[ids[0]] for a in args]
                    for g, s_ in zip(got, src):
                        for s in range(D):
                            np.testing.assert_array_equal(g[s], s_)
        # one compile each: the mask never enters the jit signature
        assert f_sub._cache_size() == 1, D
        # packed transfer: ONE ppermute per round (the full butterfly
        # pays three; copy rounds still ship the packed buffer so the
        # program stays shape-uniform)
        args = partials(D, rows=5, h=2, d=16, contrib=masks[0])
        rounds = max(D - 1, 0).bit_length()
        c = jnp.asarray(np.asarray(masks[0]))
        txt_sub = str(jax.make_jaxpr(f_sub)(*args, c))
        txt_full = str(jax.make_jaxpr(f_full)(*args))
        assert txt_sub.count("ppermute") == rounds, D
        assert txt_full.count("ppermute") == 3 * rounds, D
        print(f"D={D}: {len(masks)} masks OK")
    print("POR_PROPERTY_OK")
""")


def test_por_subgroup_merge_property_subprocess(tmp_path):
    """Property: for EVERY ownership mask at axis sizes 1/2/4, the
    sparse subgroup merge matches the full POR butterfly — bitwise in
    max space, to FMA slot asymmetry in o/l, and bitwise-verbatim for
    single-contributor rows — with one packed ppermute per round vs the
    butterfly's three and a single compiled program per axis size."""
    script = tmp_path / "por_property.py"
    script.write_text(POR_PROPERTY)
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=1200)
    assert "POR_PROPERTY_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]
