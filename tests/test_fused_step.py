"""Fused single-dispatch decode step (``serving/step_fn.py``).

Covers the three invariants the fused path must hold:

* **equivalence** — greedy token streams byte-identical to the eager
  per-layer path for every registered backend, across a bucket-boundary
  crossing (the batch grows past a power of two mid-decode);
* **compile-cache bound** — arrivals, completions, and an eviction must
  not recompile the fused step beyond the distinct shape buckets (no
  per-step recompiles);
* **single dispatch / async** — exactly one jitted call per decode
  step, with sampled tokens deferred on device between sync points.
"""

import jax
import pytest

from repro.configs import smoke_config
from repro.kernels import registry
from repro.models import transformer as T
from repro.serving.engine import PENDING_DEVICE, DecodeEngine

CFG = smoke_config("qwen2.5-14b")
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))
PAGE = 8
DOC = list(range(10, 10 + 32))


def _engine(backend, fused, **kw):
    kwargs = dict(page_size=PAGE, num_pages=256, max_q=8,
                  temperature=0.0, fused=fused)
    kwargs.update(kw)
    return DecodeEngine(CFG, PARAMS, backend=backend, **kwargs)


def _bucket_crossing_run(backend, fused):
    """2 requests decode, then arrivals push the batch to 3 and 5 rows:
    the fused row bucket crosses 2 -> 4 -> 8 mid-decode."""
    eng = _engine(backend, fused)
    rids = [eng.add_request(DOC + [100 + i], max_new=10) for i in range(2)]
    eng.step(); eng.step()
    rids.append(eng.add_request(DOC + [200], max_new=8))   # bucket 2 -> 4
    eng.step(); eng.step()
    rids.append(eng.add_request(DOC + [210], max_new=6))
    rids.append(eng.add_request(DOC + [220], max_new=6))   # bucket 4 -> 8
    eng.run(32)
    outs = {i: list(eng.requests[r].generated) for i, r in enumerate(rids)}
    assert all(outs[i] for i in outs)
    return outs, eng


@pytest.mark.parametrize("backend", registry.names())
def test_fused_matches_eager_across_bucket_boundary(backend):
    ref, _ = _bucket_crossing_run(backend, fused=False)
    got, eng = _bucket_crossing_run(backend, fused=True)
    assert got == ref, backend
    if eng.fused:    # ref backend falls back to eager
        assert eng.stats["fused_calls"] == eng.stats["steps"]
        assert eng.fused_cache_size <= len(eng.bucket_signatures)


def test_ref_backend_falls_back_to_eager():
    eng = _engine("ref", fused=True)
    assert not eng.fused            # not jit-safe -> eager fallback
    eng.add_request(DOC + [100], max_new=3)
    outs = eng.run(8)
    assert len(next(iter(outs.values()))) == 3
    assert eng.stats["fused_calls"] == 0


def test_fused_compile_cache_bounded_by_buckets():
    """Engine lifecycle sweep — arrivals, completions, an eviction —
    with the jit cache-miss count bounded by the bucket count."""
    eng = _engine("codec-xla", fused=True, num_pages=9,
                  prefill_chunk=PAGE)
    doc = list(range(10, 10 + 48))
    rids = [eng.add_request(doc + [100 + 3 * i + j for j in range(3)],
                            max_new=8) for i in range(2)]
    eng.step(); eng.step()
    rids += [eng.add_request(doc + [200 + 3 * i + j for j in range(3)],
                             max_new=6) for i in range(2)]  # mid-decode
    eng.run(80)
    assert all(len(eng.requests[r].generated)
               == eng.requests[r].max_new for r in rids)
    assert eng.stats["preempted"] >= 1                # eviction fired
    assert eng.stats["fused_calls"] == eng.stats["steps"]
    # the core regression: compiles are bounded by distinct buckets,
    # NOT by steps or plan rebuilds
    assert eng.fused_cache_size <= len(eng.bucket_signatures)
    assert eng.fused_cache_size < eng.stats["steps"]
    assert eng.stats["replans"] >= len(eng.bucket_signatures)


def test_fused_is_single_dispatch_and_async():
    """One jitted call per decode step; between sync points the sampled
    tokens stay on device (placeholders in ``generated``)."""
    eng = _engine("codec-xla", fused=True)
    rid = eng.add_request(DOC + [100], max_new=8)
    eng._attend = None      # eager-only helper must never be touched
    eng.step(); eng.step(); eng.step()
    req = eng.requests[rid]
    assert eng.stats["fused_calls"] == 3
    assert req.pending is PENDING_DEVICE
    assert any(t < 0 for t in req.generated)      # deferred placeholders
    flushes = eng.stats["token_flushes"]
    eng.flush_tokens()
    assert eng.stats["token_flushes"] == flushes + 1
    assert all(t >= 0 for t in req.generated)
    assert isinstance(req.pending, int)
    # dispatch vs compute accounting (satellite): both recorded
    assert eng.stats["decode_dispatch_time"] > 0
    assert eng.stats["decode_sync_time"] > 0
    assert any("dispatch_time" in s for s in eng.step_stats)


def test_eager_step_stats_report_dispatch_and_compute():
    eng = _engine("codec-xla", fused=False)
    eng.add_request(DOC + [100], max_new=2)
    eng.run(4)
    rows = [s for s in eng.step_stats if s.get("decoded")]
    assert rows and all("dispatch_time" in s and "compute_time" in s
                        for s in rows)
    assert eng.stats["decode_dispatch_time"] > 0
    assert eng.stats["decode_dispatch_time"] == pytest.approx(
        sum(s["dispatch_time"] for s in rows))


def test_fused_hybrid_mamba_matches_eager():
    """Batched per-request SSM state (gather/scatter at epoch
    boundaries) must not change hybrid-arch streams."""
    cfg = smoke_config("jamba-v0.1-52b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    doc = list(range(10, 42))
    prompts = [doc + [100 + i, 101 + i] for i in range(2)]
    outs = {}
    for fused in (False, True):
        eng = DecodeEngine(cfg, params, page_size=PAGE, num_pages=256,
                           backend="codec-xla", max_q=8, temperature=0.0,
                           fused=fused)
        for p in prompts:
            eng.add_request(p, max_new=4)
        outs[fused] = eng.run(8)
    assert outs[False] == outs[True]


def test_fused_sliding_window_matches_eager():
    """Per-window plans ride through the fused step (gemma3: 5 local : 1
    global layer pattern)."""
    cfg = smoke_config("gemma3-1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    doc = list(range(10, 74))
    prompts = [doc + [100 + i, 101 + i] for i in range(2)]
    outs = {}
    for fused in (False, True):
        eng = DecodeEngine(cfg, params, page_size=16, num_pages=256,
                           backend="codec-xla", max_q=8, temperature=0.0,
                           fused=fused)
        for p in prompts:
            eng.add_request(p, max_new=4)
        outs[fused] = eng.run(8)
    assert outs[False] == outs[True]


def test_fused_sampled_decoding_matches_eager():
    """temperature > 0: per-row ``fold_in`` sampling makes the draws
    independent of the fused bucket padding, so stochastic streams also
    match eager exactly (same seed, same split cadence)."""
    outs = {}
    for fused in (False, True):
        eng = _engine("codec-xla", fused=fused, temperature=0.8, seed=3)
        for i in range(3):
            eng.add_request(DOC + [100 + i], max_new=5)
        outs[fused] = eng.run(10)
    assert outs[False] == outs[True]


def test_fused_release_and_leak_free():
    eng = _engine("codec-xla", fused=True)
    rids = [eng.add_request(DOC + [100 + i], max_new=4) for i in range(2)]
    eng.run(16)
    for r in rids:
        eng.release(r)
    assert eng.pool.num_free == eng.pool.num_pages
    eng.pool.allocator.check()
    assert set(eng.forest.nodes) == {0}
