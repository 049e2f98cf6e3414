"""Tests of the benchmark's architecture modules (``bench/architectures``),
on the CPU.

    PYTHONPATH=src python -m pytest -q tests/benchmark/test_architectures.py

The dense GQA module reproduces, exactly, the weights and counts that
``bench/`` computed before each configuration named its architecture
(``data/dense_gqa_pinned.json``).  An unknown or missing architecture is
refused.  A test-only hybrid Mamba-2 / MoE module (``data/hybrid_smoke``)
is carried through the harness with no edit to ``bench/``: its weights
match the engine's layout, the pool is sized from its attention layers
and recurrent state, and the engine serves a closed loop on it.
"""

from __future__ import annotations

import copy
import hashlib
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import harness, spec, weights, work  # noqa: E402
from bench.traffic import ClosedLoopTraffic  # noqa: E402
from test_bench import weight_bytes  # noqa: E402

DATA = os.path.join(HERE, "data")
with open(os.path.join(DATA, "dense_gqa_pinned.json")) as _f:
    PINNED = json.load(_f)
CONFIGS = [c["name"] for c in spec.benchmark()["configs"]]


def _digest(params):
    """sha256 of each leaf's shape, dtype and bytes, by tree path."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h = hashlib.sha256(str((a.shape, str(a.dtype))).encode()
                           + a.tobytes())
        out[jax.tree_util.keystr(path)] = h.hexdigest()
    return out


def _counts(model, wbytes, limit):
    """What ``dense_gqa_pinned.json`` holds of one configuration."""
    arch = spec.architecture_of(model)
    heads = arch.attention_heads(model)
    return {
        "dims": arch.dims(model),
        "page_bytes": harness.page_bytes(model),
        "weight_bytes": wbytes,
        "bytes_limit": limit,
        "pool_pages": harness.pool_pages(model, spec.traffic("docqa-4k"),
                                         limit, wbytes),
        "step_flops": [arch.step_flops(model, c)
                       for c in PINNED["contexts"]],
        "attention_work": [
            list(work.attention_work(heads, work.two_level(s, p), len(p),
                                     PINNED["page"], PINNED["kv_bytes"]))
            for s, p in PINNED["forests"]],
    }


# ------------------------------------------------------------ dense GQA
@pytest.mark.parametrize("config", sorted(PINNED["configs"]))
def test_dense_gqa_smoke_weights_and_counts_are_pinned(config):
    """At smoke size: bit-identical weights from the seed, and the same
    page bytes, pool pages, step FLOPs and attention work."""
    pin = PINNED["configs"][config]["smoke"]
    full = spec.config(config)
    m = spec.architecture_of(full).smoke(full)
    params = weights.make(m, PINNED["seed"])
    assert _digest(params) == pin["weights"]
    got = _counts(m, weights.nbytes(params), pin["bytes_limit"])
    assert got == {k: pin[k] for k in got}


@pytest.mark.parametrize("config", sorted(PINNED["configs"]))
def test_dense_gqa_published_size_counts_are_pinned(config):
    """At the published widths: the same weight bytes, page bytes, pool
    pages at v5e's bytes_limit, step FLOPs and attention work."""
    pin = PINNED["configs"][config]["full"]
    m = spec.config(config)
    got = _counts(m, weight_bytes(m), pin["bytes_limit"])
    assert got == {k: pin[k] for k in got}


@pytest.mark.parametrize("config", CONFIGS)
def test_every_configuration_names_an_architecture(config):
    arch = spec.architecture_of(spec.config(config))
    assert all(callable(getattr(arch, f)) for f in spec.ARCHITECTURE_API)


def test_unknown_architecture_is_refused(tmp_path, monkeypatch):
    for name in ("no_such_architecture", "../spec", ""):
        with pytest.raises(KeyError):
            spec.architecture(name)
    # a module that lacks part of the interface is refused too
    (tmp_path / "partial.py").write_text("def dims(model):\n    return {}\n")
    monkeypatch.setattr(spec, "ARCHITECTURES", str(tmp_path))
    with pytest.raises(KeyError, match="lacks"):
        spec.architecture("partial")


def test_configuration_without_architecture_is_refused():
    m = spec.config(CONFIGS[0])
    del m["architecture"]
    for use in (spec.architecture_of, harness.page_bytes,
                lambda x: weights.make(x, 1)):
        with pytest.raises(KeyError, match="architecture"):
            use(m)


# ------------------------------------------------- test-only hybrid
SEED = 2**31 + 7


def _hybrid():
    with open(os.path.join(DATA, "hybrid_smoke.json")) as f:
        return json.load(f)


def _hybrid_mix():
    """docqa-4k at smoke size: 4 clients under a 64-token shared prefix,
    primed in two chunks."""
    x = copy.deepcopy(spec.traffic("docqa-4k"))
    x.update(clients=4, shared_prefix_tokens=64, prime_chunk_tokens=32,
             private_prompt_tokens=[8, 16],
             answer_tokens={"min": 3, "max": 9})
    return x


@pytest.fixture
def hybrid(monkeypatch):
    """The hybrid configuration, its module found among the test data."""
    monkeypatch.setattr(spec, "ARCHITECTURES", DATA)
    m = _hybrid()
    return m, spec.architecture_of(m)


@pytest.fixture(scope="module")
def hybrid_run():
    """The engine built by the harness on the hybrid's weights, the
    shared prefix primed, then 10 steps of the closed loop."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spec, "ARCHITECTURES", DATA)
        m, mix = _hybrid(), _hybrid_mix()
        params = weights.make(m, SEED)
        pages = harness.pool_pages(m, mix, 64 << 20, weights.nbytes(params))
        eng = harness.build_engine(m, mix, params, pages)
        traffic = ClosedLoopTraffic(mix, SEED, m["vocab_size"])
        harness.prime(eng, traffic.prime_prompts(mix["prime_chunk_tokens"]))
        loop = harness.ClosedLoop(eng, traffic, len(traffic.shared))
        loop.start()
        loop.run(steps=10)
        return eng, loop


def test_hybrid_weights_match_the_engine_layout(hybrid):
    import jax
    from repro.models import transformer as T
    m, arch = hybrid
    cfg = arch.model_config(m)
    assert [k.mixer for k in cfg.layer_pattern] == ["mamba"] * 3 + ["attn"]
    assert {k.ffn for k in cfg.layer_pattern} == {"moe"}
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (4, 2)
    assert cfg.capacity_factor == 0.0
    weights.check(weights.make(m, SEED), jax.eval_shape(
        lambda k: T.init_params(cfg, k), jax.random.PRNGKey(0)))


def test_hybrid_page_holds_attention_layers_only(hybrid):
    m, arch = hybrid
    assert arch.attention_layers(m) == 1
    # K and V of 1 layer: page 16 x 2 kv heads x 16 x float32
    assert harness.page_bytes(m) == 2 * 1 * 16 * 2 * 16 * 4
    dense_like = dict(m, layer_types=["attention"] * 4)
    assert harness.page_bytes(dense_like) == 4 * harness.page_bytes(m)


def test_hybrid_pool_leaves_room_for_recurrent_state(hybrid):
    m, arch = hybrid
    mix, limit, wbytes = _hybrid_mix(), 64 << 20, 1 << 20
    rows = harness.max_rows(m, mix)
    assert rows == 8                      # min_max_q over 4 clients
    # per row and Mamba layer: conv tail 3 x (128 + 2*16) and SSM state
    # 8 heads x 16 x 16, float32; held twice (carry and per request)
    per_row = 4 * (3 * (128 + 32) + 8 * 16 * 16)
    state = arch.state_bytes(m, rows)
    assert state == 2 * rows * 3 * per_row
    page = harness.page_bytes(m)
    assert harness.pool_pages(m, mix, limit, wbytes) == (
        (limit - wbytes - state) // page)


def test_hybrid_engine_serves_a_closed_loop(hybrid_run):
    eng, loop = hybrid_run
    assert len(loop.steps) == 10
    assert loop.delivered and all(r.tokens for r in loop.reqs if r.done)
    assert eng.stats["prefill_tokens"] > 0


def test_hybrid_step_work_counts_active_experts_only(hybrid, hybrid_run):
    m, arch = hybrid
    _, loop = hybrid_run
    peak = spec.peaks("TPU v5 lite")
    w = harness.step_work(m, loop.steps, 64, 16, 4, peak)
    assert math.isfinite(w["step_flops"]) and w["step_flops"] > 0
    assert math.isfinite(w["attn_least_s"]) and w["attn_least_s"] > 0
    # twice the experts at the same top-k: only the router grows, by
    # 2 FLOPs per weight (width 64 x 4 more experts) per row and layer
    more = dict(m, num_local_experts=8)
    w2 = harness.step_work(more, loop.steps, 64, 16, 4, peak)
    rows = np.mean([len(ctx) for _, ctx, _ in loop.steps])
    assert w2["step_flops"] - w["step_flops"] == pytest.approx(
        2 * 64 * 4 * 4 * rows, rel=1e-9)
    # every expert active would count 2 more experts' SwiGLU per layer
    every = dict(m, num_experts_per_tok=4)
    assert arch.step_flops(every, [10]) - arch.step_flops(m, [10]) == (
        2 * 4 * 2 * 3 * 64 * 32)
