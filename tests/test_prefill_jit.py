"""The prefill program (``step_fn.make_prefill_fn``) against the eager
per-layer chunk it replaces, its compile bound, its donation, and the
rule that decides which engines run it.

    PYTHONPATH=src python -m pytest -q tests/test_prefill_jit.py
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import transformer as T
from repro.serving.engine import PREFILL, DecodeEngine

X = list(range(10, 26))               # 16 tokens: two pages of 8
Y = [200, 201, 202, 203, 204]
Z = [210, 211, 212, 213]


@functools.lru_cache(maxsize=None)
def _params(cfg):
    return T.init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    args = dict(page_size=8, num_pages=96, max_q=8, backend="codec-xla",
                cache=True)
    args.update(kw)
    return DecodeEngine(cfg, params, **args)


def _admit_all(eng, prompts):
    """Admit each prompt and finish its prefill, chunk by chunk, with no
    decode step; returns each request's sampled first token."""
    rids = []
    for p in prompts:
        rid = eng.add_request(p, max_new=4)
        while eng.requests[rid].state == PREFILL:
            eng._admit_phase()
        rids.append(rid)
    return [eng.requests[r].pending for r in rids]


def _record_chunks(eng):
    """Wrap the engine's program dispatch to log each chunk's span and
    the lengths of the forest nodes on its path."""
    chunks = []
    run = eng._prefill_program

    def logged(req, path, segments, span_start, end, sk):
        chunks.append((span_start, end, [n.length for n in path]))
        return run(req, path, segments, span_start, end, sk)

    eng._prefill_program = logged
    return chunks


W = list(range(30, 46))               # 16 more tokens


def _two_layers(arch, *mixers, **changes):
    """The arch's smoke config cut to one layer of each given mixer (the
    eager chunk's cost grows with layers)."""
    cfg = smoke_config(arch)
    pattern = tuple(next(k for k in cfg.layer_pattern if k.mixer == m)
                    for m in mixers)
    return dataclasses.replace(cfg, layer_pattern=pattern,
                               num_layers=len(pattern), **changes)


def _softcapped_local():
    # a windowed and a global layer, the window shorter than the prefix
    return _two_layers("gemma3-1b", "attn_local", "attn", sliding_window=8,
                       attn_logit_softcap=5.0)


# case -> (config, engine kwargs, prompts, what the chunks must show)
CASES = {
    "no_prefix": (
        lambda: smoke_config("qwen2.5-14b"), {}, [X + Y],
        lambda ch: ch == [(0, 21, [21])]),
    "two_nodes_partial_page": (
        lambda: smoke_config("qwen2.5-14b"), {}, [X, X + W, X + W],
        # the last chunk's cached prefix: two pages of one node, then a
        # full and a partial page (7 of 8 slots) of the next node (the
        # request's own node is empty)
        lambda ch: ch[-1] == (31, 32, [16, 16, 0])),
    "fully_cached": (
        lambda: smoke_config("qwen2.5-14b"), {}, [X + Y, X],
        # only the last position is recomputed
        lambda ch: ch[-1] == (15, 16, [16, 0])),
    "chunked_budget": (
        lambda: smoke_config("qwen2.5-14b"), {"prefill_chunk": 16},
        [X + W], lambda ch: ch == [(0, 16, [32]), (16, 32, [32])]),
    "local_window_softcap": (
        _softcapped_local, {}, [X + Y, X + Z],
        lambda ch: ch[-1] == (16, 20, [16, 4])),
    "moe": (
        lambda: smoke_config("llama4-scout-17b-a16e"), {}, [X + Y],
        lambda ch: ch == [(0, 21, [21])]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_matches_eager_chunk(case):
    """Same sampled tokens, same pool KV (within bf16 tolerance), same
    fill bookkeeping as the eager chunk, over every chunk shape the
    engine produces."""
    make_cfg, kw, prompts, shows = CASES[case]
    cfg = make_cfg()
    params = _params(cfg)
    jit = _engine(cfg, params, **kw)
    eager = _engine(cfg, params, **kw)
    eager._prefill_fn = None                 # the eager per-layer chunk
    chunks = _record_chunks(jit)
    got = _admit_all(jit, prompts)
    want = _admit_all(eager, prompts)
    assert shows(chunks), chunks
    assert got == want
    assert jit.stats["prefill_jit_chunks"] == len(chunks)
    assert jit.stats["prefill_eager_chunks"] == 0
    assert eager.stats["prefill_eager_chunks"] == len(chunks)
    for a, b in ((jit.pool.k, eager.pool.k), (jit.pool.v, eager.pool.v)):
        # the trash page holds padded rows' KV, which only the program
        # writes
        np.testing.assert_allclose(np.asarray(a)[:, :-1],
                                   np.asarray(b)[:, :-1],
                                   rtol=4e-3, atol=4e-3)
    filled = [{n.id: n.meta.get("filled") for n in e.forest.real_nodes()}
              for e in (jit, eager)]
    assert filled[0] == filled[1]
    for key in ("prefill_tokens", "prefill_chunks", "recompute_tokens"):
        assert jit.stats[key] == eager.stats[key], key
    jit.check()


def test_one_program_per_shape_bucket():
    """Many prompt lengths, with and without a cached prefix, build at
    most one program per ``(T_pad, P_pad)`` bucket; repeats build none."""
    cfg = smoke_config("qwen2.5-14b")
    params = _params(cfg)
    eng = _engine(cfg, params, num_pages=256)
    prompts = [[7] * n for n in range(1, 30, 2)]
    prompts += [X + [100 + i] * n for i, n in enumerate(range(1, 12))]
    _admit_all(eng, prompts)
    sigs = eng.prefill_signatures
    assert all(t & (t - 1) == 0 and p & (p - 1) == 0 for t, p in sigs)
    assert eng._prefill_fn._cache_size() == len(sigs)
    assert eng.stats["prefill_signatures"] == len(sigs)
    assert eng.stats["prefill_jit_chunks"] == len(prompts) > 2 * len(sigs)
    _admit_all(eng, [[8] * 13, X + [250] * 5])
    assert eng._prefill_fn._cache_size() == len(sigs)


def test_program_donates_the_pool():
    cfg = smoke_config("qwen2.5-14b")
    params = _params(cfg)
    eng = _engine(cfg, params)
    k0, v0 = eng.pool.k, eng.pool.v
    _admit_all(eng, [X + Y])
    assert k0.is_deleted() and v0.is_deleted()
    assert not eng.pool.k.is_deleted()


def _mesh_engine(cfg, params):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    return _engine(cfg, params, mesh=mesh, fused=True)


@pytest.mark.parametrize("stack,mesh,jitted", [
    ("attention", False, True),
    ("hybrid", False, False),
    ("attention", True, False),
])
def test_engagement_follows_the_stack_and_the_pool(stack, mesh, jitted):
    """The program serves attention stacks on the single-device pool; a
    recurrent (Mamba) stack and the mesh pool keep the eager chunk, and
    every engine's first token is the dense model's greedy token."""
    cfg = (smoke_config("qwen2.5-14b") if stack == "attention"
           else _two_layers("jamba-v0.1-52b", "mamba", "attn"))
    params = _params(cfg)
    eng = (_mesh_engine if mesh else _engine)(cfg, params)
    prompt = X + Y
    got = _admit_all(eng, [prompt])
    assert (eng._prefill_fn is not None) == jitted
    jit_n, eager_n = (eng.stats[k] for k in ("prefill_jit_chunks",
                                              "prefill_eager_chunks"))
    assert (jit_n, eager_n) == ((1, 0) if jitted else (0, 1))
    assert eng.stats["prefill_signatures"] == jit_n
    logits, _, _ = T.prefill(params, cfg, jnp.asarray(prompt)[None],
                             max_len=32)
    assert got[0] == int(jnp.argmax(logits[0]))
