"""End-to-end driver: serve a small model with batched shared-prefix
requests through the CoDec decode engine (the paper's deployment kind).

Three question waves arrive against two shared documents (continuous
batching); CoDec combines the shared KV reads, the plan is reused
across steps, and the same run is repeated with the FlashDecoding
backend to verify identical outputs and show the IO gap.  A final run
deliberately undersizes the KV pool and enables chunked prefill: the
engine preempts-and-recomputes instead of failing, and still produces
byte-identical tokens.

    PYTHONPATH=src python examples/serve_docqa.py
"""

import time

import jax
import numpy as np

from repro.configs import smoke_config
from repro.models import transformer as T
from repro.serving.engine import DecodeEngine

ARCH = "qwen2.5-14b"          # GQA family (reduced smoke config on CPU)
cfg = smoke_config(ARCH)
params = T.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(0)

doc_a = rng.integers(0, cfg.vocab_size, 128).tolist()
doc_b = rng.integers(0, cfg.vocab_size, 96).tolist()


def questions(doc, n):
    return [doc + rng.integers(0, cfg.vocab_size, 6).tolist()
            for _ in range(n)]


# fixed workload, shared by both backend runs
WAVE1 = questions(doc_a, 3)
WAVE2 = questions(doc_b, 2)
WAVE3 = questions(doc_a, 2)


def run(backend: str, num_pages: int = 2048, mesh=None, **policy):
    eng = DecodeEngine(cfg, params, page_size=16, num_pages=num_pages,
                       backend=backend, max_q=16, temperature=0.0,
                       mesh=mesh, **policy)
    t0 = time.time()
    # wave 1: three questions on doc A
    for p in WAVE1:
        eng.add_request(p, max_new=12)
    for _ in range(3):
        eng.step()
    # wave 2 arrives mid-decode (continuous batching): doc B
    for p in WAVE2:
        eng.add_request(p, max_new=12)
    # wave 3: more questions on doc A — its KV is already cached
    for p in WAVE3:
        eng.add_request(p, max_new=12)
    eng.run(48)
    dt = time.time() - t0
    st = eng.stats
    print(f"[{backend}@{num_pages}p] {len(eng.requests)} requests, "
          f"{st['steps']} decode steps in {dt:.1f}s; "
          f"prefill computed {st['prefill_tokens']} tokens "
          f"(prompts total {3 * 134 + 2 * 102 + 2 * 134}); "
          f"{st['replans']} replans, plan time {st['plan_time']:.3f}s")
    io_c = eng.forest.codec_io_bytes(cfg.num_kv_heads, cfg.head_dim)
    io_f = eng.forest.flash_io_bytes(cfg.num_kv_heads, cfg.head_dim)
    print(f"    decode KV IO: {io_c / 1e3:.1f} KB/step vs "
          f"{io_f / 1e3:.1f} KB/step per-request "
          f"({io_f / io_c:.2f}x saved)")
    if st["preempted"] or st["reclaimed"] or st["prefill_chunks"]:
        print(f"    pressure: peak {eng.pool.allocator.peak_used}/"
              f"{eng.pool.num_pages} pages, {st['preempted']} preemptions, "
              f"{st['reclaimed']} reclaims, {st['recompute_tokens']} "
              f"recomputed tokens, {st['prefill_chunks']} prefill chunks")
    if mesh is not None:
        occ = "/".join(f"{o:.0%}" for o in eng.pool.shard_occupancy())
        print(f"    mesh {mesh.shape['data']}x{mesh.shape['model']}: "
              f"per-shard pool occupancy {occ}")
    return {r: req.generated for r, req in eng.requests.items()}


out_codec = run("codec-pallas")
out_hydra = run("hydragen")
out_flash = run("flash")
assert out_codec == out_flash == out_hydra, \
    "backends must produce identical tokens"
print("codec == hydragen == flash outputs: OK")

# memory pressure: a pool too small to hold all waves at once, plus
# chunked prefill — same tokens, via preemption + recompute
out_tight = run("codec-pallas", num_pages=13, prefill_chunk=32,
                reserve_pages=0)
assert out_tight == out_codec, \
    "preempt-and-recompute must not change the tokens"
print("undersized pool (preemption + chunked prefill) outputs: OK")

# SPMD sharded serving (distributed/): the whole decode step traced
# under shard_map over a (data, model) mesh.  In-process this demo gets
# whatever devices exist (a 1x1 mesh on a plain run — the full sharded
# code path, collectives degenerate); launch/serve.py --mesh DxM runs
# over the devices present (on the CPU, set
# XLA_FLAGS=--xla_force_host_platform_device_count=N to emulate N).
from repro.distributed import decode_mesh  # noqa: E402

mesh = decode_mesh(1, 1)
out_mesh = run("codec-xla", mesh=mesh, fused=True)
assert out_mesh == out_codec, \
    "sharded engine must reproduce the single-device tokens"
print("SPMD mesh engine outputs: OK")
