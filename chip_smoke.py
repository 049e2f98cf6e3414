"""Smoke run of the serving engine on a TPU, at qwen3-4b's full width.

    python chip_smoke.py              # one chip: codec-pallas vs flash
    python chip_smoke.py --chips 4    # 2x2 (data x model) mesh vs one chip

Builds ``DecodeEngine`` the way ``python -m repro.launch.serve`` does
(fused decode step, page 16, greedy) over qwen3-4b's published config
with bf16 weights drawn from ``--seed``, and serves four requests that
share a 1024-token document, each with its own 32-token question, for
16 new tokens each.

* default: the compiled PAC kernel is first held to the python-loop
  oracle at these widths; then the requests are served with
  ``codec-pallas`` and with ``flash`` (the paper's per-request
  baseline).
* ``--chips 4``: the requests are served with ``codec-pallas`` on one
  chip and then on a 2x2 mesh (KV pages over ``data``, heads over
  ``model``).

Every emitted token is held to a dense reference forward of the same
weights (``transformer.forward``): it must be the reference's greedy
choice up to a bf16 tie (``TIE_ULPS``).  The two runs' streams are
compared and the first difference is printed; the model rounds its
logits to bf16, so two correct runs can part at a tie.

The run fails (non-zero exit, no result line) when JAX finds no TPU,
the kernel misses its oracle, a request is left unfinished, a token
misses the reference, or the fused step of a ``codec-pallas`` engine
holds no ``tpu_custom_call`` (the kernel would have been interpreted).
Wall times printed are smoke timings, not benchmark results.  The last
line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-4b"
PAGE = 16
REQUESTS, DOC_LEN, QUESTION_LEN, MAX_NEW = 4, 1024, 32, 16
MAX_Q = 8                      # query slots per plan task, as serve.py sets
MESH = (2, 2)                  # (data, model) for --chips 4
# device memory kept free of weights and KV pool: the temporaries of
# the compiled fused step and prefill program
HEADROOM_BYTES = 4 << 30
# An emitted greedy token passes when its logit in the dense reference
# forward is within TIE_ULPS bf16 ulps (at the maximum's magnitude) of
# the maximum.  The model rounds its logits to bf16, so exact ties are
# common, and correct attention implementations that sum in another
# order move a logit by an ulp or two and so break a tie the other way.
# With random weights attention averages nearly uniformly, so this
# catches gross faults only; the kernel phase holds PAC to its oracle.
TIE_ULPS = 4
# f32 attention partials, PAC against the python-loop oracle
KERNEL_TOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


class CompileClock:
    """Sums XLA backend compile time and counts compiles and cache hits
    (JAX's monitoring events), so each phase can report its own."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


def memory_line(dev) -> str:
    st = dev.memory_stats() or {}
    return (f"peak_bytes_in_use {st.get('peak_bytes_in_use')}, "
            f"bytes_in_use {st.get('bytes_in_use')}, "
            f"bytes_limit {st.get('bytes_limit')}")


def pool_pages(dev, cfg, weight_bytes: int, chips: int) -> int:
    """KV pool pages that fit beside the weights, less the headroom."""
    limit = dev.memory_stats()["bytes_limit"]
    # float32 K and V over every attention layer (the pool stays f32)
    page_bytes = 2 * cfg.num_layers * PAGE * cfg.num_kv_heads \
        * cfg.head_dim * 4
    free = limit - weight_bytes - HEADROOM_BYTES
    # pages shard over the mesh's data axis and heads over its model
    # axis, so each chip holds 1/chips of the pool's bytes
    pages = max(0, chips * free // page_bytes) // chips * chips
    need = REQUESTS * -(-(DOC_LEN + QUESTION_LEN + MAX_NEW) // PAGE)
    log(f"pool: {pages} pages of {page_bytes} B (K+V, f32, "
        f"{cfg.num_layers} layers) = bytes_limit {limit} - weights "
        f"{weight_bytes} - headroom {HEADROOM_BYTES}, x{chips} chip(s); "
        f"this workload needs at most {need} without prefix sharing")
    if pages < need:
        raise SmokeFailure(f"pool of {pages} pages is below the "
                           f"{need} the workload needs")
    return pages


def serve(label, cfg, params, prompts, backend, num_pages, dev, clock,
          mesh=None):
    """Serve the prompts on a fresh engine; returns the token streams."""
    from repro.serving.engine import DecodeEngine

    c0 = clock.snapshot()
    t0 = time.perf_counter()
    eng = DecodeEngine(cfg, params, page_size=PAGE, num_pages=num_pages,
                       backend=backend, max_q=MAX_Q, temperature=0.0,
                       fused=True, mesh=mesh)
    rids = [eng.add_request(p, max_new=MAX_NEW) for p in prompts]
    t1 = time.perf_counter()
    outs = eng.run(4 * MAX_NEW + 16)
    t2 = time.perf_counter()
    c1 = clock.snapshot()
    streams = [list(outs[r]) for r in rids]
    st = eng.stats
    log(f"[{label}] {sum(len(s) for s in streams)} tokens over "
        f"{len(rids)} requests; {st['steps']} engine steps, "
        f"{st['fused_calls']} fused dispatches, {eng.fused_cache_size} "
        f"fused-step compiles, {st['replans']} plan epochs, "
        f"{st['prefill_tokens']} prefill tokens")
    log(f"[{label}] compile: {c1[0] - c0[0]:.1f} s over "
        f"{c1[1] - c0[1]} XLA compiles, {c1[2] - c0[2]} cache hits")
    log(f"[{label}] smoke timing, not a benchmark: prefill "
        f"{t1 - t0:.1f} s, decode loop {t2 - t1:.1f} s (both include "
        f"compilation)")
    log(f"[{label}] memory: {memory_line(dev)}")
    short = [r for r, s in zip(rids, streams) if len(s) != MAX_NEW]
    if short:
        raise SmokeFailure(f"[{label}] requests {short} unfinished")
    if backend == "codec-pallas":
        text = eng.lower_fused_step().as_text()
        kernels = text.count("tpu_custom_call")
        log(f"[{label}] fused step holds {kernels} tpu_custom_call "
            f"(compiled PAC kernel)")
        if not kernels:
            raise SmokeFailure(f"[{label}] no tpu_custom_call in the "
                               f"fused step: PAC was interpreted")
    del eng
    gc.collect()
    log(f"[{label}] engine freed: {memory_line(dev)}")
    return streams


def check_streams(cfg, params, prompts, runs) -> None:
    """Hold every emitted token to the dense reference forward.

    ``runs`` maps a run's name to its token streams.  The reference is
    ``transformer.forward`` over prompt + stream (no paged pool, no
    plan, no Pallas): at each generated position the emitted token's
    logit must be within ``TIE_ULPS`` bf16 ulps of the reference's
    maximum.  Where
    two runs' streams differ, the first difference is printed; it can
    only pass at a reference near-tie, since both tokens were chosen
    from the same context.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T

    # row t holds the logits that predict generated token t
    fwd = jax.jit(lambda p, t: T.forward(p, cfg, t)[0][0, -MAX_NEW - 1:-1])
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    ref = {}
    for name, streams in runs.items():
        worst = (0.0, 0, 0, 0)
        for i, (prompt, st) in enumerate(zip(prompts, streams)):
            lg = np.asarray(fwd(params, jnp.asarray([prompt + st],
                                                    jnp.int32)))
            ref[name, i] = lg
            top = lg.max(-1)
            ulp = eps * 2.0 ** np.floor(np.log2(np.abs(top)))
            ulps = (top - lg[np.arange(len(st)), st]) / ulp
            t = int(np.argmax(ulps))
            if ulps[t] >= worst[0]:
                worst = (float(ulps[t]), i, t, int(lg[t].argmax()))
        short, i, t, best = worst
        log(f"[{name}] largest shortfall of an emitted token below the "
            f"reference forward's max logit: {short:g} bf16 ulps "
            f"(request {i}, token {t}; tolerance {TIE_ULPS})")
        if short > TIE_ULPS:
            raise SmokeFailure(
                f"[{name}] request {i} token {t} is {runs[name][i][t]}, "
                f"{short:g} ulps below the reference's greedy token {best}")
    (name_a, a), (name_b, b) = runs.items()
    diffs = [(i, next(t for t, (x, y) in enumerate(zip(sa, sb)) if x != y))
             for i, (sa, sb) in enumerate(zip(a, b)) if sa != sb]
    if not diffs:
        log(f"token streams identical: {name_a} == {name_b} "
            f"({sum(map(len, a))} tokens)")
    for i, t in diffs:
        top = np.sort(ref[name_a, i][t])[-2:]
        log(f"streams differ: request {i} first at token {t} "
            f"({name_a} {a[i][t]}, {name_b} {b[i][t]}); reference top-2 "
            f"gap there {top[1] - top[0]:.4f}; identical before it")


def check_kernel(cfg, seed) -> None:
    """Compiled PAC against the python-loop oracle (``ref``) on one
    random plan at the model's widths and this workload's shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import plan as plan_mod, tree as tree_mod
    from repro.core.cost_model import CostModel
    from repro.kernels import registry

    forest = tree_mod.two_level(REQUESTS, DOC_LEN, QUESTION_LEN + MAX_NEW,
                                block_size=PAGE)
    pages = plan_mod.assign_dense_pages(forest)
    cm = CostModel(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                   page_size=PAGE)
    plan = plan_mod.build_plan(forest, cm, num_lanes=2, max_q=MAX_Q)
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (pages, PAGE, cfg.num_kv_heads, cfg.head_dim)
    # bf16-valued K/V in the f32 pool, as the engine stores them
    k = jax.random.normal(kk, shape, jnp.bfloat16).astype(jnp.float32)
    v = jax.random.normal(kv, shape, jnp.bfloat16).astype(jnp.float32)
    q = jax.random.normal(kq, (REQUESTS, cfg.num_heads, cfg.head_dim),
                          jnp.bfloat16)
    got, want = ([np.asarray(x) for x in registry.get(name).partials(
        q, k, v, plan)] for name in ("codec-pallas", "ref"))
    # the output, and the log-sum-exp m + log l the POR merge weighs by
    err = max(np.abs(got[0] - want[0]).max(),
              np.abs(got[1] + np.log(got[2])
                     - want[1] - np.log(want[2])).max())
    log(f"kernel: compiled PAC vs ref oracle, {plan.num_tasks} tasks over "
        f"{pages} pages: max |difference| of o and of log-sum-exp "
        f"{err:.3e}")
    if not err < KERNEL_TOL:
        raise SmokeFailure(f"PAC differs from the oracle by {err:.3e}")


def weights(cfg, seed, sharding=None):
    import jax
    from repro.models import transformer as T

    t0 = time.perf_counter()
    # one compiled program for every placement, so a mesh run and a
    # one-chip run hold bitwise the same weights
    kw = {} if sharding is None else {"out_shardings": sharding}
    init = jax.jit(lambda k: T.init_params(cfg, k), **kw)
    params = jax.block_until_ready(init(jax.random.PRNGKey(seed)))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"weights: {n} parameters, {nbytes} B bf16 per chip, seed {seed} "
        f"({time.perf_counter() - t0:.1f} s)")
    return params, nbytes


def run(args) -> dict:
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"JAX found no TPU (platform "
                           f"{dev.platform!r}); there is no CPU fallback")
    if len(devs) < args.chips:
        raise SmokeFailure(f"--chips {args.chips} needs {args.chips} "
                           f"devices, JAX found {len(devs)}")
    log(f"device: {dev.device_kind}, {len(devs)} device(s), platform "
        f"{dev.platform}, jax {jax.__version__}")

    import numpy as np
    from repro.configs import get_config
    from repro.launch.serve import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()
    cfg = get_config(ARCH)
    rng = np.random.default_rng(args.seed)
    doc = rng.integers(0, cfg.vocab_size, DOC_LEN).tolist()
    prompts = [doc + rng.integers(0, cfg.vocab_size, QUESTION_LEN).tolist()
               for _ in range(REQUESTS)]
    log(f"model: {ARCH} ({cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads} q / {cfg.num_kv_heads} kv heads x "
        f"{cfg.head_dim}, vocab {cfg.vocab_size}); {REQUESTS} requests = "
        f"{DOC_LEN}-token shared document + {QUESTION_LEN}-token "
        f"question, {MAX_NEW} new tokens, greedy, page {PAGE}")

    if args.chips == 1:
        check_kernel(cfg, args.seed)
    params, wbytes = weights(cfg, args.seed)
    one_chip_pages = pool_pages(dev, cfg, wbytes, 1)
    single = serve("codec-pallas, 1 chip", cfg, params, prompts,
                   "codec-pallas", one_chip_pages, dev, clock)
    if args.chips == 1:
        other = serve("flash, 1 chip", cfg, params, prompts, "flash",
                      one_chip_pages, dev, clock)
        check_streams(cfg, params, prompts,
                      {"codec-pallas": single, "flash": other})
    else:
        from repro.distributed.mesh import decode_mesh

        del params
        gc.collect()
        mesh = decode_mesh(*MESH)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        params, wbytes = weights(cfg, args.seed, rep)
        label = f"codec-pallas, {MESH[0]}x{MESH[1]} mesh"
        meshed = serve(label, cfg, params, prompts, "codec-pallas",
                       pool_pages(dev, cfg, wbytes, args.chips), dev,
                       clock, mesh=mesh)
        check_streams(cfg, params, prompts,
                      {"1 chip": single, f"{MESH[0]}x{MESH[1]} mesh": meshed})
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(devs)}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: compare a 2x2 mesh against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        result = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
