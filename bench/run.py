"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process holds the chip for the whole run: weights from the seed,
the engine as ``launch/serve.py`` builds it, the shared prefix (if any)
put in the prefix cache, warm-up by the cell's own traffic, then the
measured window of ``--seconds``.  After the window the served tokens of
a sample of finished requests are held to the plain reference.  With
``--trace 1`` the start of the window is traced and the per-layer
metrics are reported in place of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its
limit).  Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from bench import spec  # noqa: E402

CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")
TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")
DRAIN_S = 60.0     # after the window: wait this long at most for first tokens
TRACE_S = 10.0     # with --trace 1: the traced start of the window


class BenchError(Exception):
    pass


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def use_compile_cache() -> None:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache``, for
    every program, small ones included (the eager prefill's)."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r})"
                         f"; there is no CPU fallback")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def check_pool(eng, model) -> int:
    """Refuse a pool whose dtype or page size departs from what the
    configuration states (the pool was sized from it); returns the bytes
    of one pool element, which the attention work is counted in."""
    import jax.numpy as jnp
    want = jnp.dtype(model["engine"]["kv_pool_dtype"])
    if eng.pool.k.dtype != want or eng.pool.v.dtype != want:
        raise BenchError(f"the engine's KV pool is {eng.pool.k.dtype}, the "
                         f"configuration states {want}")
    from bench import harness
    if eng.pool.page_bytes != harness.page_bytes(model):
        raise BenchError(f"a pool page holds {eng.pool.page_bytes} B, the "
                         f"configuration's sizes give "
                         f"{harness.page_bytes(model)}")
    return int(eng.pool.k.dtype.itemsize)


def import_program() -> None:
    src = os.path.join(spec.ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the program is not in {src}")
    sys.path.insert(0, src)


def per_layer(bench, name: str, ctx) -> dict:
    out = {}
    for m in spec.metrics_of(bench, name, "per_layer"):
        v = spec.layer_metric(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def end_to_end(bench, name: str, values: dict) -> dict:
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec.metrics_of(bench, name, "end_to_end")}


def run(args) -> dict:
    from bench import check, harness, trace, weights
    from bench.traffic import ClosedLoopTraffic

    bench = spec.benchmark()
    cell = spec.workload(bench, args.workload)
    model = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    limit = spec.limits(cell["name"])["max_logit_gap"]["limit"]
    names = spec.kernels(model["engine"]["backend"])
    ref = spec.reference(model["reference"])

    use_compile_cache()
    import jax
    devs = find_chips(cell["chips"])
    dev = devs[0]
    peak = spec.peaks(dev.device_kind)
    import_program()
    from repro.models import transformer as T

    clock = harness.CompileClock()
    t_phase = [T_START]

    def phase(what: str) -> None:
        t = time.perf_counter()
        sec, n, hits = clock.snapshot()
        log(f"{what}: {t - t_phase[0]:.2f} s (so far {n - hits} programs "
            f"compiled, {hits} loaded, {sec:.1f} s)")
        t_phase[0] = t

    mcfg = harness.model_config(model)
    params = weights.make(model, args.seed)
    weights.check(params, jax.eval_shape(
        lambda k: T.init_params(mcfg, k), jax.random.PRNGKey(0)))
    wbytes = weights.nbytes(params)
    page = model["engine"]["page_size"]
    pages = harness.pool_pages(model, dev.memory_stats()["bytes_limit"],
                               wbytes)
    traffic = ClosedLoopTraffic(mix, args.seed, model["vocab_size"])
    need = traffic.worst_case_pages(page)
    log(f"{cell['name']}: weights {wbytes} B; pool {pages} pages of "
        f"{harness.page_bytes(model)} B; the mix needs at most {need}")
    if pages < need:
        raise BenchError(f"pool of {pages} pages is below the {need} the "
                         f"mix can hold at once")
    eng = harness.build_engine(model, mix, params, pages)
    kv_bytes = check_pool(eng, model)
    phase("weights and engine")
    harness.prime(eng, traffic.prime_prompts(mix["prime_chunk_tokens"]))
    phase("priming the shared prefix")
    loop = harness.ClosedLoop(eng, traffic, len(traffic.shared))
    loop.start()
    phase(f"{traffic.clients} first admissions")
    loop.run(steps=int(mix["warmup_steps"]))
    phase(f"{mix['warmup_steps']} warm-up steps")
    c_setup = clock.snapshot()
    sig0 = len(eng.bucket_signatures)

    # ---- the measured window ---------------------------------------- #
    pf0 = eng.stats["prefill_tokens"]
    w0 = harness.now()
    setup_s = w0 - T_START
    w1 = w0 + args.seconds
    traced = [None, None]
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # host: the bench.* spans
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        traced[0] = harness.now()
        stop_at = w0 + min(args.seconds, TRACE_S)

        def stop_trace():
            if traced[1] is None and harness.now() >= stop_at:
                traced[1] = harness.now()
                jax.profiler.stop_trace()
    loop.run(until=w1, on_step=stop_trace if args.trace else None)
    if args.trace and traced[1] is None:
        traced[1] = harness.now()
        jax.profiler.stop_trace()
    pf1 = eng.stats["prefill_tokens"]
    c_win = clock.snapshot()
    sig1 = len(eng.bucket_signatures)
    n_steps = sum(1 for s in loop.steps if w0 <= s[0] < w1)
    hm = harness.drain(loop, w0, w1, DRAIN_S)
    mem_peak = max(d.memory_stats()["peak_bytes_in_use"] for d in devs)
    adm = [r.admit_s for r in hm["sent"]]
    log(f"set-up {setup_s:.3f} s: {c_setup[1] - c_setup[2]} programs "
        f"compiled, {c_setup[2]} loaded from the persistent cache, "
        f"{c_setup[0]:.1f} s in all")
    log(f"window: {c_win[1] - c_win[2] - c_setup[1] + c_setup[2]} "
        f"programs compiled, {c_win[2] - c_setup[2]} loaded from the "
        f"persistent cache ({c_win[0] - c_setup[0]:.2f} s); fused-step "
        f"shapes {sig0} -> {sig1}; {n_steps} engine steps; "
        f"{len(adm)} admissions, {sum(adm):.2f} s in add_request; "
        f"{len(hm['sent'])} requests sent, {len(hm['finished'])} finished, "
        f"{hm['tokens']} tokens delivered, {hm['failed']} failed")

    # ---- correctness, with the engine's state freed -------------------- #
    sent_in_window = hm["sent"]
    del eng, loop.eng
    gc.collect()
    picks = check.sample(hm["finished"], args.seed)
    t_ref = harness.now()
    gap, n_tok = check.widest_gap(ref, params, model, picks)
    log(f"reference: {len(picks)} requests, {n_tok} served tokens, "
        f"{harness.now() - t_ref:.1f} s")
    correct = check.correct(len(picks), gap, limit, hm["failed"])

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": correct, "attempted": len(sent_in_window),
              "failed": hm["failed"]}
    if args.trace:
        events = trace.load(TRACE_DIR)
        hosts = [(s, s + d) for k, _, _, s, d in events if k == "host"]
        window = (min(s for s, _ in hosts), max(e for _, e in hosts))
        summ = trace.summarize(events, names["step_program"],
                               names["attention_ops"], window)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        dims = weights.dims(model)
        steps = harness.trace_steps(loop, *traced)
        ctx = {
            "trace": summ, "peak": peak, "dims": dims,
            "prompt_tokens": sum(len(r.prompt) for r in sent_in_window),
            "prefill_tokens": pf1 - pf0,
            "admit_s": [r.admit_s for r in sent_in_window],
            "work": harness.step_work(
                dims, steps, len(traffic.shared), page, kv_bytes,
                bool(model["tie_word_embeddings"]), peak),
        }
        result["metrics"] = per_layer(bench, cell["name"], ctx)
        device.update(busy_s=summ["busy_s"], window_s=summ["window_s"])
        result["device"] = device
        result["breakdown"] = {"device_ops": summ["device_ops"],
                               "idle_gaps": summ["idle_gaps"]}
        log(f"trace: {summ['step_runs']} step runs over "
            f"{summ['step_s']:.3f} s, {summ['kernel_runs']} kernel runs "
            f"over {summ['kernel_s']:.3f} s, busy {summ['busy_s']:.3f} of "
            f"{summ['window_s']:.3f} s; {len(steps)} host steps traced")
    else:
        values = {
            "tokens_per_s": hm["tokens"] / args.seconds,
            "ttft_p50_ms": 1e3 * harness.pctl(hm["ttft"], 0.5),
            "tpot_p50_ms": 1e3 * harness.pctl(hm["tpot"], 0.5),
            "setup_s": setup_s,
        }
        log(f"tokens/s delivered in the window {values['tokens_per_s']!r} "
            f"(not an end-to-end metric: it moves in whole admission "
            f"cycles, see PERF.md)")
        result["metrics"] = end_to_end(bench, cell["name"], values)
        result["device"] = device
    result["checks"] = {"max_logit_gap": {"value": gap, "limit": limit},
                        "failed_requests": {"value": hm["failed"],
                                            "limit": 0}}
    log(f"check max_logit_gap {gap!r} limit {limit!r} "
        f"({'pass' if gap <= limit else 'FAIL'})")
    log(f"check failed_requests {hm['failed']} limit 0")
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    try:
        result = run(args)
    except BenchError as e:
        log(f"FAIL: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
