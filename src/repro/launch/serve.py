"""Serving launcher: prefix-shared decode with the CoDec engine.

Generates a synthetic document-QA workload (shared document prefix +
per-request questions), serves it with the chosen attention backend, and
reports TPOT + prefix-cache + memory-pressure statistics.  ``--compare``
runs codec vs. the FlashDecoding baseline back-to-back (the paper's
Fig. 7 setup).  ``--max-pages`` sizes the paged KV pool — undersize it
and the engine preempts-and-recomputes instead of failing;
``--prefill-chunk`` (int or ``auto``) admits long prompts in chunks
interleaved with decode steps.

Usage::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --smoke --requests 4 --doc-len 256 --max-new 8 --compare

    # memory-pressure demo: tiny pool + chunked prefill
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --smoke --max-pages 24 --prefill-chunk 32
"""

from __future__ import annotations

import argparse
import os
import sys
import time

# <repo>/.jax_cache: a fixed path, since the path is part of a cache key
REPO_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache lives at ``<repo>/.jax_cache``.
    Called from entry points' ``main``, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.normpath(REPO_COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _chunk(v: str):
    if v in ("none", ""):
        return None
    return v if v == "auto" else int(v)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--smoke", action="store_true")
    from ..kernels import registry
    ap.add_argument("--backend", default="codec-pallas",
                    choices=registry.names())
    ap.add_argument("--compare", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--doc-len", type=int, default=256)
    ap.add_argument("--q-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-pages", type=int, default=8192,
                    help="KV pool size in pages; undersizing triggers "
                         "preempt-and-recompute instead of MemoryError")
    ap.add_argument("--prefill-chunk", type=_chunk, default=None,
                    help="prefill token budget per step: int, 'auto' "
                         "(cost-model-driven), or 'none' (whole prompt)")
    ap.add_argument("--reserve-pages", type=int, default=0,
                    help="admission low watermark: free pages kept back "
                         "for decode growth of the running batch")
    ap.add_argument("--max-running", type=int, default=None,
                    help="cap on concurrently admitted requests")
    ap.add_argument("--cascade", action="store_true",
                    help="cascade prefill (DESIGN.md §14): co-admit "
                         "waiting requests sharing forest paths, compute "
                         "shared uncached spans once per group and batch "
                         "the per-request suffix chunks into one dispatch")
    ap.add_argument("--fused", action="store_true",
                    help="fused single-dispatch decode step with async "
                         "dispatch (serving/step_fn.py); falls back to "
                         "the eager path for non-jit-safe backends")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative tree-decoding (DESIGN.md §10): "
                         "self-drafted token trees verified in one "
                         "multi-query dispatch; greedy-only, "
                         "single-device")
    ap.add_argument("--spec-depth", type=int, default=4,
                    help="max draft chain length per branch")
    ap.add_argument("--spec-branch", type=int, default=2,
                    help="max sibling draft branches at the leaf")
    ap.add_argument("--spec-nodes", type=int, default=6,
                    help="total draft nodes per request per step")
    ap.add_argument("--mesh", default="1x1",
                    help="DATAxMODEL decode mesh for SPMD sharded serving "
                         "(distributed/; implies --fused, needs a "
                         "shardable backend) over the devices present")
    ap.add_argument("--seq-split-pages", type=int, default=0,
                    help="placement quota: pages a node keeps on one "
                         "data shard before sequence-splitting to the "
                         "next (0 = split only when a shard fills)")
    ap.add_argument("--replicate", action="store_true",
                    help="replication-aware placement: copy hot short "
                         "prefix nodes onto every data shard when the "
                         "merge saving beats the extra read cost, so "
                         "their rows skip the cross-shard POR merge")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit the cost model's bandwidth/overhead "
                         "coefficients from measured sharded step times "
                         "(blocks each dispatch to time it)")
    ap.add_argument("--cache", action="store_true",
                    help="persistent cross-request prefix cache: finished "
                         "requests detach but their prefix KV stays "
                         "resident (serves a second wave over the same "
                         "document to show warm-cache admission)")
    ap.add_argument("--cache-ttl", type=int, default=None,
                    help="evict cached nodes untouched for this many "
                         "engine steps (implies --cache)")
    ap.add_argument("--cache-pages", type=int, default=None,
                    help="LRU cap on resident cached pages "
                         "(implies --cache)")
    ap.add_argument("--stream", action="store_true",
                    help="register per-request streaming callbacks and "
                         "report first-token latencies")
    ap.add_argument("--max-steps", type=int, default=0,
                    help="engine step budget (0 = max-new + slack)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request wall-clock deadline in seconds; "
                         "requests past it finish TIMED_OUT with their "
                         "KV released (docs/FAULTS.md)")
    ap.add_argument("--max-queue", type=float, default=None,
                    help="max seconds a request may sit WAITING before "
                         "it times out unadmitted")
    ap.add_argument("--check-every", type=int, default=0,
                    help="run the engine invariant self-check every N "
                         "steps (0 = only after recoveries)")
    ap.add_argument("--inject", default=None,
                    help="fault schedule, e.g. 'dispatch@3*2,"
                         "nan_logits@5:0,stall@8=0.01' or "
                         "'seed:7[:rate]' (serving/faults.py grammar)")
    ap.add_argument("--nan-guard", action="store_true",
                    help="per-row NaN/inf logit guard: poisoned rows "
                         "are quarantined as FAILED instead of "
                         "streaming garbage (single-device only)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable the metrics registry + tracing layer "
                         "(docs/OBSERVABILITY.md); on by default since "
                         "its measured TPOT overhead is <3%")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON (load in "
                         "Perfetto / chrome://tracing); with --compare "
                         "the backend name is suffixed")
    ap.add_argument("--metrics-out", default=None,
                    help="write the final metrics registry snapshot as "
                         "JSON (schema codec-metrics/1)")
    ap.add_argument("--profile-every", type=int, default=0,
                    help="block on every Nth fused step to split it into "
                         "dispatch/device/host phases (0 = never; "
                         "sampled steps only, async path untouched)")
    ap.add_argument("--report-every", type=int, default=0,
                    help="print a one-line metrics summary every N "
                         "engine steps (0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.cache_ttl is not None or args.cache_pages is not None:
        args.cache = True

    from repro.distributed.mesh import parse_mesh
    mesh_d, mesh_m = parse_mesh(args.mesh)
    use_compile_cache()

    import jax
    import numpy as np
    from repro.configs import get_config, smoke_config
    from repro.distributed.mesh import decode_mesh
    from repro.models import transformer as T
    from repro.serving.engine import DecodeEngine

    mesh = decode_mesh(mesh_d, mesh_m) if mesh_d * mesh_m > 1 else None
    if mesh is not None:
        args.fused = True                 # mesh serving is fused-only

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.encoder_layers:
        print("encoder-decoder archs are served via the decoder backbone "
              "only; use a decoder-only arch for the engine demo")
        return 1
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    doc = rng.integers(0, cfg.vocab_size, args.doc_len).tolist()
    prompts = [doc + rng.integers(0, cfg.vocab_size, args.q_len).tolist()
               for _ in range(args.requests)]
    max_steps = args.max_steps or 4 * args.max_new + 16

    spec = None
    if args.speculative:
        from repro.serving.speculation import SpecConfig
        spec = SpecConfig(depth=args.spec_depth, branch=args.spec_branch,
                          max_nodes=args.spec_nodes)

    cache_policy = None
    if args.cache:
        from repro.serving.cache import CachePolicy
        cache_policy = CachePolicy(ttl_steps=args.cache_ttl,
                                   max_pages=args.cache_pages)

    fault_plan = None
    if args.inject:
        from repro.serving.faults import FaultPlan
        fault_plan = FaultPlan.parse(args.inject)

    from repro.core import metrics as metrics_mod
    from repro.serving.telemetry import Telemetry

    def run(backend: str):
        telemetry = None if args.no_telemetry else Telemetry(
            profile_every=args.profile_every)
        eng = DecodeEngine(cfg, params, page_size=args.page_size,
                           num_pages=args.max_pages, backend=backend,
                           max_q=max(args.requests, 8), temperature=0.0,
                           prefill_chunk=args.prefill_chunk,
                           reserve_pages=args.reserve_pages,
                           max_running=args.max_running,
                           cascade=args.cascade,
                           fused=args.fused, mesh=mesh,
                           seq_split_pages=args.seq_split_pages,
                           replicate=args.replicate,
                           calibrate=args.calibrate,
                           speculative=spec, cache=cache_policy,
                           faults=fault_plan, nan_guard=args.nan_guard,
                           check_every=args.check_every,
                           telemetry=telemetry)
        first_tok = {}

        def on_token(rid, tok):
            first_tok.setdefault(rid, time.time())

        # periodic one-line metrics summary: reader-owned snapshot so
        # the per-interval deltas are exact regardless of other readers
        report_prev = [eng.publish_metrics().snapshot()
                       if telemetry is not None else None]

        def report(engine):
            if (args.report_every <= 0
                    or engine.stats["steps"] % args.report_every):
                return
            now = engine.publish_metrics().snapshot()
            d = metrics_mod.delta(now, report_prev[0])
            report_prev[0] = now
            line = (f"    [step {engine.stats['steps']}] "
                    f"+{d['tokens_generated']['value']:.0f} tok, "
                    f"run/wait {d['running']['value']:.0f}"
                    f"/{d['waiting']['value']:.0f}, "
                    f"pool {d['pool_occupancy']['value']:.0%}, "
                    f"step p50 "
                    f"{1000 * metrics_mod.hist_quantile(d['step_s'], 0.5):.1f} ms")
            if d["ttft_s"]["count"]:
                line += (f", ttft p50 {1000 * metrics_mod.hist_quantile(d['ttft_s'], 0.5):.0f} ms")
            if cache_policy is not None:
                line += f", cache hit {d['cache_hit_rate']['value']:.0%}"
            print(line)

        on_step = report if (telemetry is not None
                             and args.report_every > 0) else None
        t0 = time.time()
        for p in prompts:
            eng.add_request(p, max_new=args.max_new,
                            on_token=on_token if args.stream else None,
                            deadline_s=args.deadline,
                            max_queue_s=args.max_queue)
        t_prefill = time.time() - t0
        t0 = time.time()
        try:
            outs = eng.run(max_steps, on_step=on_step)
        except KeyboardInterrupt:
            # graceful shutdown: cancel everything in flight, release
            # all KV, verify nothing leaked, report what was running
            print("\ninterrupted — draining engine")
            summary = eng.shutdown()
            st = eng.stats
            n_done = sum(1 for q in eng.requests.values()
                         if q.state == "done")
            print(f"    shutdown: {summary['requests']} requests "
                  f"({n_done} done, {st['cancelled']} cancelled, "
                  f"{st['timed_out']} timed out, {st['failed']} failed), "
                  f"{summary['used_pages']} pages leaked, "
                  f"{st['faults_injected']} faults injected, "
                  f"{st['callback_errors']} callback errors")
            raise SystemExit(130)
        t_decode = time.time() - t0
        steps = eng.stats["steps"]
        io = eng.forest.codec_io_bytes(cfg.num_kv_heads, cfg.head_dim)
        io_flash = eng.forest.flash_io_bytes(cfg.num_kv_heads, cfg.head_dim)
        print(f"[{backend}] prefill {t_prefill:.2f}s "
              f"({eng.stats['prefill_tokens']} new tokens; prefix reuse "
              f"saved {sum(len(p) for p in prompts) - eng.stats['prefill_tokens']}), "
              f"decode {t_decode:.2f}s / {steps} steps "
              f"= TPOT {1000 * t_decode / max(steps, 1):.1f} ms, "
              f"plan {eng.stats['plan_time']:.3f}s "
              f"({eng.stats['replans']} replans)")
        print(f"    KV IO per step: codec {io / 1e6:.2f} MB vs "
              f"per-request {io_flash / 1e6:.2f} MB "
              f"({io_flash / max(io, 1):.1f}x reduction, "
              f"mean sharing degree {eng.forest.mean_sharing_degree():.1f})")
        st = eng.stats
        if eng.fused:
            print(f"    fused step: {st['fused_calls']} dispatches, "
                  f"{eng.fused_cache_size} compiles "
                  f"({len(eng.bucket_signatures)} shape buckets), "
                  f"{st['token_flushes']} token syncs, dispatch "
                  f"{st['decode_dispatch_time']:.3f}s / sync "
                  f"{st['decode_sync_time']:.3f}s")
        if eng.spec is not None:
            tok_total = sum(len(q.generated)
                            for q in eng.requests.values())
            print(f"    speculation: {st['spec_steps']} verify dispatches "
                  f"for {tok_total} committed tokens; "
                  f"{st['spec_accepted']}/{st['spec_proposed']} drafts "
                  f"accepted (+{st['spec_accepted'] / max(st['spec_steps'], 1):.2f} "
                  f"extra tokens/dispatch, "
                  f"{st['spec_draft_stalls']} page stalls)")
        peak = eng.pool.allocator.peak_used
        shard_occ = ""
        if mesh is not None:
            occ = eng.pool.shard_occupancy()
            shard_occ = (" | shard occupancy "
                         + "/".join(f"{o:.0%}" for o in occ))
            # max over per-window plans (a node split across shards
            # appears in every window's plan); last epoch's snapshot
            splits = max((sp.seq_splits
                          for sp in eng._sharded_plans.values()),
                         default=0)
            shard_occ += f", {splits} seq-split nodes (last plan)"
            last_sp = next(iter(eng._sharded_plans.values()), None)
            if last_sp is not None:
                ls = last_sp.stats()
                shard_occ += (f", {ls['replicated_nodes']} replicated "
                              f"nodes / {ls['merge_row_count']} merge "
                              f"rows (last plan)")
            if eng.cost_model.calibrated:
                hw = eng.cost_model.hw
                shard_occ += (f" | calibrated hw: hbm "
                              f"{hw.hbm_bw / 1e9:.0f} GB/s, ici "
                              f"{hw.ici_bw / 1e9:.1f} GB/s "
                              f"({st['calibrations']} fits)")
        print(f"    memory pressure: peak {peak}/{eng.pool.num_pages} pages "
              f"({100 * peak / eng.pool.num_pages:.0f}%), "
              f"{st['preempted']} preemptions, {st['reclaimed']} reclaims, "
              f"{st['recompute_tokens']} recomputed tokens, "
              f"{st['prefill_chunks']} prefill chunks{shard_occ}")
        if eng.injector is not None or args.nan_guard or args.deadline:
            ended = {s: sum(1 for q in eng.requests.values()
                            if q.state == s)
                     for s in ("done", "cancelled", "timed_out", "failed")}
            fired = (dict(eng.injector.fired)
                     if eng.injector is not None else {})
            print(f"    faults: {st['faults_injected']} injected "
                  f"{fired}, {st['dispatch_failures']} dispatch "
                  f"failures / {st['dispatch_recoveries']} recovered, "
                  f"{st['nan_rows']} NaN rows quarantined, "
                  f"{st['callback_errors']} callback errors, "
                  f"{st['invariant_checks']} self-checks | outcomes "
                  f"{ended}")
        if args.stream and first_tok:
            if telemetry is not None:
                # registry is the source of truth: TTFT measured from
                # add_request to the token landing host-side
                h = telemetry.metrics["ttft_s"]
                print(f"    streaming: first token after "
                      f"{1000 * h.min:.0f}–{1000 * h.max:.0f} ms "
                      f"(p50 {1000 * h.quantile(0.5):.0f} ms, "
                      f"{h.count} streams)")
            else:
                ttfts = sorted(1000 * (first_tok[r] - t0)
                               for r in first_tok)
                print(f"    streaming: first token after "
                      f"{ttfts[0]:.0f}–{ttfts[-1]:.0f} ms "
                      f"({len(first_tok)} streams)")
        if eng.cache is not None:
            # second wave: new questions over the same document served
            # by the SAME engine — admission hits the resident prefix
            warm = [doc + rng.integers(0, cfg.vocab_size,
                                       args.q_len).tolist()
                    for _ in range(args.requests)]
            t0w = time.time()
            for p in warm:
                eng.add_request(p, max_new=args.max_new)
            eng.run(max_steps)
            t_warm = time.time() - t0w
            cs = eng.cache.stats
            last = eng.step_stats[-1] if eng.step_stats else {}
            print(f"    prefix cache: warm wave {t_warm:.2f}s, hit rate "
                  f"{eng.cache.hit_rate:.0%} ({cs['hits']} hits / "
                  f"{cs['misses']} misses, {cs['hit_tokens']} of "
                  f"{cs['lookup_tokens']} prompt tokens cached), "
                  f"resident {last.get('cache_resident_pages', 0)} pages "
                  f"({last.get('cache_resident_bytes', 0) / 1e6:.1f} MB), "
                  f"{cs['evicted_nodes']} nodes / {cs['evicted_pages']} "
                  f"pages evicted")
        unfinished = [r for r, q in eng.requests.items()
                      if len(q.generated) < q.max_new and not q.finished]
        if unfinished:
            print(f"    ERROR: {len(unfinished)} requests unfinished "
                  f"within {max_steps} steps: {unfinished}")
        if telemetry is not None:
            snap = eng.publish_metrics().snapshot()
            print(f"    telemetry: {snap['requests_done']['value']:.0f} "
                  f"done, {snap['tokens_generated']['value']:.0f} tokens, "
                  f"tpot p50 "
                  f"{1000 * metrics_mod.hist_quantile(snap['tpot_s'], 0.5):.1f} ms, "
                  f"e2e p50 "
                  f"{1000 * metrics_mod.hist_quantile(snap['e2e_s'], 0.5):.0f} ms, "
                  f"{len(telemetry.trace_events())} trace events")
            suffix = f".{backend}" if args.compare else ""
            if args.trace_out:
                path = args.trace_out + suffix
                telemetry.export_trace(path)
                print(f"    trace -> {path}")
            if args.metrics_out:
                path = args.metrics_out + suffix
                eng.export_metrics(path)
                print(f"    metrics -> {path}")
        return outs, bool(unfinished)

    if args.compare:
        # flash (per-request baseline) is not shardable; on a mesh the
        # comparison pair is the two shardable codec backends instead
        other = "codec-xla" if mesh is not None else "flash"
        o1, short1 = run("codec-pallas")
        o2, short2 = run(other)
        match = o1 == o2
        print(f"outputs codec == {other}: {match}")
        return 0 if match and not (short1 or short2) else 1
    _, short = run(args.backend)
    return 1 if short else 0


if __name__ == "__main__":
    sys.exit(main())
