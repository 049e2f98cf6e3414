"""Run one cell traced, as ``bench.run`` does, and report where the
engine's own spans put the time.

    python3 -m bench.span_report --workload <cell> --seed <n> \\
        --seconds <s> [--span-cost N]

``bench.run`` reads only the harness's ``bench.*`` spans from its trace,
and drops the trace and the engine before its readers run.  This entry
runs ``bench.run.run`` with ``--trace 1`` unchanged, and keeps, besides:
the engine's ``codec.*`` spans from the same trace (``bench.spans``),
``engine.stats`` at the two ends of the measured window, and the
compiled fused step's HLO text at the window's end (for the device
operations' scopes).  The last line of standard output is one JSON
object: the run's own result under ``run``, and under ``spans`` the
readings of ``bench.spans`` (``prefill_ms_per_request``,
``prefill_build_share``, ``prefill_device_share``, ``step_idle_ms``,
``idle_by_span``, ``device_by_scope``, the spans counted per step and
per admission), and under ``op_stats`` what the trace tells of a few
device operations.

``--span-cost N`` first times N empty ``telemetry.Span``s with the
profiler off and then on, and reports microseconds per span.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time
from unittest import mock

from bench import harness, spans, spec, trace
from bench import run as bench_run

COST_TRACE_DIR = os.path.join(spec.ROOT, ".span_cost_trace")


def span_cost(n: int) -> dict:
    """Microseconds per empty ``Span``, the profiler off and on."""
    import jax
    from repro.serving.telemetry import Span

    def per_span() -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with Span("codec.cost"):
                pass
        return 1e6 * (time.perf_counter() - t0) / n

    off = per_span()
    shutil.rmtree(COST_TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(COST_TRACE_DIR)
    on = per_span()
    jax.profiler.stop_trace()
    shutil.rmtree(COST_TRACE_DIR, ignore_errors=True)
    return {"spans": n, "off_us": off, "on_us": on}


def op_stats(trace_dir: str, n: int = 8) -> list:
    """The stats the trace gives the first ``n`` device operations, with
    their names: which of them, if any, carries the scope path."""
    from jax.profiler import ProfileData

    out = []
    for path in glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out += [[e.name, {k: str(v) for k, v in e.stats}]
                            for e in list(line.events)[:n]]
                    return out
    return out


def report(events, stats, hlo, step_program: str) -> dict:
    """``bench.spans``' readings of one traced run."""
    hosts = [(s, s + d) for k, _, _, s, d in events if k == "host"]
    window = (min(s for s, _ in hosts), max(e for _, e in hosts))
    dev = trace.devices(events)[0]
    return {
        "prefill_ms_per_request": spans.prefill_ms_per_request(*stats),
        "prefill_build_share": spans.prefill_build_share(*stats),
        "prefill_device_share": spans.prefill_device_share(events, dev,
                                                           window),
        "step_idle_ms": spans.step_idle_ms(events, dev, window),
        "window_idle_s": sum(
            e - s for s, e in spans.idle(events, dev, window)) / 1e9,
        "idle_by_span": spans.idle_by_span(events, dev, window),
        "device_by_scope": spans.device_by_scope(
            events, dev, step_program, spans.scope_map(hlo)),
        "counts": spans.span_counts(events),
        "stats_window": {k: stats[1][k] - stats[0][k] for k in
                         ("admitted", "prefill_s", "prefill_build_s",
                          "prefill_builds", "steps", "plan_time",
                          "decode_dispatch_time", "decode_sync_time")},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--span-cost", type=int, default=0)
    args = ap.parse_args()
    args.trace = 1
    kept: dict = {}
    load, loop_run = trace.load, harness.ClosedLoop.run

    def keep_spans(trace_dir):
        events = load(trace_dir)
        kept["events"] = events + spans.load(trace_dir)
        kept["op_stats"] = op_stats(trace_dir)
        return events

    def keep_window(self, until=float("inf"), **kw):
        if until == float("inf"):      # warm-up and drain steps
            return loop_run(self, until, **kw)
        before = dict(self.eng.stats)
        loop_run(self, until, **kw)
        kept["stats"] = (before, dict(self.eng.stats))
        kept["hlo"] = self.eng.lower_fused_step().compile().as_text()

    try:
        bench_run.use_compile_cache()
        bench_run.find_chips(1)
        bench_run.import_program()
        cost = span_cost(args.span_cost) if args.span_cost else None
        with mock.patch.object(trace, "load", keep_spans), \
                mock.patch.object(harness.ClosedLoop, "run", keep_window):
            result = bench_run.run(args)
    except bench_run.BenchError as e:
        bench_run.log(f"FAIL: {e}")
        return 1
    model = spec.config(spec.workload(spec.benchmark(),
                                      args.workload)["config"])
    step_program = spec.kernels(model["engine"]["backend"])["step_program"]
    out = {"run": result, "span_cost": cost,
           "spans": report(kept["events"], kept["stats"], kept["hlo"],
                           step_program),
           "op_stats": kept["op_stats"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
