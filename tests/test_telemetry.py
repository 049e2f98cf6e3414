"""Telemetry layer (``core/metrics.py`` + ``serving/telemetry.py``).

DESIGN.md §13 contracts:

* metrics primitives — monotone counters, histogram quantiles against
  exact percentiles, non-destructive snapshots + reader-owned deltas;
* engine wiring — counters stay monotone across a replay, two readers
  polling at different cadences see consistent (never double-counted)
  cache deltas, the eager flush wait lands in ``flush_time`` instead
  of polluting a later step's dispatch split;
* tracing — exported Chrome trace JSON is well-formed, per-request
  spans nest without partial overlap, every finished request closes
  with a terminal instant, and a fake clock makes the timestamps
  deterministic;
* non-perturbation — token streams are byte-identical with telemetry
  on vs off across eager / fused / cached / speculative modes.
"""

import json

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import metrics as M
from repro.models import transformer as T
from repro.serving.cache import CachePolicy
from repro.serving.engine import DecodeEngine
from repro.serving.speculation import SpecConfig
from repro.serving.telemetry import (METRIC_CATALOG, MemoryTraceSink,
                                     Telemetry)

CFG = smoke_config("qwen2.5-14b")
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))
PAGE = 8
DOC = list(range(10, 10 + 24))
PATTERN = [5, 7, 11, 13]
REP_PROMPT = (PATTERN * 6)[:24]


# --------------------------------------------------------------------- #
# metrics primitives
# --------------------------------------------------------------------- #
def test_counter_monotone_and_gauge():
    reg = M.MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    reg.gauge("g").set(7)
    assert reg["g"].value == 7.0
    with pytest.raises(TypeError):
        reg.gauge("c")            # kind clash


def test_histogram_quantiles_vs_exact():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-4.0, sigma=1.0, size=2000)
    h = M.Histogram("h")
    for s in samples:
        h.observe(float(s))
    assert h.count == len(samples)
    assert np.isclose(h.sum, samples.sum())
    for q in (0.1, 0.5, 0.9, 0.99):
        exact = float(np.quantile(samples, q))
        est = h.quantile(q)
        # bucket growth is 1.25x: interpolation error bounded by one
        # bucket width
        assert exact / 1.25 <= est <= exact * 1.25, (q, exact, est)
    assert h.quantile(0.0) == pytest.approx(h.min)
    assert h.quantile(1.0) == pytest.approx(h.max)


def test_snapshot_delta_is_reader_owned():
    reg = M.MetricsRegistry()
    c = reg.counter("c")
    h = reg.histogram("h")
    c.inc(2)
    h.observe(0.5)
    snap_a = reg.snapshot()       # reader A
    snap_b = reg.snapshot()       # reader B, same instant
    c.inc(3)
    h.observe(1.0)
    now = reg.snapshot()
    da = M.delta(now, snap_a)
    db = M.delta(now, snap_b)
    assert da["c"]["value"] == db["c"]["value"] == 3
    assert da["h"]["count"] == 1
    # snapshots are non-destructive: taking one changed nothing
    assert reg["c"].value == 5
    assert M.hist_quantile(da["h"], 0.5) > 0.5 / 1.25


def test_hist_quantile_empty_and_bounds():
    h = M.Histogram("h")
    assert M.hist_quantile(h.snapshot(), 0.5) == 0.0
    with pytest.raises(ValueError):
        M.hist_quantile(h.snapshot(), 1.5)


# --------------------------------------------------------------------- #
# engine wiring
# --------------------------------------------------------------------- #
def _engine(telemetry=None, **kw):
    kwargs = dict(page_size=PAGE, num_pages=256, backend="codec-xla",
                  max_q=8, temperature=0.0, telemetry=telemetry)
    kwargs.update(kw)
    return DecodeEngine(CFG, PARAMS, **kwargs)


def _streams(eng, prompts, max_new=6):
    rids = [eng.add_request(list(p), max_new=max_new) for p in prompts]
    eng.run(100)
    return {i: list(eng.requests[r].generated)
            for i, r in enumerate(rids)}


def test_counters_monotone_across_replay():
    tm = Telemetry()
    eng = _engine(telemetry=tm, cache=CachePolicy())
    prev = eng.publish_metrics().snapshot()
    for wave in range(3):
        prompts = [DOC + [100 + 10 * wave + i] for i in range(2)]
        for p in prompts:
            eng.add_request(p, max_new=4)
        while eng.has_work():
            eng.step()
            now = eng.publish_metrics().snapshot()
            for name, s in now.items():
                if s["type"] == "counter":
                    assert s["value"] >= prev[name]["value"], name
                elif s["type"] == "histogram":
                    assert s["count"] >= prev[name]["count"], name
            prev = now
        eng.flush_tokens()
        eng._stream_ready()
        for r in list(eng.requests):
            eng.release(r)
    snap = eng.publish_metrics().snapshot()
    assert snap["requests_done"]["value"] == 6
    assert snap["ttft_s"]["count"] == 6
    assert snap["cache_hits"]["value"] > 0


def test_two_cache_readers_never_double_count():
    """serve.py-style interval reader + serve_replay-style per-step
    reader must both see the true cache-hit total (the old rolling
    ``step_stats`` snapshot double-counted on the second read)."""
    tm = Telemetry()
    eng = _engine(telemetry=tm, cache=CachePolicy())
    interval_prev = eng.publish_metrics().snapshot()
    step_prev = eng.publish_metrics().snapshot()
    interval_total = step_total = 0.0
    for wave in range(3):
        for i in range(2):
            eng.add_request(DOC + [50 + 10 * wave + i], max_new=3)
        k = 0
        while eng.has_work():
            eng.step()
            now = eng.publish_metrics().snapshot()      # per-step reader
            step_total += M.delta(now, step_prev)["cache_hits"]["value"]
            step_prev = now
            k += 1
            if k % 2 == 0:                              # interval reader
                now = eng.publish_metrics().snapshot()
                interval_total += M.delta(
                    now, interval_prev)["cache_hits"]["value"]
                interval_prev = now
        eng.flush_tokens()
        eng._stream_ready()
        for r in list(eng.requests):
            eng.release(r)
    final = eng.publish_metrics().snapshot()["cache_hits"]["value"]
    tail = M.delta(eng.publish_metrics().snapshot(),
                   interval_prev)["cache_hits"]["value"]
    assert step_total == final
    assert interval_total + tail == final
    assert final == eng.cache.stats["hits"]
    # the per-step step_stats view agrees with the registry total
    assert sum(s.get("cache_hits", 0) for s in eng.step_stats) == final


def test_flush_time_attribution():
    """Deferred token syncs land in their own ``flush_time`` key, never
    in the dispatch/compute split of whichever step ran the flush."""
    tm = Telemetry()
    eng = _engine(telemetry=tm, fused=True)
    for i in range(2):
        eng.add_request(DOC + [100 + i], max_new=6)
    eng.run(100)
    rows = [s for s in eng.step_stats if "flush_time" in s]
    assert rows, "no step recorded a flush"
    assert all(s["flush_time"] >= 0 for s in rows)
    assert all(s.get("dispatch_time", 0) >= 0 for s in eng.step_stats)
    # every sync the engine performed is accounted under flush_time
    # (step rows for in-step flushes; boundary flushes accumulate on
    # the engine total), and the registry saw one observation per sync
    assert sum(s["flush_time"] for s in rows) \
        <= eng.stats["decode_sync_time"] + 1e-9
    snap = tm.metrics.snapshot()
    assert snap["flush_s"]["count"] == eng.stats["token_flushes"]
    assert snap["flush_s"]["sum"] == pytest.approx(
        eng.stats["decode_sync_time"])


def test_profile_every_splits_step():
    tm = Telemetry(profile_every=2)
    eng = _engine(telemetry=tm, fused=True)
    for i in range(2):
        eng.add_request(DOC + [100 + i], max_new=8)
    eng.run(100)
    profiled = [s for s in eng.step_stats if s.get("profiled")]
    assert profiled, "profile_every=2 sampled no steps"
    for s in profiled:
        assert s["dispatch_time"] >= 0
        assert s["compute_time"] >= 0
    snap = tm.metrics.snapshot()
    assert snap["profile_device_s"]["count"] == len(profiled)
    # unsampled fused steps stay async: no compute split recorded
    unsampled = [s for s in eng.step_stats
                 if s.get("dispatch_time", 0) and not s.get("profiled")]
    assert all("compute_time" not in s for s in unsampled)


# --------------------------------------------------------------------- #
# tracing
# --------------------------------------------------------------------- #
def _check_trace_shape(events):
    assert events, "no trace events"
    for ev in events:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
    spans = {}
    for ev in events:
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            spans.setdefault((ev["pid"], ev["tid"]), []).append(
                (ev["ts"], ev["ts"] + ev["dur"], ev["name"]))
    for track, ss in spans.items():
        ss.sort()
        for (s0, e0, n0), (s1, e1, n1) in zip(ss, ss[1:]):
            assert not (s1 < e0 < e1), \
                f"{track}: {n1} partially overlaps {n0}"
    return spans


def test_trace_export_valid_chrome_json(tmp_path):
    tm = Telemetry()
    eng = _engine(telemetry=tm)
    _streams(eng, [DOC + [100], DOC + [101]], max_new=4)
    path = tmp_path / "trace.json"
    tm.export_trace(str(path))
    doc = json.loads(path.read_text())
    spans = _check_trace_shape(doc["traceEvents"])
    req_tracks = [t for (pid, t) in spans if pid == 2]
    assert len(req_tracks) == 2
    for (pid, tid), ss in spans.items():
        if pid != 2:
            continue
        names = [n for (_, _, n) in ss]
        assert "queued" in names and "prefill" in names \
            and "decode" in names
    # every request reached a terminal instant on its own track
    instants = {ev["tid"] for ev in doc["traceEvents"]
                if ev["ph"] == "i" and ev["pid"] == 2
                and ev["name"] == "done"}
    assert instants == set(req_tracks)


def test_fake_clock_trace_is_deterministic():
    def run():
        clock = lambda: float(clock.t)
        clock.t = 0.0
        tm = Telemetry(sink=MemoryTraceSink())
        eng = _engine(telemetry=tm, clock=clock)
        eng.add_request(DOC + [100], max_new=4)
        while eng.has_work():
            eng.step()
            clock.t += 1.0
        eng.flush_tokens()
        eng._stream_ready()
        eng._notify_done()
        return [(e["name"], e["ph"], e.get("ts"), e.get("dur"))
                for e in tm.trace_events()]

    a, b = run(), run()
    assert a == b
    # fake seconds, microsecond trace units: integral timestamps
    assert all(ts is None or ts == int(ts) for (_, _, ts, _) in a)


def test_queue_wait_on_fake_clock():
    clock = lambda: float(clock.t)
    clock.t = 0.0
    tm = Telemetry()
    # one slot: the second request must wait in the queue
    eng = _engine(telemetry=tm, clock=clock, max_running=1)
    eng.add_request(DOC + [100], max_new=3)
    eng.add_request(DOC + [101], max_new=3)
    while eng.has_work():
        eng.step()
        clock.t += 1.0
    eng.flush_tokens()
    eng._stream_ready()
    snap = tm.metrics.snapshot()
    assert snap["queue_wait_s"]["count"] == 2
    assert snap["queue_wait_s"]["min"] == 0.0    # first admitted at once
    assert snap["queue_wait_s"]["max"] >= 1.0    # second waited steps


# --------------------------------------------------------------------- #
# non-perturbation
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["eager", "fused", "cached", "spec"])
def test_streams_identical_with_telemetry_on_off(mode):
    kw = {}
    prompts = [DOC + [100], DOC + [101], DOC + [102]]
    if mode == "fused":
        kw["fused"] = True
    elif mode == "cached":
        kw["cache"] = CachePolicy()
    elif mode == "spec":
        kw["speculative"] = SpecConfig(depth=2, branch=2, max_nodes=3)
        prompts = [list(REP_PROMPT), REP_PROMPT + [9]]
    off = _streams(_engine(telemetry=None, **kw), prompts)
    on = _streams(_engine(telemetry=Telemetry(profile_every=3), **kw),
                  prompts)
    assert on == off
    assert all(off.values())


def test_metrics_export_schema(tmp_path):
    tm = Telemetry()
    eng = _engine(telemetry=tm)
    _streams(eng, [DOC + [100]], max_new=3)
    path = tmp_path / "metrics.json"
    eng.export_metrics(str(path), extra={"passes": {"cold": {}}})
    doc = json.loads(path.read_text())
    assert doc["schema"] == "codec-metrics/1"
    assert doc["passes"] == {"cold": {}}
    assert set(doc["metrics"]) >= set(METRIC_CATALOG)
    for name, (kind, _) in METRIC_CATALOG.items():
        assert doc["metrics"][name]["type"] == kind


# --------------------------------------------------------------------- #
# profiler-clock spans and program-build counters
# --------------------------------------------------------------------- #
def test_span_records_its_event_from_its_readings():
    import time

    from repro.serving.telemetry import Span

    # no Telemetry: the readings alone
    with Span("codec.test") as sp:
        pass
    assert sp.t1 >= sp.t0 and sp.seconds == sp.t1 - sp.t0
    # the engine's default clock: the event takes the very same readings
    tm = Telemetry(clock=time.perf_counter)
    with Span("codec.test", tm, "test", args={"a": 1}) as sp:
        sp.args["b"] = 2
    (ev,) = [e for e in tm.trace_events() if e["ph"] == "X"]
    assert (sp.c0, sp.c1) == (sp.t0, sp.t1)
    assert ev["name"] == "test" and ev["args"] == {"a": 1, "b": 2}
    # an injected clock: the event rides it, the counters do not
    clock = lambda: float(clock.t)         # noqa: E731
    clock.t = 5.0
    tm = Telemetry(clock=clock)
    with Span("codec.test", tm, "test", step=3) as sp:
        clock.t = 7.0
    assert (sp.c0, sp.c1) == (5.0, 7.0)
    assert sp.seconds < 1.0
    # a block that raises records no event
    with pytest.raises(RuntimeError):
        with Span("codec.test", tm, "boom"):
            raise RuntimeError
    assert [e["name"] for e in tm.trace_events() if e["ph"] == "X"] == \
        ["test"]


def test_fused_step_lowering_carries_its_named_scopes():
    eng = _engine(fused=True)
    eng.add_request(DOC + [100], max_new=3)
    eng.step()
    text = eng.lower_fused_step().as_text(debug_info=True)
    for scope in ("embed", "plan_advance", "kv_write", "pool_slice", "attn",
                  "tail_page", "por_merge", "ffn", "sample"):
        assert f"codec.{scope}" in text, scope


def test_prefill_counts_its_program_builds():
    """A cold prefill builds programs (the per-call ``lax.map`` in
    ``layers.mha`` at least); the counters see them, and builds outside
    a prefill span are not counted."""
    tm = Telemetry()
    eng = _engine(telemetry=tm)
    eng.add_request(DOC + [100], max_new=2)
    st = eng.stats
    assert st["prefill_builds"] > 0
    assert st["prefill_build_s"] > 0
    assert st["prefill_s"] > 0
    before = (st["prefill_builds"], st["prefill_build_s"])
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    assert (st["prefill_builds"], st["prefill_build_s"]) == before
    snap = eng.publish_metrics().snapshot()
    assert snap["prefill_builds"]["value"] == st["prefill_builds"]
    assert snap["prefill_s"]["value"] == pytest.approx(st["prefill_s"])
