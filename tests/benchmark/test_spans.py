"""Tests of ``bench/spans.py`` and ``bench/span_report.py``, on the CPU.

    PYTHONPATH=src python -m pytest -q tests/benchmark/test_spans.py

The engine's ``codec.*`` spans are read back from a real profiler trace
of a smoke engine; the reductions that need device operations (which a
CPU trace does not put on a device plane) are checked on hand events.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import pytest  # noqa: E402

from bench import span_report, spans  # noqa: E402

DEV = "/device:TPU:0"


def _nested(events):
    """Spans of one track either nest or are disjoint."""
    by_track = {}
    for k, t, n, s, d in events:
        by_track.setdefault(t, []).append((s, s + d, n))
    for ss in by_track.values():
        ss.sort(key=lambda sp: (sp[0], -sp[1]))
        stack = []
        for s, e, n in ss:
            while stack and stack[-1][1] <= s:
                stack.pop()
            assert not stack or e <= stack[-1][1], (n, stack[-1][2])
            stack.append((s, e, n))


def _parents(events):
    """Each span's innermost enclosing span on its track (or None)."""
    out = {}
    ss = sorted(((s, s + d, n) for k, t, n, s, d in events),
                key=lambda sp: (sp[0], -sp[1]))
    stack = []
    for s, e, n in ss:
        while stack and stack[-1][1] <= s:
            stack.pop()
        out.setdefault(n, set()).add(stack[-1][2] if stack else None)
        stack.append((s, e, n))
    return out


def test_engine_spans_on_the_profiler_clock(tmp_path):
    """A smoke engine under ``jax.profiler.trace``: every engine phase
    shows as a ``codec.*`` span of kind ``span``, properly nested."""
    import jax
    from repro.configs import smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import DecodeEngine

    cfg = smoke_config("qwen2.5-14b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, page_size=8, num_pages=128,
                       backend="codec-xla", max_q=8, fused=True)
    doc = list(range(10, 34))
    with jax.profiler.trace(str(tmp_path)):
        for i in range(2):
            eng.add_request(doc + [100 + i], max_new=4)
        eng.run(20)
    ev = spans.load(str(tmp_path))
    assert ev and all(k == "span" for k, *_ in ev)
    names = {n for _, _, n, _, _ in ev}
    assert {"codec.step", "codec.admit", "codec.prefill",
            "codec.prefill.gather", "codec.prefill.attn",
            "codec.prefill.kv_write", "codec.prefill.sample",
            "codec.plan_build", "codec.flush", "codec.dispatch",
            "codec.decode", "codec.stream"} <= names, names
    _nested(ev)
    up = _parents(ev)
    for child in ("gather", "attn", "kv_write", "sample"):
        assert up[f"codec.prefill.{child}"] == {"codec.prefill"}
    assert up["codec.prefill"] == {"codec.admit"}
    assert up["codec.dispatch"] == {"codec.decode"}
    assert up["codec.decode"] == {"codec.step"}
    assert up["codec.plan_build"] <= {"codec.decode"}
    assert sum(n == "codec.step" for _, _, n, _, _ in ev) == \
        len(eng.step_stats)


def _hand_events():
    # one device op per busy stretch; host: a harness step holding an
    # engine step (an admission with one prefill inside it), then a
    # harness admission holding an engine admission and its prefill
    return [
        ("op", DEV, "fusion.1", 0, 10),
        ("op", DEV, "fusion.2", 50, 10),
        ("op", DEV, "fusion.3", 120, 20),
        ("host", "python", "bench.step", 0, 100),
        ("span", "python", "codec.step", 0, 90),
        ("span", "python", "codec.admit", 20, 30),
        ("span", "python", "codec.prefill", 22, 26),
        ("span", "python", "codec.prefill.attn", 25, 10),
        ("host", "python", "bench.add_request", 100, 60),
        ("span", "python", "codec.admit", 105, 50),
        ("span", "python", "codec.prefill", 106, 48),
    ]


def test_idle_by_span_puts_each_gap_on_the_innermost_span():
    ev = _hand_events()
    win = (0, 200)
    # gaps: 10-50 (mid 30: codec.prefill.attn), 60-120 (mid 90:
    # bench.step, after codec.step ended), 140-200 (mid 170: none)
    got = spans.idle_by_span(ev, DEV, win)
    assert dict(got) == pytest.approx({"none": 60e-9, "bench.step": 60e-9,
                                       "codec.prefill.attn": 40e-9})
    assert [n for n, _ in got][-1] == "codec.prefill.attn"
    assert sum(s for _, s in got) == pytest.approx(
        sum(e - s for s, e in spans.idle(ev, DEV, win)) / 1e9)


def test_prefill_device_share_and_step_idle_on_hand_events():
    ev = _hand_events()
    win = (0, 200)
    # prefill spans 22-48 (device busy 0 of 26) and 106-154 (busy
    # 120-140: 20 of 48)
    assert spans.prefill_device_share(ev, DEV, win) == pytest.approx(
        100.0 * 20 / 74)
    # gaps with midpoints in codec.step but not codec.admit: none here
    # (10-50 is inside the admission)
    assert spans.step_idle_ms(ev, DEV, win) == 0.0
    ev2 = ev + [("span", "python", "codec.step", 200, 100),
                ("op", DEV, "g", 210, 10), ("op", DEV, "g", 250, 10)]
    # gaps 220-250 and 260-300 lie in the second step: 70 ns over the
    # 2 steps in the window
    assert spans.step_idle_ms(ev2, DEV, (0, 300)) == pytest.approx(
        70e-6 / 2)
    assert spans.step_idle_ms([e for e in ev if e[2] != "codec.step"],
                              DEV, win) is None
    assert spans.prefill_device_share(
        [e for e in ev if e[2] != "codec.prefill"], DEV, win) is None


def test_device_by_scope_counts_a_loop_only_outside_its_body():
    hlo = "\n".join([
        'ENTRY %main {',
        '  %while.2 = (f32[2]) while(%t), metadata={op_name="jit(step)/'
        'while"}',
        '  %fusion.7 = f32[2] fusion(%p), metadata={op_name="jit(step)/'
        'while/body/codec.attn/dot_general"}',
        '  %pac.4 = f32[2] custom-call(%p), metadata={op_name="jit(step)/'
        'while/body/codec.attn/pallas_call"}',
        '  ROOT %copy.1 = f32[2] copy(%p), metadata={op_name="jit(step)/'
        'while/body/codec.pool_slice/dynamic_slice"}',
        '}'])
    assert spans.scope_map(hlo) == {"fusion.7": "codec.attn",
                                    "pac.4": "codec.attn",
                                    "copy.1": "codec.pool_slice"}
    ev = [("module", DEV, "jit_step", 0, 100),
          ("module", DEV, "jit_other", 200, 10),
          ("op", DEV, "while.2", 0, 100),
          ("op", DEV, "%pac.4 = f32[2] custom-call(...)", 10, 30),
          ("op", DEV, "fusion.7", 40, 10),
          ("op", DEV, "copy.1", 60, 20),
          ("op", DEV, "copy.1", 200, 10)]       # another program's op
    got = spans.device_by_scope(ev, DEV, "jit_step", spans.scope_map(hlo))
    # the loop's own 40 ns, the kernel and a fusion in codec.attn, the
    # copy in codec.pool_slice; the other program's copy is left out
    assert dict(got) == pytest.approx({"codec.attn": 40e-9, "none": 40e-9,
                                       "codec.pool_slice": 20e-9})


def test_span_counts_per_step_and_admission():
    c = spans.span_counts(_hand_events())
    # step: itself (the admission's 3 spans count there, not here);
    # admissions: 3 spans (admit, prefill, attn) + 2 (admit, prefill)
    assert c == {"steps": 1, "admits": 2, "spans_per_step": 1.0,
                 "spans_per_admit": 2.5}


def test_prefill_counters_between_two_readings():
    s0 = {"admitted": 4, "prefill_s": 1.0, "prefill_build_s": 0.5}
    s1 = {"admitted": 6, "prefill_s": 3.0, "prefill_build_s": 1.5}
    assert spans.prefill_ms_per_request(s0, s1) == pytest.approx(1000.0)
    assert spans.prefill_build_share(s0, s1) == pytest.approx(50.0)
    # nothing admitted, or a program without the counters: nothing read
    assert spans.prefill_ms_per_request(s0, s0) is None
    assert spans.prefill_build_share(s0, s0) is None
    assert spans.prefill_ms_per_request({"admitted": 0},
                                        {"admitted": 3}) is None
    assert spans.prefill_build_share({}, {"admitted": 3}) is None


def test_report_reads_every_number_from_one_run():
    stats = ({"admitted": 0, "prefill_s": 0.0, "prefill_build_s": 0.0,
              "prefill_builds": 0, "steps": 0, "plan_time": 0.0,
              "decode_dispatch_time": 0.0, "decode_sync_time": 0.0},
             {"admitted": 2, "prefill_s": 0.2, "prefill_build_s": 0.1,
              "prefill_builds": 9, "steps": 5, "plan_time": 0.01,
              "decode_dispatch_time": 0.02, "decode_sync_time": 0.03})
    ev = _hand_events() + [("module", DEV, "jit_step", 0, 10)]
    r = span_report.report(ev, stats, "", "jit_step")
    assert r["prefill_ms_per_request"] == pytest.approx(100.0)
    assert r["prefill_build_share"] == pytest.approx(50.0)
    assert r["prefill_device_share"] is not None
    assert r["step_idle_ms"] == 0.0
    assert sum(s for _, s in r["idle_by_span"]) == pytest.approx(
        r["window_idle_s"])
    assert dict(r["device_by_scope"]) == pytest.approx({"none": 10e-9})
    assert r["stats_window"]["prefill_builds"] == 9
