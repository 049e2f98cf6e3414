"""Put the engine's own spans and scopes beside the device trace.

The engine runs every phase inside a ``jax.profiler.TraceAnnotation``
named ``codec.*`` (``repro.serving.telemetry.Span``: ``codec.step``,
``codec.admit``, ``codec.prefill`` and its ``.gather``/``.attn``/
``.kv_write``/``.sample`` children, ``codec.plan_build``,
``codec.flush``, ``codec.dispatch``, ``codec.stream``, ...), and its
fused step names the parts of its layer body with ``jax.named_scope``
(``codec.kv_write``, ``codec.pool_slice``, ``codec.attn``,
``codec.tail_page``, ``codec.por_merge``, ``codec.ffn``, ...).

``load`` reads those spans from a trace as events of kind ``span``, in
``bench.trace``'s form ``(kind, track, name, start_ns, dur_ns)``; the
functions below work on them together with ``bench.trace.load``'s
``op``, ``module`` and ``host`` events, so they can be checked on hand
events.  A device operation's scope comes from the compiled program's
HLO text (``scope_map``): the trace names the instruction, the HLO's
``op_name`` metadata holds its scope path.

The engine's spans come from one host thread, so they nest.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace

Event = trace.Event
SPAN_PREFIX = "codec."


def load(trace_dir: str) -> List[Event]:
    """The engine's ``codec.*`` host spans in the trace, as kind
    ``span``."""
    from jax.profiler import ProfileData

    out: List[Event] = []
    for path in glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    out += [("span", line.name, e.name, e.start_ns,
                             e.duration_ns) for e in line.events
                            if e.name.startswith(SPAN_PREFIX)]
    return out


def _intervals(events: Sequence[Event], kind: str,
               name: Optional[str] = None) -> List[Tuple[float, float, str]]:
    return [(s, s + d, n) for k, _, n, s, d in events
            if k == kind and (name is None or n == name)]


def _innermost(spans: List[Tuple[float, float, str]],
               points: List[float]) -> List[Optional[str]]:
    """For each of the sorted ``points``, the name of the innermost of
    the nested ``spans`` that covers it, or None."""
    spans = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    out: List[Optional[str]] = []
    stack: List[Tuple[float, float, str]] = []
    i = 0
    for p in points:
        while i < len(spans) and spans[i][0] <= p:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= p:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


def _busy(events: Sequence[Event], device: str,
          window: Tuple[float, float]) -> List[Tuple[float, float]]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in trace.union(
        (s, s + d) for k, t, _, s, d in events if k == "op" and t == device)
        if e > lo and s < hi]


def idle(events: Sequence[Event], device: str,
         window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """The intervals of ``window`` in which nothing ran on ``device``."""
    edges = ([window[0]] + [x for iv in _busy(events, device, window)
                            for x in iv] + [window[1]])
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _sorted_seconds(tot: Dict[str, float]) -> List[List]:
    return [[name, ns / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])]


def idle_by_span(events: Sequence[Event], device: str,
                 window: Tuple[float, float]) -> List[List]:
    """Idle time on ``device`` in ``window``, each gap put down to the
    innermost ``codec.*`` span covering its midpoint, else to the
    ``bench.*`` span covering it, else to ``none``.  Every gap is
    counted, so the seconds sum to the window's idle time.  Longest
    first, in seconds."""
    gaps = idle(events, device, window)
    mids = [(s + e) / 2 for s, e in gaps]
    engine = _innermost(_intervals(events, "span"), mids)
    harness = _innermost(_intervals(events, "host"), mids)
    tot: Dict[str, float] = defaultdict(float)
    for (s, e), a, b in zip(gaps, engine, harness):
        tot[a or b or "none"] += e - s
    return _sorted_seconds(tot)


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> innermost ``codec.*`` scope, from a compiled
    program's HLO text (the ``op_name`` metadata of each instruction)."""
    out: Dict[str, str] = {}
    for m in re.finditer(r'^\s*(?:ROOT )?%([\w.-]+) = .*?op_name="([^"]*)"',
                         hlo_text, re.M):
        scopes = re.findall(r"codec\.[\w.]+", m.group(2))
        if scopes:
            out[m.group(1)] = scopes[-1]
    return out


def _exclusive(ops: List[Tuple[float, float, str]]
               ) -> List[Tuple[float, float, str]]:
    """(start, own ns, name) of nested device operations: an operation
    that contains others (a loop over the layers) keeps only the time
    none of them covers."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    own = [e - s for s, e, _ in ops]
    stack: List[int] = []
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(ops[i][0], own[i], ops[i][2]) for i in range(len(ops))]


def device_by_scope(events: Sequence[Event], device: str,
                    module_prefix: str, scopes: Dict[str, str]
                    ) -> List[List]:
    """Device time of the operations that ran inside executions of the
    programs named ``module_prefix`` on ``device``, summed by their
    ``codec.*`` scope (``scope_map``; ``none`` outside any scope).  A
    loop's time counts only where none of its body's operations ran.
    Longest first, in seconds."""
    runs = sorted((s, s + d) for k, t, n, s, d in events
                  if k == "module" and t == device
                  and n.startswith(module_prefix))
    starts = [r[0] for r in runs]
    ops = []
    for k, t, n, s, d in events:
        if k != "op" or t != device:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s + d <= runs[i][1]:
            ops.append((s, s + d, trace.instruction(n)))
    tot: Dict[str, float] = defaultdict(float)
    for _, ns, name in _exclusive(ops):
        tot[scopes.get(name, "none")] += max(ns, 0.0)
    return _sorted_seconds(tot)


def prefill_device_share(events: Sequence[Event], device: str,
                         window: Tuple[float, float]) -> Optional[float]:
    """% of the host time inside ``codec.prefill`` spans (clipped to
    ``window``) in which some operation ran on ``device``."""
    busy = _busy(events, device, window)
    ends = [e for _, e in busy]
    inside = total = 0.0
    for s, e, _ in _intervals(events, "span", "codec.prefill"):
        s, e = max(s, window[0]), min(e, window[1])
        if e <= s:
            continue
        total += e - s
        i = bisect.bisect_right(ends, s)
        while i < len(busy) and busy[i][0] < e:
            inside += min(e, busy[i][1]) - max(s, busy[i][0])
            i += 1
    return 100.0 * inside / total if total else None


def step_idle_ms(events: Sequence[Event], device: str,
                 window: Tuple[float, float]) -> Optional[float]:
    """Device idle time whose gap midpoint lies in a ``codec.step`` span
    and in no ``codec.admit`` span, over the ``codec.step`` spans that
    overlap ``window``: the decode step's own stall, in ms a step."""
    steps = [sp for sp in _intervals(events, "span", "codec.step")
             if sp[1] > window[0] and sp[0] < window[1]]
    if not steps:
        return None
    gaps = idle(events, device, window)
    mids = [(s + e) / 2 for s, e in gaps]
    in_step = _innermost(steps, mids)
    in_admit = _innermost(_intervals(events, "span", "codec.admit"), mids)
    ns = sum(e - s for (s, e), a, b in zip(gaps, in_step, in_admit)
             if a is not None and b is None)
    return ns / 1e6 / len(steps)


def span_counts(events: Sequence[Event]) -> Dict[str, float]:
    """``codec.*`` spans per ``codec.admit`` (itself included) and per
    ``codec.step`` outside admissions (itself included)."""
    spans = sorted(_intervals(events, "span"),
                   key=lambda sp: (sp[0], -sp[1]))
    starts = [sp[0] for sp in spans]
    outer = {"codec.step": [], "codec.admit": []}
    for sp in spans:
        if sp[2] in outer:
            outer[sp[2]].append(sp)
    admits = sorted(outer["codec.admit"])

    def within(s, e):
        return (bisect.bisect_left(starts, e)
                - bisect.bisect_left(starts, s))

    per_admit = sum(within(s, e) for s, e, _ in admits)
    per_step = 0
    for s, e, _ in outer["codec.step"]:
        per_step += within(s, e) - sum(within(a, b) for a, b, _ in admits
                                       if a >= s and b <= e)
    n_s, n_a = len(outer["codec.step"]), len(admits)
    return {"steps": n_s, "admits": n_a,
            "spans_per_step": per_step / n_s if n_s else 0.0,
            "spans_per_admit": per_admit / n_a if n_a else 0.0}


def prefill_ms_per_request(stats0: Dict, stats1: Dict) -> Optional[float]:
    """Host ms inside ``codec.prefill`` spans per admission, between two
    readings of ``engine.stats``."""
    n = stats1.get("admitted", 0) - stats0.get("admitted", 0)
    if "prefill_s" not in stats1 or n <= 0:
        return None
    return 1e3 * (stats1["prefill_s"] - stats0.get("prefill_s", 0.0)) / n


def prefill_build_share(stats0: Dict, stats1: Dict) -> Optional[float]:
    """% of the host time inside ``codec.prefill`` spans that JAX spent
    building programs (tracing, lowering, compiling or loading from the
    persistent cache), between two readings of ``engine.stats``."""
    if "prefill_build_s" not in stats1:
        return None
    t = stats1["prefill_s"] - stats0.get("prefill_s", 0.0)
    b = stats1["prefill_build_s"] - stats0.get("prefill_build_s", 0.0)
    return 100.0 * b / t if t > 0 else None
