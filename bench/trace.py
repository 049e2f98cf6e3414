"""Reduce a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into flat
events; everything else works on those events, so it can be checked on
a small recorded excerpt (``bench/tests/data``).  An event is
``(kind, track, name, start_ns, dur_ns)``: ``kind`` is ``op`` (a device
operation), ``module`` (one execution of a compiled program on a
device) or ``host`` (one of the benchmark's own ``bench.*`` spans);
``track`` names the device, or the host thread.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, str, str, float, float]
HOST_PREFIX = "bench."


def load(trace_dir: str) -> List[Event]:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: List[Event] = []
    for path in paths:
        data = ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    kind = {"XLA Ops": "op",
                            "XLA Modules": "module"}.get(line.name)
                    if kind:
                        out += [(kind, plane.name, e.name, e.start_ns,
                                 e.duration_ns) for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    out += [("host", line.name, e.name, e.start_ns,
                             e.duration_ns) for e in line.events
                            if e.name.startswith(HOST_PREFIX)]
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def devices(events: Sequence[Event]) -> List[str]:
    return sorted({t for k, t, *_ in events if k in ("op", "module")})


def busy_ns(events: Sequence[Event], device: str,
            window: Tuple[float, float]) -> float:
    """Time in ``window`` in which some operation ran on ``device``."""
    iv = union((s, s + d) for k, t, _, s, d in events
               if k == "op" and t == device)
    return sum(e - s for s, e in _clip(iv, *window))


def module_runs(events: Sequence[Event], prefix: str) -> List[float]:
    """Durations (ns) of each execution of the programs whose name
    starts with ``prefix``, on every device."""
    return [d for k, _, name, s, d in events
            if k == "module" and name.startswith(prefix)]


def instruction(name: str) -> str:
    """The HLO instruction of a device op's trace name, which may carry
    the whole instruction: ``%pac.3 = (f32[...]) custom-call(...)`` is
    ``pac.3``."""
    return name.lstrip("%").split(" ")[0]


def stem(name: str) -> str:
    """The instruction without its ``.N`` suffix: ``pac.3`` is an
    instance of ``pac``."""
    return instruction(name).split(".")[0]


def op_time(events: Sequence[Event], names: Sequence[str]
            ) -> Tuple[int, float]:
    """(count, ns) of device operations that are instances of one of
    ``names``."""
    want = set(names)
    hits = [d for k, _, name, s, d in events
            if k == "op" and stem(name) in want]
    return len(hits), sum(hits)


def top_ops(events: Sequence[Event], n: int = 10) -> List[List]:
    """The device operations that took most time, in seconds, summed by
    HLO instruction over every device."""
    tot: Dict[str, float] = defaultdict(float)
    for k, _, name, s, d in events:
        if k == "op":
            tot[instruction(name)] += d
    return [[name, ns / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Sequence[Event], device: str,
              window: Tuple[float, float], n: int = 10) -> List[List]:
    """Idle time on ``device`` in ``window``, summed by what the host was
    doing: the ``bench.*`` span that covers the middle of each
    gap, or ``none``.  Longest first, in seconds."""
    busy = _clip(union((s, s + d) for k, t, _, s, d in events
                       if k == "op" and t == device), *window)
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    # the benchmark's host spans never overlap one another
    spans = sorted((s, s + d, name) for k, _, name, s, d in events
                   if k == "host")
    starts = [sp[0] for sp in spans]
    tot: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid) - 1
        label = spans[i][2] if i >= 0 and spans[i][1] > mid else "none"
        tot[label] += e - s
    return [[name, ns / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def summarize(events: Sequence[Event], step_prefix: str,
              kernel_names: Sequence[str],
              window: Tuple[float, float]) -> Dict:
    """Everything the per-layer readers and the result's ``device`` and
    ``breakdown`` take from the trace, over ``window`` (ns)."""
    devs = devices(events)
    if not devs:
        raise ValueError("the trace holds no device operation")
    busy = [busy_ns(events, dev, window) for dev in devs]
    steps = module_runs(events, step_prefix)
    k_count, k_ns = op_time(events, kernel_names)
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "step_runs": len(steps),
        "step_s": sum(steps) / 1e9,
        "kernel_runs": k_count,
        "kernel_s": k_ns / 1e9,
        "device_ops": top_ops(events),
        "idle_gaps": idle_gaps(events, devs[0], window),
    }
