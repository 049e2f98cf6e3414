"""Collective-traffic extraction from compiled (SPMD-partitioned) HLO.

``cost_analysis()`` gives FLOPs and HBM bytes but not collective bytes;
we parse the per-device HLO text, sum the shard-level result sizes of
every collective op, and convert to *link seconds* with the standard
ring-algorithm byte multipliers:

    all-reduce        2 (n-1)/n x s     (reduce-scatter + all-gather)
    all-gather          (n-1)/n x S_out
    reduce-scatter      (n-1)/n x S_in
    all-to-all          (n-1)/n x s
    collective-permute  1.0     x s

where n = replica-group size and s = per-device operand bytes.  The
roofline collective term is then ``sum(bytes_on_link) / link_bw`` —
per-device wire time, matching the `collective_bytes / (chips*link_bw)`
formulation (collective_bytes there being the all-chip total).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute")

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(", )


def _shape_bytes(type_str: str, result_half_only: bool = False) -> int:
    shapes = [s for s in _SHAPE_RE.findall(type_str)
              if s[0] in _DTYPE_BYTES]
    if result_half_only and len(shapes) > 1:
        # async '-start' ops carry (operands..., results...) tuples; only
        # the result half is traffic.
        shapes = shapes[len(shapes) // 2:]
    total = 0
    for dtype, dims in shapes:
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, total_devices: int) -> int:
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota form [num_groups, group_size]<=[...]
        return int(m.group(2))
    return total_devices


@dataclasses.dataclass
class CollectiveStats:
    count: Dict[str, int]
    result_bytes: Dict[str, float]   # per-device result-shard bytes
    link_bytes: Dict[str, float]     # ring-multiplier adjusted wire bytes

    @property
    def total_link_bytes(self) -> float:
        return sum(self.link_bytes.values())

    def link_seconds(self, link_bw: float) -> float:
        return self.total_link_bytes / link_bw


def collect_collectives(hlo_text: str, total_devices: int) -> CollectiveStats:
    count: Dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    result_bytes: Dict[str, float] = {op: 0.0 for op in COLLECTIVE_OPS}
    link_bytes: Dict[str, float] = {op: 0.0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        type_str, op, start = m.group(1), m.group(2), m.group(3)
        # '-done' ops don't match (no '('-following result type pattern);
        # async '-start' counted once here.
        s = _shape_bytes(type_str, result_half_only=bool(start))
        if s == 0:
            continue
        n = max(_group_size(line, total_devices), 1)
        if n == 1:
            continue  # degenerate group: no traffic
        frac = (n - 1) / n
        if op == "all-reduce":
            wire = 2.0 * frac * s
        elif op == "collective-permute":
            wire = float(s)
        else:  # all-gather (s = full out), reduce-scatter, all-to-all
            wire = frac * s
        count[op] += 1
        result_bytes[op] += s
        link_bytes[op] += wire
    return CollectiveStats(count, result_bytes, link_bytes)


def extract_cost(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0))}


def extract_memory(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    fields = ["argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"]
    return {f: float(getattr(ma, f, 0)) for f in fields}
