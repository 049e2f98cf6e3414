"""Operations and bytes the decode work needs, counted from shapes.

These are the numerators of ``decode_mfu`` and ``attn_roofline``.  They
depend only on the model's sizes and on the prefix forest of the live
rows, never on which attention backend ran, so a later change to the
kernel cannot change what they count."""

from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple


def matmul_params_per_token(n: Dict[str, int], tied_head: bool) -> int:
    """Weights one decoded token multiplies by: every layer's
    projections and MLP, and the head (the embedding row is a gather,
    not a product, and a tied head still multiplies)."""
    d, hq, hkv, hd, ff = n["d"], n["hq"], n["hkv"], n["hd"], n["ff"]
    attn = d * (hq + 2 * hkv) * hd + hq * hd * d
    mlp = 3 * d * ff
    return n["layers"] * (attn + mlp) + d * n["vocab"]


def step_flops(n: Dict[str, int], contexts: Sequence[int],
               tied_head: bool = True) -> int:
    """Model FLOPs of one decode step over live rows with these context
    lengths (tokens attended, the new one included): 2 per weight per
    row, plus 4 * heads * head_dim per attended token per attention
    layer (scores and the weighted sum of values)."""
    rows = len(contexts)
    dense = 2 * matmul_params_per_token(n, tied_head) * rows
    attn = 4 * n["hq"] * n["hd"] * sum(contexts) * n["layers"]
    return dense + attn


def forest_pages(nodes: Iterable[Tuple[int, int]], page: int
                 ) -> Tuple[int, int]:
    """``nodes``: (tokens, rows reading it) per forest node, each node
    starting on a page boundary.  Returns (distinct full pages, full-page
    tokens summed over the rows that read them).  A node's partial last
    page is the tail page, which the step attends outside the kernel."""
    pages = row_tokens = 0
    for tokens, rows in nodes:
        full = tokens // page
        pages += full
        row_tokens += full * page * rows
    return pages, row_tokens


def attention_work(n: Dict[str, int], nodes: List[Tuple[int, int]],
                   rows: int, page: int, kv_bytes: int,
                   q_bytes: int = 2) -> Tuple[int, int]:
    """(FLOPs, bytes) of one attention layer's kernel call over the
    forest: every distinct full page of K and V read once at the pool's
    dtype, the rows' queries read, and each row's float32 output with
    its two softmax statistics per head written."""
    pages, row_tokens = forest_pages(nodes, page)
    flops = 4 * n["hq"] * n["hd"] * row_tokens
    kv = 2 * pages * page * n["hkv"] * n["hd"] * kv_bytes
    qo = rows * n["hq"] * (n["hd"] * q_bytes + (n["hd"] + 2) * 4)
    return flops, kv + qo


def two_level(shared: int, privates: Sequence[int]) -> List[Tuple[int, int]]:
    """The forest of rows that share one prefix of ``shared`` tokens and
    each hold ``privates[r]`` tokens of their own."""
    nodes = [(shared, len(privates))] if shared and privates else []
    return nodes + [(p, 1) for p in privates]


def least_time(flops: float, nbytes: float, peak: Dict[str, float]
               ) -> Tuple[float, str]:
    """The roofline bound in seconds, and which of the two bounds it."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "bandwidth")
