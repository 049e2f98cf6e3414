"""Operations and bytes the decode work needs, counted from shapes.

These are the numerators of ``decode_mfu`` and ``attn_roofline``.  They
depend only on the model's sizes and on the prefix forest of the live
rows, never on which attention backend ran, so a later change to the
kernel cannot change what they count.  A whole step's FLOPs depend on
the layers a model has, and are its architecture module's
(``step_flops``); one attention kernel call's work is counted here, from
the heads the module gives (``attention_heads``)."""

from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple


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


def attention_work(heads: Dict[str, int], nodes: List[Tuple[int, int]],
                   rows: int, page: int, kv_bytes: int,
                   q_bytes: int = 2) -> Tuple[int, int]:
    """(FLOPs, bytes) of one attention layer's kernel call over the
    forest, ``heads`` being ``{"hq", "hkv", "hd"}``: every distinct full
    page of K and V read once at the pool's dtype, the rows' queries
    read, and each row's float32 output with its two softmax statistics
    per head written."""
    hq, hkv, hd = heads["hq"], heads["hkv"], heads["hd"]
    pages, row_tokens = forest_pages(nodes, page)
    flops = 4 * hq * hd * row_tokens
    kv = 2 * pages * page * hkv * hd * kv_bytes
    qo = rows * hq * (hd * q_bytes + (hd + 2) * 4)
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
