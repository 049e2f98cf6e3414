"""The comparison that decides ``correct``.

A sample of the requests the window finished, drawn from the seed and
always holding the one with most served tokens, is run through the
plain reference (the configuration's ``reference`` module) over its
prompt and served tokens.  Each served token is greedy, so it should be
the reference's best up to rounding: its gap is how far its reference
logit lies below the reference's largest at that position.  The widest
gap is held to the cell's limit (``bench/limits/<cell>.json``).

The control puts the reference itself, computed with float8 operands,
in the program's place: at each position of the same sequences, the
token that float8 puts first, and its gap in the float32 reference."""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

_SAMPLE_STREAM = 3
SAMPLE_REQUESTS = 4    # finished requests held to the reference per run


def sample(finished: Sequence, seed: int, k: int = SAMPLE_REQUESTS) -> List:
    """``k`` of the finished requests: the one with most served tokens,
    and the rest drawn from the seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (-len(finished[i].tokens), i))
    rest = order[1:]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed),
                                                        _SAMPLE_STREAM]))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)),
                      replace=False).tolist() if rest else []
    return [finished[order[0]]] + [finished[rest[i]] for i in sorted(pick)]


def _rows(prompt: List[int], served: List[int]) -> Tuple[List[int], List[int]]:
    """The sequence the reference reads, and the positions whose logits
    predict each served token."""
    seq = list(prompt) + list(served[:-1])
    return seq, list(range(len(prompt) - 1, len(seq)))


def gaps(ref, params, model: Dict[str, Any], prompt: List[int],
         served: List[int], control: bool = False) -> np.ndarray:
    """Per served position: the gap of the served token in the float32
    reference, or with ``control`` the gap of float8's first choice."""
    seq, rows = _rows(prompt, served)
    lg = ref.logits(params, model, seq, rows, "f32")
    pick = (ref.logits(params, model, seq, rows, "fp8").argmax(-1)
            if control else np.asarray(served))
    return lg.max(-1) - lg[np.arange(len(rows)), pick]


def widest_gap(ref, params, model: Dict[str, Any], reqs: Sequence,
               control: bool = False) -> Tuple[float, int]:
    """(widest gap over every served token of ``reqs``, tokens read)."""
    worst, n = 0.0, 0
    for r in reqs:
        g = gaps(ref, params, model, r.prompt, r.tokens, control)
        worst = max(worst, float(g.max()))
        n += len(g)
    return worst, n


def correct(checked: int, gap: float, limit: float, failed: int) -> bool:
    """The verdict: some requests were checked, the widest gap is within
    the limit, and every request sent in the window got its answer."""
    return checked > 0 and gap <= limit and failed == 0
