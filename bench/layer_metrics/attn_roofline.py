"""Attention kernel: the least time of the attention work the steps
needed over the device time of the kernel's events.  The work of one
layer's call is counted from the forest of live rows (``bench/work.py``:
each distinct full KV page read once at the pool's dtype, plus queries
and outputs); its least time is the larger of FLOPs over peak and bytes
over bandwidth.  There is one kernel event per attention layer per
step."""


def read(ctx):
    tr, w = ctx["trace"], ctx["work"]
    if not tr["kernel_runs"] or not tr["kernel_s"] or not w:
        return None
    return 100.0 * w["attn_least_s"] * tr["kernel_runs"] / tr["kernel_s"]
