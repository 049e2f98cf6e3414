"""Prefill: mean host time of ``add_request`` over the requests sent in
the window.  The call returns once the eager prefill has sampled the
first token, so this is the admission's whole stall of the decode
loop."""


def read(ctx):
    t = ctx["admit_s"]
    return 1e3 * sum(t) / len(t) if t else None
