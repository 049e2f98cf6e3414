"""Decode step: device time of one execution of the fused step program,
the mean over its executions in the trace."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["step_runs"]:
        return None
    return 1e3 * tr["step_s"] / tr["step_runs"]
