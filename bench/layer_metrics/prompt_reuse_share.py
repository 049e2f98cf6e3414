"""Admission: share of the prompt tokens sent in the window that the
engine did not prefill (``stats["prefill_tokens"]``, a program counter),
because the prefix cache held them."""


def read(ctx):
    if not ctx["prompt_tokens"]:
        return None
    return 100.0 * (1.0 - ctx["prefill_tokens"] / ctx["prompt_tokens"])
