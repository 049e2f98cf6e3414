# Decode-attention kernels + the pluggable backend registry.
#
# ``registry`` is the public resolution point: string name -> backend
# (codec-pallas / codec-xla / flash / hydragen / ref).  ``pac``/``por``
# are the Pallas TPU kernels, ``ops`` the jit'd wrappers + XLA fallback,
# ``hydragen`` the batched shared-prefix backend, ``ref`` the oracles.


def pallas_interpret() -> bool:
    """Whether the Pallas kernels run in the interpreter.

    The one place this is decided: on the CPU backend (the test suite)
    they are interpreted, on a TPU they are compiled by Mosaic, and any
    other backend has no way to run them.  A kernel entry point called
    with ``interpret=None`` asks here.
    """
    import jax

    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas TPU kernels cannot run on the "
                       f"{backend!r} backend")
