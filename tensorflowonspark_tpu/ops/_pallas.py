"""Where the pallas kernels of this package run: compiled, or interpreted."""

from __future__ import annotations

import jax


def resolve_interpret(interpret=None):
    """``interpret`` for a ``pallas_call``: the caller's explicit choice,
    else False on a TPU backend and True on a CPU backend that was ASKED
    for (``JAX_PLATFORMS=cpu``: the tests, virtual-device rehearsals).

    Any other backend is an error.  The kernels are written for the TPU,
    and the interpreter is a test vehicle: a process that meant to own a
    chip and came up on something else must fail, not succeed slowly."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    asked = (jax.config.jax_platforms or "").split(",")[0].strip().lower()
    if backend == "cpu" and asked == "cpu":
        return True
    raise RuntimeError(
        f"pallas kernels need a TPU backend; jax came up on {backend!r} "
        f"with jax_platforms={jax.config.jax_platforms!r}.  Set "
        "JAX_PLATFORMS=cpu to run them interpreted on purpose.")
