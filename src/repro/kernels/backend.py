"""Which way a kernel wrapper runs: compiled for the TPU, or interpreted on request."""
from __future__ import annotations

import jax


def resolve_interpret(name: str, interpret: bool) -> bool:
    """``interpret`` unchanged, or an error when a compiled kernel is asked for
    off-TPU: a wrapper never switches to the interpreter by itself.

    Raises:
        RuntimeError: ``interpret`` is false and the default backend is not a TPU.
    """
    backend = jax.default_backend()
    if not interpret and backend != "tpu":
        raise RuntimeError(
            f"{name}: the Pallas kernel compiles only for a TPU, and the default "
            f"backend is {backend!r}; pass interpret=True to run it in the "
            f"Pallas interpreter")
    return interpret
