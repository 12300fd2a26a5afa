"""The one rule for how a Pallas kernel runs.

On a TPU the kernel runs compiled.  Interpret mode, which executes the
kernel body on the host, runs only when the caller asks for it with
``interpret=True`` (tests on a CPU).  Asking for the kernel on any other
backend without that flag raises: a path that quietly fell back to the
interpreter would time, and ship, something other than the kernel.
"""

from __future__ import annotations

import jax

__all__ = ["KernelBackendError", "pallas_interpret"]


class KernelBackendError(RuntimeError):
    """A compiled Pallas kernel was requested off the TPU."""


def pallas_interpret(interpret: bool) -> bool:
    """The ``interpret`` flag to hand ``pl.pallas_call``.

    Called while tracing, so it reads the backend the jitted wrapper is
    being compiled for.
    """
    if interpret:
        return True
    backend = jax.default_backend()
    if backend != "tpu":
        raise KernelBackendError(
            f"the compiled Pallas kernel needs a TPU, but JAX's backend is "
            f"{backend!r}; pass interpret=True to run the kernel body on the "
            "host, or use_kernel=False for the jnp reference"
        )
    return False
