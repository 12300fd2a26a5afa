"""Pluggable PE payloads: what a live processing element *does* per message.

A payload is an async callable ``(msg, clock) -> None`` awaited by the PE
task while it holds the message; when it returns, the message is complete.
Two built-ins:

- ``sleep`` — a calibrated timed wait: the PE occupies its slot for exactly
  ``msg.duration`` scenario seconds, so service times mirror the stream
  generator's distributions and the live runtime's scheduling dynamics are
  directly comparable to the discrete-event simulator.
- ``jax`` — runs the grouped-matmul Pallas kernel on the accelerator (a
  TPU; on a CPU only with ``interpret=True``, see ``kernels.dispatch``) in
  a worker thread per message, then pads with a calibrated sleep up to
  ``msg.duration``.  This exercises genuine dispatch/compute interleaving
  on the event loop: the master keeps brokering and the IRM keeps packing
  while the device computes.

A payload with ``on_device = True`` holds the accelerator, which belongs to
one process at a time, so it runs only in the process that owns the chip:
process-backed transports refuse it (``MultiprocTransport``).

Payloads resolve by name through ``make_payload`` so scenarios/CLI can
select them (``--payload jax``), mirroring ``core.binpack.make_packer``.
Each payload also exposes ``run_sync(msg, time_scale)``, the blocking
variant a process-backed transport executes on its worker-side PE threads
(``runtime.transport.MultiprocTransport``) — there the payload *is* the
worker's real, measurable CPU.
"""

from __future__ import annotations

import asyncio
import time
from typing import Callable, Dict

from ..obs.spans import span
from .annotations import worker_side

__all__ = ["SleepPayload", "JaxPayload", "make_payload", "PAYLOADS"]

# Largest |kernel - reference| allowed at warm-up, as a fraction of
# max |reference|.  The kernel takes f32 operands; if the MXU rounds them
# to bf16 (8-bit mantissa) the error over a 2048-long contraction is about
# 2e-3 of the largest output, so 2e-2 leaves a tenfold margin.
GMM_CHECK_RTOL = 2e-2


class SleepPayload:
    """Occupy the PE for ``msg.duration`` scenario seconds (timed wait)."""

    name = "sleep"
    on_device = False

    async def __call__(self, msg, clock) -> None:
        await clock.sleep(msg.duration)

    @worker_side
    def run_sync(self, msg, time_scale: float) -> None:
        """Blocking variant for a transport's worker-process PE thread."""
        if msg.duration > 0:
            time.sleep(msg.duration * time_scale)


class JaxPayload:
    """Run the grouped-matmul kernel per message, padded to ``msg.duration``.

    Each message triggers one ``kernels.grouped_matmul.gmm`` call with
    ``use_kernel=True`` (compiled on a TPU; on a CPU only when constructed
    with ``interpret=True``) in a thread executor — the event loop, master
    broker, and IRM stay live while the device computes — then sleeps
    whatever remains of the message's scenario-time duration so the
    *schedule* stays calibrated to the stream's service-time distribution
    regardless of device speed.  The warm-up call is checked against the
    jnp reference (``kernels/grouped_matmul/ref.py``).
    """

    name = "jax"
    on_device = True

    def __init__(self, experts: int = 4, rows: int = 64, dim: int = 64,
                 interpret: bool = False):
        # Import here so the live runtime stays usable without jax installed
        # (the sleep payload has no such dependency).
        import jax.numpy as jnp
        import numpy as np

        from ..kernels.grouped_matmul.ops import gmm

        self._gmm = gmm
        self._interpret = interpret
        rng = np.random.default_rng(0)
        self._x = jnp.asarray(
            rng.standard_normal((experts, rows, dim)), jnp.float32
        )
        self._w = jnp.asarray(
            rng.standard_normal((experts, dim, dim)), jnp.float32
        )
        self._sizes = jnp.full((experts,), rows, jnp.int32)
        # warm the jit cache outside any message's budget
        self.check_error = self._check(self._compute())

    @worker_side
    def _compute(self):
        return self._gmm(self._x, self._w, self._sizes, use_kernel=True,
                         interpret=self._interpret).block_until_ready()

    def _check(self, out) -> float:
        """Scale-normalized error of ``out`` against the f32 reference;
        raises when it exceeds ``GMM_CHECK_RTOL``."""
        import jax
        import numpy as np

        from ..kernels.grouped_matmul.ref import grouped_matmul_ref

        with jax.default_matmul_precision("highest"):
            ref = np.asarray(grouped_matmul_ref(self._x, self._w, self._sizes))
        err = float(np.abs(np.asarray(out) - ref).max()
                    / max(np.abs(ref).max(), 1e-30))
        if not err <= GMM_CHECK_RTOL:
            raise RuntimeError(
                f"grouped-matmul kernel disagrees with its reference: "
                f"max error {err:.3g} of max |ref| > {GMM_CHECK_RTOL}"
            )
        return err

    @worker_side
    def _kernel(self, msg_id: int):
        # ``_compute`` is looked up here, per call, so that a wrapper put
        # in its place after construction is the one timed
        with span("repro.payload.kernel", msg_id=msg_id):
            return self._compute()

    async def __call__(self, msg, clock) -> None:
        loop = asyncio.get_running_loop()
        wall0 = time.perf_counter()
        with span("repro.payload.call", msg_id=msg.msg_id):
            await loop.run_in_executor(None, self._kernel, msg.msg_id)
        spent_virtual = (time.perf_counter() - wall0) / clock.time_scale
        with span("repro.payload.pad"):
            await clock.sleep(msg.duration - spent_virtual)

    @worker_side
    def run_sync(self, msg, time_scale: float) -> None:
        """Blocking variant for a transport's worker-process PE thread:
        the kernel runs on the PE thread itself (that *is* the worker's
        CPU now), then pads to the message's calibrated duration."""
        wall0 = time.perf_counter()
        self._compute()
        remaining = msg.duration * time_scale - (time.perf_counter() - wall0)
        if remaining > 0:
            time.sleep(remaining)


PAYLOADS: Dict[str, Callable[[], object]] = {
    "sleep": SleepPayload,
    "jax": JaxPayload,
}


def make_payload(name: str, **kwargs):
    """Resolve a payload by name (mirrors ``core.binpack.make_packer``)."""
    try:
        factory = PAYLOADS[name]
    except KeyError:
        raise ValueError(
            f"unknown payload {name!r}; available: {sorted(PAYLOADS)}"
        ) from None
    return factory(**kwargs)
