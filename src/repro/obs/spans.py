"""Named host spans on the profiler's clock: the obs plane's side that a
``jax.profiler`` trace sees.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation``, a TraceMe
that the profiler records, with its ids as stats, on the clock of the
device planes; with no trace running it costs about a microsecond.  A
process that has not loaded JAX cannot be tracing, so there it is one
shared ``nullcontext`` and JAX is never imported for it: the sleep
payload runs without JAX.  ``open_span``/``close_span`` bound an interval
that begins and ends in different callbacks of the event-loop thread;
``set_stats`` gives an open span ids known only at its end.

Every name below is read by the chip benchmark's reduction
(``benchmarks/chip/program_trace.py``); the value says what reads it.
"""

from __future__ import annotations

import sys
from contextlib import nullcontext

__all__ = ["SPANS", "span", "open_span", "close_span", "set_stats"]

SPANS = {
    "repro.live.run": "batch_cold_start_ms; program_gaps",
    "repro.live.setup": "program_gaps",
    "repro.live.shutdown": "program_gaps",
    "repro.irm.step": "program_gaps",
    "repro.worker.boot": "program_gaps",
    "repro.pe.start": "program_gaps",
    "repro.payload.call": "executor_hop_ms",
    "repro.payload.kernel":
        "payload_kernel_ms; executor_hop_ms; batch_cold_start_ms",
    "repro.payload.pad": "program_gaps",
    "repro.data.pack": "pack_fill (its ``real_tokens`` and ``slots``)",
    "repro.train.batch": "batch_wait_ms",
}

_NULL = nullcontext()


def span(name: str, **ids):
    """A context manager that records ``name`` with ``ids`` in a trace."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return _NULL
    return prof.TraceAnnotation(name, **ids)


def open_span(name: str, **ids):
    """Start ``span(name, **ids)``; end it with ``close_span``."""
    s = span(name, **ids)
    s.__enter__()
    return s


def close_span(s) -> None:
    s.__exit__(None, None, None)


def set_stats(s, **ids) -> None:
    """Add ``ids`` to the open span ``s`` (nothing when not tracing)."""
    if s is not None:
        s.set_metadata(**ids)
